"""setup_coarsen_s: host seconds of the AMG setup's coarsening stages —
strength, splitting and interpolation, the program's ``amg.setup.strength``,
``amg.setup.splitting`` and ``amg.setup.interp`` spans
(``repro.amg.spans``), summed over the levels and over the process, which
sets up once (program spans)."""
NAMES = ("amg.setup.strength", "amg.setup.splitting", "amg.setup.interp")


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    ns = [s.duration_ns for s in spans.recent() if s.name in NAMES]
    return sum(ns) / 1e9 if ns else None
