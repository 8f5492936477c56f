"""setup_galerkin_s: host seconds of the AMG setup's Galerkin products —
R = Pᵀ, A·P, R·(AP) and the pruning of the coarse operator, the program's
``amg.setup.galerkin`` spans (``repro.amg.spans``), summed over the levels
and over the process, which sets up once (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    ns = [s.duration_ns for s in spans.recent()
          if s.name == "amg.setup.galerkin"]
    return sum(ns) / 1e9 if ns else None
