"""lower_plan_s: host seconds of the lowering's communication plans and
layouts — each level's comm graphs, strategy selection, halo plans and
ELL / block-ELL layout of A, P and R, the program's ``amg.lower.plan``
spans (``repro.amg.spans``), summed over the levels and over the process,
which lowers once (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    ns = [s.duration_ns for s in spans.recent()
          if s.name == "amg.lower.plan"]
    return sum(ns) / 1e9 if ns else None
