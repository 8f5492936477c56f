"""lower_factors_s: host seconds of the lowering's numeric factors — each
level's D⁻¹, ρ(D⁻¹A) estimate and local square blocks, and the coarsest
level's dense pseudo-inverse, the program's ``amg.lower.factors`` spans
(``repro.amg.spans``), summed over the levels and over the process, which
lowers once (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    ns = [s.duration_ns for s in spans.recent()
          if s.name == "amg.lower.factors"]
    return sum(ns) / 1e9 if ns else None
