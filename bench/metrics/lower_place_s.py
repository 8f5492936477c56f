"""lower_place_s: host seconds of placing the lowered level arrays on the
mesh (their cast to the session's precision and ``jax.device_put``), the
program's ``amg.lower.place`` span (``repro.amg.spans``), summed over the
process, which lowers once (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    ns = [s.duration_ns for s in spans.recent()
          if s.name == "amg.lower.place"]
    return sum(ns) / 1e9 if ns else None
