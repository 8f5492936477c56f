"""iter_dispatch_ms: host milliseconds a PCG iteration takes to hand its
``pcg_step`` program to the device — the self time of the program's
``amg.pcg.step`` spans (``repro.amg.spans``), which end when the call
returns, before the device has finished — mean over the window's
iterations.  The window's solves are the last ``amg.pcg`` spans, one a
request, so the warm-up is left out (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    recent = spans.recent()
    solves = [s.id for s in recent if s.name == "amg.pcg"]
    window = set(solves[len(solves) - len(run.window.requests):])
    ns = [s.self_ns for s in recent
          if s.name == "amg.pcg.step" and s.parent_id in window]
    return sum(ns) / len(ns) / 1e6 if ns else None
