"""refine_host_ms: host milliseconds a solve spends between the segments
of its float64 refinement — updating the answer and taking the float64
residual b − A·x, the program's ``amg.refine.residual`` spans
(``repro.amg.spans``) — summed over a solve's segments, mean over the
window's solves.  The window's solves are the last ``amg.refine`` spans,
one a request, so the warm-up is left out; a session that does not refine
has none and reads nothing (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    recent = spans.recent()
    solves = [s.id for s in recent if s.name == "amg.refine"]
    window = set(solves[len(solves) - len(run.window.requests):])
    ns = [s.duration_ns for s in recent
          if s.name == "amg.refine.residual" and s.parent_id in window]
    return sum(ns) / len(window) / 1e6 if ns else None
