"""solve_staging_ms: host milliseconds a solve spends moving its vectors
between host and device — the right-hand side and initial guess scattered
onto the mesh and the answer gathered back, the program's
``amg.pcg.scatter`` and ``amg.pcg.gather`` spans (``repro.amg.spans``) —
mean over the window's solves.  The window's solves are the last
``amg.pcg`` spans, one a request, so the warm-up is left out (program
spans)."""
NAMES = ("amg.pcg.scatter", "amg.pcg.gather")


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    recent = spans.recent()
    solves = [s.id for s in recent if s.name == "amg.pcg"]
    window = set(solves[len(solves) - len(run.window.requests):])
    ns = [s.duration_ns for s in recent
          if s.name in NAMES and s.parent_id in window]
    return sum(ns) / len(window) / 1e6 if ns else None
