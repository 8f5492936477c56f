"""refine_drift: how far a refinement segment's float32 arithmetic drifts
from what the segment believes — the largest ratio, over the window's
segments, of the float64 true relative residual at a segment's end to the
one its own recursive residual predicts (attributes ``rel`` and
``rec_rel`` of the program's ``amg.refine.residual`` spans,
``repro.amg.spans``).  1 where they agree.  The window's solves are the
last ``amg.refine`` spans, one a request; a session that does not refine
has none and reads nothing (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    recent = spans.recent()
    solves = [s.id for s in recent if s.name == "amg.refine"]
    window = set(solves[len(solves) - len(run.window.requests):])
    ratios = [s.attrs["rel"] / s.attrs["rec_rel"] for s in recent
              if s.name == "amg.refine.residual" and s.parent_id in window
              and s.attrs.get("rec_rel")]
    return max(ratios) if ratios else None
