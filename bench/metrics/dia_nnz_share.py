"""dia_nnz_share: the share, in %, of the hierarchy's ``A`` nonzeros that
the lowering put in the diagonal-offset (DIA) layout — the on-process
nonzeros of each level whose ``A`` took DIA, over all of ``A``'s
nonzeros on every level and device.  Read from the program's
``amg.lower.layout`` spans (``repro.amg.spans``, attributes ``nnz`` and
``dia_nnz``), one a level, summed over the process, which lowers once
(program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    layouts = [s.attrs for s in spans.recent()
               if s.name == "amg.lower.layout"]
    nnz = sum(a.get("nnz", 0) for a in layouts)
    if not nnz:
        return None         # a program that records no layout
    return 100.0 * sum(a.get("dia_nnz", 0) for a in layouts) / nnz
