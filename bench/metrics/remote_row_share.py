"""remote_row_share: the share, in %, of the local rows of the operators
that exchange a halo over which their off-process product (``A_off·halo``)
runs — the rows that hold a halo entry where the lowering compacted the
off-part to a row list, every row where it did not.  Read from the
program's ``amg.lower.remote`` spans (``repro.amg.spans``, attributes
``rows`` and ``remote_rows``), one a level, summed over the process, which
lowers once (program spans)."""


def read(run):
    try:
        from repro.amg import spans
    except ImportError:
        return None         # a program without spans
    levels = [s.attrs for s in spans.recent()
              if s.name == "amg.lower.remote"]
    rows = sum(a.get("rows", 0) for a in levels)
    if not rows:
        return None         # no operator with a halo, or no such span
    return 100.0 * sum(a.get("remote_rows", 0) for a in levels) / rows
