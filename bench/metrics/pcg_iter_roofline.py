"""pcg_iter_roofline: the least time the traced solves could take on one
of the cell's chips, over that chip's busy time inside them (mean over
the chips), in %.

The least time of a solve is its pcg_init and each of its iterations, each
the larger of least bytes over peak bandwidth and operations over peak
rate (bench/leastbytes.py, bench/peaks.json).  That is the whole system's
work, so on several chips each does at least its share of it: the least
time on one chip is the whole over the number of chips.  The fused device
programs stand in for the local products until the program names its
scopes."""


def read(run):
    if run.trace is None:
        return None
    busy_s = run.trace.mean("busy_in_solves_ns") / 1e9
    if busy_s <= 0:
        return None
    least_s = (sum(it + 1 for it in run.iterations)
               * run.work.seconds(run.peaks) / run.cell.chips)
    return 100.0 * least_s / busy_s
