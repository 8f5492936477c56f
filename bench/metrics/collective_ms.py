"""collective_ms: device time of the all-to-all, all-gather, all-reduce,
reduce-scatter and collective-permute operations (the halo exchanges and
hierarchical reductions of core/nap_collectives.py) per PCG iteration,
mean over the chips (device trace)."""


def read(run):
    if run.trace is None or not sum(run.iterations):
        return None
    if not run.trace.mean("collective_ns"):
        return None      # no collective in the trace: nothing to read
    return run.trace.mean("collective_ns") / sum(run.iterations) / 1e6
