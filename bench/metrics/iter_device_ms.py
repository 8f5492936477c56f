"""iter_device_ms: device busy time (the union of a chip's operation
intervals) inside the traced solves, over their PCG iterations, mean over
the chips (device trace)."""


def read(run):
    if run.trace is None or not sum(run.iterations):
        return None
    return run.trace.mean("busy_in_solves_ns") / sum(run.iterations) / 1e6
