"""device_idle: the share of the traced window in which no operation ran
on a chip, mean over the chips, in % (device trace)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.mean("busy_ns") / run.trace.window_ns)
