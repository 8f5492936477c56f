"""solve_p95_s: 95th percentile, by nearest rank, of each solve's wall
time from the call until the answer is on the host, over every solve of the
window (host clock).  Below 20 solves it is their maximum."""
import math


def read(run):
    ordered = sorted(r.seconds for r in run.window.requests)
    return ordered[math.ceil(0.95 * len(ordered)) - 1]
