"""solves_per_s: right-hand sides solved, each for the configuration's
fixed iteration count and held to its tolerance, over the whole window,
from its start to the return of the last solve that started inside it
(host clock)."""


def read(run):
    columns = int(run.cell.traffic.get("columns", 1))
    return columns * len(run.window.requests) / run.window.seconds
