"""setup_s: seconds from the start of the process to the window's start —
generating the matrix, AMGSolver.setup, lowering, compile-cache loads and
the warm-up solve (host clock)."""


def read(run):
    return run.setup_s
