"""host_setup_s: seconds of AMGSolver.setup, the host AMG setup
(amg/hierarchy.py), from the harness's span around the call (host clock)."""


def read(run):
    return run.spans["host_setup_s"]
