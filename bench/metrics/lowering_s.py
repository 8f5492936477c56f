"""lowering_s: seconds of the first access to bound.dist_hierarchy, which
lowers the hierarchy onto the mesh (amg/dist_solve.py _lower_levels,
amg/dist_spmv.py), from the harness's span around it (host clock)."""


def read(run):
    return run.spans["lowering_s"]
