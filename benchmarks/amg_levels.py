"""Fig. 2/4: per-level setup and solve cost, split into measured local
compute (this CPU) and modeled communication, for RS and SA hierarchies."""
import time
from collections import Counter

import numpy as np

from repro.amg import setup, spans, vcycle
from repro.amg.dist import analyze_hierarchy, phase_costs
from repro.amg.problems import laplace_3d
from repro.core import BLUE_WATERS, Topology


def rows(n=16, n_nodes=16, ppn=16):
    A = laplace_3d(n)
    topo = Topology(n_nodes=n_nodes, ppn=ppn)
    out = []
    for solver in ("rs", "sa"):
        spans.clear()
        h = setup(A, solver=solver)
        setup_ns = Counter()                 # host setup time of each level
        for s in spans.recent():
            if s.name.startswith("amg.setup."):
                setup_ns[s.attrs["level"]] += s.duration_ns
        ops = analyze_hierarchy(h, topo, BLUE_WATERS)
        costs = phase_costs(ops, h.n_levels)
        for l in range(h.n_levels):
            local_us = setup_ns[l] / 1e3 / topo.n_procs
            comm_us = costs["setup"][l]["selected"] * 1e6
            out.append((f"fig2_{solver}_setup_L{l}",
                        local_us + comm_us,
                        f"local={local_us:.0f};comm={comm_us:.0f};"
                        f"n={h.levels[l].A.nrows}"))
            comm_us = costs["solve"][l]["selected"] * 1e6
            out.append((f"fig4_{solver}_solve_L{l}", comm_us,
                        f"comm_per_cycle={comm_us:.0f}"))
        # one measured V-cycle (local compute on this core)
        b = A.matvec(np.ones(A.nrows))
        t0 = time.perf_counter()
        vcycle(h, b)
        out.append((f"fig4_{solver}_vcycle_local", (time.perf_counter() - t0)
                    * 1e6, "measured 1-core"))
    return out
