"""Figs. 1/3/5/16-20: strong/weak scaling of AMG setup+solve with standard
vs node-aware (model-selected) communication.

Local compute is measured once on this core and divided by the process
count (perfect-local-scaling assumption); communication is modeled per
topology with the paper's Blue Waters max-rate constants — reproducing the
paper's *relative* claims (comm share grows with scale; NAP extends strong
scaling; ~2-4× total speedups near the scaling limit)."""
import time

import numpy as np

from repro.amg import setup, spans, vcycle
from repro.amg.dist import analyze_hierarchy
from repro.amg.problems import grad_div_3d, laplace_3d
from repro.core import BLUE_WATERS, Topology

SOLVE_OPS = ("spmv_A", "restrict", "interp")
SETUP_OPS = ("spgemm_AP", "spgemm_PtAP")
N_CYCLES = 20  # solve iterations counted (typical for these systems)


def _phase_times(ops, phase_ops, pure: str):
    sel = 0.0
    std = 0.0
    for oc in ops:
        if oc.op not in phase_ops:
            continue
        sel += oc.selection.modeled_time
        std += oc.selection.times[pure]
    return std, sel


def _measure_local(A, h):
    b = A.matvec(np.ones(A.nrows))
    t0 = time.perf_counter()
    vcycle(h, b)
    solve_local = time.perf_counter() - t0
    setup_local = sum(s.duration_ns for s in spans.recent()
                      if s.name.startswith("amg.setup.")) / 1e9
    return setup_local, solve_local


def rows(system="graddiv", machine=BLUE_WATERS, weak=False):
    out = []
    A = grad_div_3d(10) if system == "graddiv" else laplace_3d(18)
    spans.clear()
    h = setup(A, solver="rs")
    setup_local, solve_local = _measure_local(A, h)
    procs_list = (256, 512, 1024, 2048, 4096)
    for p in procs_list:
        topo = Topology(n_nodes=p // machine.ppn, ppn=machine.ppn)
        ops = analyze_hierarchy(h, topo, machine)
        std_setup, sel_setup = _phase_times(ops, SETUP_OPS, "standard")
        std_solve, sel_solve = _phase_times(ops, SOLVE_OPS, "standard")
        std_solve *= N_CYCLES
        sel_solve *= N_CYCLES
        # weak scaling: constant local work per core (paper Fig. 20 keeps
        # ~10k dofs/core); strong scaling: local work divided across cores
        local_div = procs_list[0] if weak else p
        tag = "fig20" if weak else "fig16"
        for phase, std, sel, local in (
                ("setup", std_setup, sel_setup, setup_local),
                ("solve", std_solve, sel_solve, solve_local * N_CYCLES)):
            t_std = local / local_div + std
            t_nap = local / local_div + sel
            out.append((f"{tag}_{system}_{machine.name}_{phase}_p{p}_std",
                        t_std * 1e6, f"comm_share={std / t_std:.2f}"))
            out.append((f"{tag}_{system}_{machine.name}_{phase}_p{p}_nap",
                        t_nap * 1e6, f"speedup={t_std / t_nap:.2f}x"))
        loc_tot = (setup_local + solve_local * N_CYCLES) / local_div
        std_tot = loc_tot + std_setup + std_solve
        sel_tot = loc_tot + sel_setup + sel_solve
        out.append((f"fig17_{system}_{machine.name}_total_p{p}",
                    sel_tot * 1e6, f"speedup={std_tot / sel_tot:.2f}x"))
    return out
