"""Multi-device distributed solve validation — run as a SUBPROCESS by
test_dist_solve.py (device count must be set before jax init).

Asserts that the device-resident ``backend="dist"`` V-cycle / stationary /
PCG solves reproduce the host backend's residual histories to fp32
tolerance for every halo strategy, that per-level model selection picks a
non-standard strategy somewhere in the hierarchy, that the Pallas ELL
kernel route agrees with the inline form, that an fp64 ``AMGSolver``
session's batched multi-RHS dist solve matches per-column host solves to
1e-7 relative residual on the full 2x4 mesh, and that a stencil's fine
level lowers to DIA on a 2x2 and a 2x4 mesh and solves like the host.
Prints "OK <check>" per passing check; any exception fails the run.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)   # for the fp64 multi-RHS check

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.amg import AMGConfig, AMGSolver, SolveOptions, pcg, setup, solve  # noqa: E402
from repro.amg.dist_solve import DistHierarchy, cycle_comm_stats  # noqa: E402
from repro.amg.problems import laplace_3d  # noqa: E402
from repro.amg.solve import CYCLES, SMOOTHERS  # noqa: E402
from repro.core import BLUE_WATERS  # noqa: E402

N_PODS, LANES = 2, 4
TOL = 2e-4   # normalized-by-r0 fp32 tolerance


def history_diff(a, b):
    n = min(len(a), len(b))
    r0 = a[0] or 1.0
    return max(abs(x - y) / r0 for x, y in zip(a[:n], b[:n]))


def main():
    A = laplace_3d(8)
    h = setup(A, solver="rs")
    b = A.matvec(np.ones(A.nrows))
    res_h = solve(h, b, tol=1e-5, maxiter=12)
    pcg_h = pcg(h, b, tol=1e-5, maxiter=12)

    for strat in ("standard", "nap2", "nap3"):
        dh = DistHierarchy.build(h, N_PODS, LANES, strategy=strat)
        res_d = solve(h, b, tol=1e-5, maxiter=12, backend="dist", dist=dh)
        assert history_diff(res_h.residuals, res_d.residuals) < TOL, strat
        print(f"OK solve_{strat}")
        pcg_d = pcg(h, b, tol=1e-5, maxiter=12, backend="dist", dist=dh)
        assert history_diff(pcg_h.residuals, pcg_d.residuals) < TOL, strat
        assert pcg_d.converged
        print(f"OK pcg_{strat}")

    # model-driven per-level selection: coarse levels must go node-aware
    dh = DistHierarchy.build(h, N_PODS, LANES, params=BLUE_WATERS)
    chosen = {r["strategy"] for r in dh.selection_table()}
    assert chosen - {"standard"}, dh.summary()
    res_d = solve(h, b, tol=1e-5, maxiter=12, backend="dist", dist=dh)
    assert history_diff(res_h.residuals, res_d.residuals) < TOL
    print("OK auto_select")

    # block-ELL route inside the fused cycle: force every smoothing level's
    # A onto BCSR (bs=8) and hold PCG to the host history
    import repro.amg.dist_solve as ds
    pick = ds.select_dist_kernel
    ds.select_dist_kernel = lambda cols: dict(pick(cols), kernel="bcsr",
                                              block_size=8)
    try:
        dh_b = DistHierarchy.build(h, N_PODS, LANES, strategy="nap3")
    finally:
        ds.select_dist_kernel = pick
    assert all(dl.A.local_kernel == "bcsr" for dl in dh_b.levels[:-1])
    pcg_b = pcg(h, b, tol=1e-5, maxiter=12, backend="dist", dist=dh_b)
    assert history_diff(pcg_h.residuals, pcg_b.residuals) < TOL
    print("OK bcsr_path")

    # chebyshev smoother parity through the same fused program
    oc = SolveOptions(smoother="chebyshev")
    ch = solve(h, b, tol=1e-5, maxiter=10, opts=oc)
    dh3 = DistHierarchy.build(h, N_PODS, LANES, strategy="nap3")
    cd = solve(h, b, tol=1e-5, maxiter=10, opts=oc, backend="dist", dist=dh3)
    assert history_diff(ch.residuals, cd.residuals) < TOL
    print("OK chebyshev")

    # EVERY (cycle, smoother) pair — including the symmetric-sweep hybrid
    # GS — as ONE fused fp64 shard_map program on the 2x4 mesh, ≤1e-7
    # residual parity with the host reference (block smoothers: the host
    # mimics the 8-device partition) and a monotone 5-iteration residual
    # decline — the dist half of the property test
    h3 = setup(A, solver="rs", max_coarse=30)   # ≥3 levels so W/F differ
    assert h3.n_levels >= 3, h3.n_levels
    dh64 = DistHierarchy.build(h3, N_PODS, LANES, params=BLUE_WATERS,
                               dtype=jnp.float64)
    for cycle in CYCLES:
        for sm in SMOOTHERS:
            o = SolveOptions(cycle=cycle, smoother=sm,
                             smoother_parts=N_PODS * LANES)
            rh = solve(h3, b, tol=0.0, maxiter=5, opts=o)
            rd = solve(h3, b, tol=0.0, maxiter=5, opts=o, backend="dist",
                       dist=dh64)
            hd = history_diff(rh.residuals, rd.residuals)
            assert hd < 1e-7, (cycle, sm, hd)
            assert all(rd.residuals[i + 1] < rd.residuals[i]
                       for i in range(5)), (cycle, sm, rd.residuals)
    # W/F multiply exactly the coarse-level messages (modeled counts)
    stV = cycle_comm_stats(dh64, SolveOptions(cycle="V"))
    stW = cycle_comm_stats(dh64, SolveOptions(cycle="W"))
    assert stW["coarse_inter_msgs"] == 2 * stV["coarse_inter_msgs"] > 0, \
        (stV, stW)
    print("OK cycle_smoother_parity")

    # one batched [n, 4] cycle per (cycle, smoother) pair on the same fp64
    # 2x4 mesh: the native multi-RHS SpMM trace equals the four single-RHS
    # cycles column for column, and the overlapped on/off-process split
    # (exchange issued before A_on·x, then + A_off·halo) equals the host
    # cycle of each column, both ≤1e-7.  The heuristic must have lowered at
    # least one level to BCSR so the block path is covered.
    from repro.amg.dist_solve import dist_vcycle
    from repro.amg.solve import host_cycle

    assert any(r["kernel"] == "bcsr" for r in dh64.kernel_table()), \
        dh64.kernel_table()
    assert any(not r["halo_empty"] for r in dh64.kernel_table()), \
        "hierarchy must actually communicate somewhere"
    Bm = np.stack([b] + [np.random.default_rng(3).standard_normal(A.nrows)
                         for _ in range(3)], axis=1)
    for cycle in CYCLES:
        for sm in SMOOTHERS:
            o = SolveOptions(cycle=cycle, smoother=sm,
                             smoother_parts=N_PODS * LANES)
            xm = dist_vcycle(dh64, Bm, o)
            xc = np.stack([dist_vcycle(dh64, Bm[:, j], o)
                           for j in range(Bm.shape[1])], axis=1)
            xh = np.stack([host_cycle(h3, Bm[:, j], opts=o)
                           for j in range(Bm.shape[1])], axis=1)
            scale = max(np.abs(xh).max(), 1e-30)
            md = np.abs(xm - xc).max() / scale
            assert md < 1e-7, (cycle, sm, md)
            hd = np.abs(xm - xh).max() / scale
            assert hd < 1e-7, (cycle, sm, hd)
    print("OK multi_rhs_parity")
    print("OK overlap_parity")

    # 1-device-per-node mesh (8x1): a block-diagonal operator aligned to
    # the partition has an empty halo on every device — the lowered apply
    # must contain NO collective at all, and still match the dense product
    from repro.amg.csr import CSR
    from repro.amg.dist_spmv import build_dist_spmv
    from repro.core.topology import Partition, Topology

    nE = 96
    partE = Partition.balanced(nE, Topology(n_nodes=8, ppn=1))
    rngE = np.random.default_rng(0)
    denseE = np.zeros((nE, nE))
    for d in range(8):
        lo, hi = partE.local_range(d)
        denseE[lo:hi, lo:hi] = rngE.normal(size=(hi - lo, hi - lo))
    rE, cE = np.nonzero(denseE)
    spE = build_dist_spmv(CSR.from_coo(rE, cE, denseE[rE, cE], (nE, nE)),
                          8, 1, "standard", dtype=np.float64)
    assert spE.op.halo_empty and spE.op.onoff_nnz()["off_nnz"] == 0
    from repro.analysis import audit_jaxpr, collect_collectives

    jxp = jax.make_jaxpr(spE.fn)(jnp.zeros((8, spE.op.plan.local_n),
                                           dtype=jnp.float64))
    assert collect_collectives(jxp) == []          # structural, not substring
    assert audit_jaxpr(jxp, "apply_A",
                       expected_signature=spE.op.expected_signature).ok
    xE = rngE.normal(size=nE)
    np.testing.assert_allclose(spE.matvec(xE), denseE @ xE, rtol=0,
                               atol=1e-11)
    print("OK empty_halo")

    # comm audit on the real 2x4 mesh: every fused program of every
    # (cycle, smoother) pair plus PCG and the *_m variants lowers exactly
    # the collectives its selected strategies predict, every per-operator
    # apply matches its ordered halo signature (with the on-process
    # contraction dataflow-independent of the exchange), and the modeled
    # cycle_comm_stats counters agree with the static plans
    from repro.analysis import audit_hierarchy
    from repro.core.nap_collectives import (HALO_SIGNATURES,
                                            REDUCE_SIGNATURES)

    audits, violations = audit_hierarchy(dh64)
    assert not violations, [str(v) for v in violations]
    assert len(audits) >= 15 * 2 + 10, len(audits)
    # golden ordered signatures on the 2x4 mesh: the finest A communicates
    # with its selected strategy's exact lowering
    sigA = [a for a in audits if a.program == "apply_A" and a.level == 0]
    assert sigA and sigA[0].signature() == HALO_SIGNATURES[
        dh64.levels[0].A.strategy]
    # NAP-3 hier_psum shows up in resid_norm as RS(fast)+AR(slow)+AG(fast)
    rn = next(a for a in audits if a.program == "resid_norm")
    assert all(rn.counts.get(p, 0) >= 1
               for p in REDUCE_SIGNATURES["nap3"]), rn.counts
    # injected regression: silently lowering hier_psum to a flat psum must
    # be caught as a count mismatch on a freshly built hierarchy
    import repro.amg.dist_solve as _ds
    from repro.analysis import audit_program

    orig_hier_psum = _ds.hier_psum
    _ds.hier_psum = lambda x, slow, fast, strategy="nap3": \
        jax.lax.psum(x, (slow, fast))
    try:
        dh_bad = DistHierarchy.build(h3, N_PODS, LANES, params=BLUE_WATERS,
                                     dtype=jnp.float64)
        bad = audit_program(dh_bad, "resid_norm")
        kinds = [v.kind for v in bad.violations]
        assert "count-mismatch" in kinds, (kinds, bad.counts, bad.expected)
    finally:
        _ds.hier_psum = orig_hier_psum
    print("OK comm_audit")

    # the symmetric hybrid GS sweep is an SPD preconditioner: dist PCG with
    # it converges on the 2x4 mesh and matches the host PCG history ≤1e-7
    osym = SolveOptions(smoother="hybrid_gs_sym",
                        smoother_parts=N_PODS * LANES)
    ph = pcg(h3, b, tol=1e-8, maxiter=30, opts=osym)
    pd = pcg(h3, b, tol=1e-8, maxiter=30, opts=osym, backend="dist",
             dist=dh64)
    assert ph.converged and pd.converged, (ph.iterations, pd.iterations)
    assert history_diff(ph.residuals, pd.residuals) < 1e-7
    # 2 SpMVs/sweep lands in the modeled comm counts
    assert (cycle_comm_stats(dh64, osym)["inter_msgs"]
            > cycle_comm_stats(dh64, SolveOptions(smoother="hybrid_gs"))
            ["inter_msgs"])
    print("OK hybrid_gs_sym_pcg")

    # AMGService cross-burst coalescing on the 2x4 mesh: k same-matrix
    # requests submitted in separate bursts inside one window must ride
    # ONE multi-RHS device trace and match per-request host solves ≤1e-7
    import time as _time

    from repro.amg import AMGService

    svc = AMGService(AMGConfig(backend="dist", n_pods=N_PODS, lanes=LANES,
                               machine="blue_waters", dtype="float64"),
                     max_rhs=8, coalesce_window=1.5)
    svc.register("lap", A)
    rng = np.random.default_rng(11)
    bs = [b] + [rng.standard_normal(A.nrows) for _ in range(2)]
    with svc:
        tickets = []
        for bi in bs:                       # three separate bursts
            tickets.append(svc.submit("lap", bi, method="solve", tol=0.0,
                                      maxiter=12))
            _time.sleep(0.05)
        xs = [t.result(timeout=300) for t in tickets]
    assert svc.stats["batches"] == 1, svc.stats     # ONE device trace
    assert svc.stats["batched_rhs"] == len(bs), svc.stats
    for bi, xi, t in zip(bs, xs, tickets):
        href = solve(h, bi, tol=0.0, maxiter=12)
        xd = np.linalg.norm(xi - href.x) / np.linalg.norm(href.x)
        assert xd < 1e-7, (t.rid, xd)
        assert t.diagnostics["batch_cols"] == len(bs)
    print("OK service_cross_burst_coalescing")

    # the setup_backend="dist" session (hierarchy=None, levels born
    # partitioned) drives the same W-cycle + block-Jacobi fused program
    cfg_w = AMGConfig(setup_backend="dist", backend="dist", n_pods=N_PODS,
                      lanes=LANES, machine="blue_waters", dtype="float64",
                      opts=SolveOptions(cycle="W", smoother="block_jacobi",
                                        smoother_parts=N_PODS * LANES))
    bound_w = AMGSolver(cfg_w).setup(A)
    assert bound_w.hierarchy is None
    rw = bound_w.solve(b, tol=0.0, maxiter=5)
    rh = solve(h, b, tol=0.0, maxiter=5, opts=cfg_w.opts)
    assert history_diff(rh.residuals, rw.residuals) < 1e-7
    print("OK dist_setup_cycles")

    # fp64 AMGSolver session: a [n, 4] multi-RHS dist solve batched through
    # one device trace matches 4 independent host solves to 1e-7 relative
    # residual (the PR-1 parity bar), with ONE DistHierarchy build.
    from repro.amg.api import clear_sessions

    clear_sessions()      # the service above shared this config's setup
    builds = []
    orig_build = DistHierarchy.build.__func__
    DistHierarchy.build = classmethod(
        lambda cls, *a, **k: builds.append(1) or orig_build(cls, *a, **k))
    cfg = AMGConfig(backend="dist", n_pods=N_PODS, lanes=LANES,
                    machine="blue_waters", dtype="float64")
    bound = AMGSolver(cfg).setup(A)
    rng = np.random.default_rng(7)
    B = np.stack([b] + [rng.standard_normal(A.nrows) for _ in range(3)],
                 axis=1)
    mres = bound.solve(B, tol=0.0, maxiter=12)
    assert bound.solve(b, tol=1e-5, maxiter=12).converged  # second call
    assert builds == [1], f"expected one DistHierarchy build, got {builds}"
    assert len(bound.dist_hierarchy._programs) == 1
    for j in range(B.shape[1]):
        href = solve(h, B[:, j], tol=0.0, maxiter=12)
        hd = history_diff(href.residuals, mres.columns[j].residuals)
        xd = (np.linalg.norm(mres.x[:, j] - href.x)
              / np.linalg.norm(href.x))
        assert hd < 1e-7 and xd < 1e-7, (j, hd, xd)
    DistHierarchy.build = classmethod(orig_build)
    print("OK multi_rhs")

    # streaming refresh on the fp64 2x4 mesh: bound.update(A2) keeps the
    # SAME lowered DistHierarchy (comm graphs, NAP selections, compiled
    # programs) while the refreshed PCG matches a fresh setup(A2) session
    # ≤1e-7; an injected convergence regression then triggers exactly one
    # adaptive re-setup
    from repro.amg.api import LRUPolicy, SessionStore

    store_s = SessionStore(LRUPolicy())
    cfg_s = AMGConfig(backend="dist", n_pods=N_PODS, lanes=LANES,
                      machine="blue_waters", dtype="float64", tol=1e-9)
    bound_s = AMGSolver(cfg_s, store=store_s).setup(A)
    base_its = bound_s.pcg(b).iterations
    dh_before = bound_s.dist_hierarchy
    progs_before = dict(bound_s.dist_hierarchy._programs)
    rng_s = np.random.default_rng(13)
    d2 = A.data * (1.0 + 0.02 * rng_s.random(A.nnz))
    At = CSR(A.shape, A.indptr.copy(), A.indices.copy(), d2).T
    A2 = CSR(A.shape, A.indptr.copy(), A.indices.copy(),
             0.5 * (d2 + At.data))
    assert bound_s.update(A2) == "refresh"
    assert bound_s.dist_hierarchy is dh_before
    assert all(bound_s.dist_hierarchy._programs.get(k) is v
               for k, v in progs_before.items())   # programs reused verbatim
    x_r = np.asarray(bound_s.pcg(b).x)
    clear_sessions()
    x_f = np.asarray(AMGSolver(cfg_s).setup(A2).pcg(b).x)
    rd = np.abs(x_r - x_f).max() / max(np.abs(x_f).max(), 1e-30)
    assert rd < 1e-7, rd
    assert A.data is not A2.data and bound_s._fine is not A2  # copy-on-write
    bound_s.last_iterations = 10 * base_its + 100  # inject a regression
    assert bound_s.update(A2) == "resetup"
    st_s = store_s.stats()
    assert st_s["resetups"] == 1 and st_s["refreshes"] == 1, st_s
    assert st_s["triggers"] == {"drift": 1, "regression": 1}, st_s
    assert bound_s.pcg(b).converged
    print("OK streaming_refresh")

    dia_layout()
    print("ALL_OK")


def dia_layout():
    """The 27-point stencil with three whole grid planes a device: L0's A
    lowers to DIA on 27 diagonals on a 2x2 mesh (four of the eight
    devices) and on 2x4, the Galerkin levels do not, PCG agrees with the
    host, forcing BCSR still lowers every smoothing level to BCSR, and a
    value refresh equals a fresh lowering."""
    from repro.amg.csr import CSR
    from repro.amg.dist_solve import DEV_AXES
    from repro.amg.hierarchy import refresh_values

    A = laplace_3d(24, 12, 12)
    h = setup(A, solver="rs")
    b = A.matvec(np.ones(A.nrows))
    ref = pcg(h, b, tol=1e-6, maxiter=30)
    mesh22 = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                               DEV_AXES)
    for pods, lanes, mesh in ((2, 2, mesh22), (N_PODS, LANES, None)):
        dh = DistHierarchy.build(h, pods, lanes, mesh=mesh)
        rows = dh.kernel_table()
        assert rows[0]["kernel"] == "dia" and rows[0]["diagonals"] == 27, rows
        assert not rows[0]["halo_empty"]
        assert all(r["kernel"] != "dia" for r in rows[1:]), rows
        got = pcg(h, b, tol=1e-6, maxiter=30, backend="dist", dist=dh)
        assert got.converged and got.iterations == ref.iterations
        assert history_diff(ref.residuals, got.residuals) < TOL

    import repro.amg.dist_solve as ds
    pick = ds.select_dist_kernel
    ds.select_dist_kernel = lambda cols: dict(pick(cols), kernel="bcsr",
                                              block_size=8)
    try:
        dh_b = DistHierarchy.build(h, 2, 2, mesh=mesh22)
    finally:
        ds.select_dist_kernel = pick
    assert all(dl.A.local_kernel == "bcsr" for dl in dh_b.levels[:-1])

    dh = DistHierarchy.build(h, 2, 2, mesh=mesh22)
    rng = np.random.default_rng(5)
    d2 = A.data * (1.0 + 0.05 * rng.random(A.nnz))
    At = CSR(A.shape, A.indptr.copy(), A.indices.copy(), d2).T
    A2 = CSR(A.shape, A.indptr.copy(), A.indices.copy(), 0.5 * (d2 + At.data))
    refresh_values(h, A2)
    dh.refresh_values(h.levels)
    fresh = DistHierarchy.build(h, 2, 2, mesh=mesh22)
    assert dh.levels[0].A.dia_offsets == fresh.levels[0].A.dia_offsets
    np.testing.assert_array_equal(dh.levels[0].A.dia_vals,
                                  fresh.levels[0].A.dia_vals)
    b2 = A2.matvec(np.ones(A.nrows))
    x_r = pcg(h, b2, tol=0.0, maxiter=8, backend="dist", dist=dh).x
    x_f = pcg(h, b2, tol=0.0, maxiter=8, backend="dist", dist=fresh).x
    np.testing.assert_array_equal(np.asarray(x_r), np.asarray(x_f))
    print("OK dia_layout")


if __name__ == "__main__":
    main()
