"""Distributed solve-phase tests.

Host-side (no extra devices): rectangular halo-plan/ELL correctness, the
per-level strategy-selection table, backend dispatch on a 1x1 mesh, and
the DIA lowering of a stencil's fine level (lossless, refreshed like a
fresh lowering, PCG held to the host).
Multi-device parity for all three strategies runs in a subprocess
(``dist_solve_script.py``) so this pytest process keeps one CPU device.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.amg import SolveOptions, pcg, setup, solve
from repro.amg.problems import laplace_3d, laplace_3d_7pt
from repro.core import BLUE_WATERS
from repro.core.topology import Partition, Topology

SCRIPT = pathlib.Path(__file__).parent / "dist_solve_script.py"
EXPECTED = [
    "OK solve_standard", "OK pcg_standard",
    "OK solve_nap2", "OK pcg_nap2",
    "OK solve_nap3", "OK pcg_nap3",
    "OK auto_select", "OK bcsr_path", "OK chebyshev",
    "OK cycle_smoother_parity", "OK multi_rhs_parity", "OK overlap_parity",
    "OK empty_halo", "OK comm_audit", "OK dist_setup_cycles", "OK multi_rhs",
    "OK streaming_refresh", "OK dia_layout",
    "ALL_OK",
]


@pytest.fixture(scope="module")
def rect_ops():
    """P and R DistOperators (all strategies) for a small RS hierarchy."""
    from repro.amg.dist import rect_vector_graph
    from repro.amg.dist_spmv import build_dist_operator

    A = laplace_3d_7pt(6)
    h = setup(A, solver="rs", max_coarse=30)
    P, R = h.levels[0].P, h.levels[0].R
    topo = Topology(n_nodes=2, ppn=2)
    fp = Partition.balanced(P.nrows, topo)
    cp = Partition.balanced(P.ncols, topo)
    out = []
    for M, rp_, cp_ in ((P, fp, cp), (R, cp, fp)):
        g = rect_vector_graph(M, rp_, cp_)
        for strat in ("standard", "nap2", "nap3"):
            op = build_dist_operator(M, 2, 2, strat, row_part=rp_,
                                     col_part=cp_, dtype=np.float64)
            out.append((M, g, op, strat))
    return out


def test_rect_halo_plan_and_ell_reconstruction(rect_ops):
    """The rectangular lowering is lossless: per-device ELL blocks with
    [local | halo] column remapping reassemble to the exact operator, and
    every halo slot maps to an owned entry of some other device."""
    for M, g, op, strat in rect_ops:
        dense = np.zeros(M.shape)
        x_local = op.plan.local_n
        for d in range(op.n_devices):
            rlo, rhi = op.row_part.local_range(d)
            clo, chi = op.col_part.local_range(d)
            need = np.sort(g.need[d])
            cols, vals = op.ell_cols[d], op.ell_vals[d]
            local = (cols >= 0) & (cols < x_local)
            halo = cols >= x_local
            # halo indices must be in range of this device's need array
            assert cols[halo].max(initial=0) - x_local < need.size + 1
            for i in range(rhi - rlo):
                for c, v in zip(cols[i], vals[i]):
                    if c < 0:
                        continue
                    gcol = clo + c if c < x_local else need[c - x_local]
                    dense[rlo + i, gcol] += v
        np.testing.assert_allclose(dense, M.to_dense(), atol=1e-12,
                                   err_msg=strat)


def test_rect_plan_halo_slots_are_offproc(rect_ops):
    """No device 'needs' x-entries it owns (the paper's no-self-comm rule)."""
    for M, g, op, strat in rect_ops:
        for d in range(op.n_devices):
            clo, chi = op.col_part.local_range(d)
            need = g.need[d]
            assert not ((need >= clo) & (need < chi)).any()


def test_dist_hierarchy_selection_table():
    """Every (level, op) row carries a chosen strategy + modeled times."""
    A = laplace_3d(8)
    h = setup(A, solver="rs")
    from repro.amg.dist_solve import DistHierarchy
    dh = DistHierarchy.build(h, 1, 1, params=BLUE_WATERS)
    rows = dh.selection_table()
    ops = {(r["level"], r["op"]) for r in rows}
    assert (0, "spmv_A") in ops
    for l in range(len(dh.levels) - 1):
        assert (l, "interp") in ops and (l, "restrict") in ops
    for r in rows:
        assert r["strategy"] in ("standard", "nap2", "nap3")
        if r["modeled"]:
            assert r["modeled"][r["strategy"]] == min(r["modeled"].values())
    assert "dist hierarchy" in dh.summary()


def test_backend_dispatch_single_device():
    """backend="dist" on a 1x1 mesh matches the host solver bit-for-fp32."""
    A = laplace_3d(8)
    h = setup(A, solver="rs")
    b = A.matvec(np.ones(A.nrows))
    from repro.amg.dist_solve import DistHierarchy
    dh = DistHierarchy.build(h, 1, 1, strategy="standard")
    res_h = pcg(h, b, tol=1e-5, maxiter=12)
    res_d = pcg(h, b, tol=1e-5, maxiter=12, backend="dist", dist=dh)
    assert res_d.converged
    n = min(len(res_h.residuals), len(res_d.residuals))
    r0 = res_h.residuals[0]
    for a, c in zip(res_h.residuals[:n], res_d.residuals[:n]):
        assert abs(a - c) / r0 < 2e-4
    with pytest.raises(ValueError):
        solve(h, b, backend="bogus")
    with pytest.raises(ValueError):
        pcg(h, b, backend="bogus")
    with pytest.raises(ValueError):
        solve(h, b, backend="dist")            # dist= is required
    with pytest.raises(ValueError):
        pcg(h, b, backend="dist", dist={"n_pods": 1})  # lanes missing


def _drift(A, seed=13):
    """Same pattern, new symmetric values."""
    from repro.amg.csr import CSR

    d = A.data * (1.0 + 0.05 * np.random.default_rng(seed).random(A.nnz))
    At = CSR(A.shape, A.indptr.copy(), A.indices.copy(), d).T
    return CSR(A.shape, A.indptr.copy(), A.indices.copy(), 0.5 * (d + At.data))


def _dia_entries(op, d):
    """(row, col, val) of device d's DIA lowering, stored zeros left out."""
    vals = op.dia_vals[d].reshape(len(op.dia_offsets), -1)[:, :op.rows_local]
    diag, r = np.nonzero(vals)
    c = r + np.asarray(op.dia_offsets)[diag]
    return sorted(zip(r.tolist(), c.tolist(), vals[diag, r].tolist()))


@pytest.mark.parametrize("n_pods,lanes", [(1, 1), (2, 2)])
def test_lower_dia_is_lossless_and_refreshes_like_a_fresh_lowering(
        n_pods, lanes):
    """Every on-process entry lands on its diagonal, per device (the
    off-part stays ELL), and a value refresh gives the arrays a fresh
    lowering of the new values gives."""
    from repro.amg.dist_spmv import build_dist_operator
    from repro.kernels.spmv.ops import select_dia

    A = laplace_3d(12, 6, 6)    # 3 whole planes of 36 rows a device on 2x2
    op = build_dist_operator(A, n_pods, lanes, "standard", dtype=np.float64)
    offsets = select_dia(op.on_cols)
    assert offsets is not None and len(offsets) == 27
    op.lower_dia(offsets)
    assert op.local_kernel == "dia"
    arrs = op.device_arrays()
    assert "dia" in arrs and "on_cols" not in arrs and "cols" not in arrs
    for d in range(op.n_devices):
        keep = op.on_cols[d] >= 0
        r = np.broadcast_to(np.arange(op.rows_local)[:, None],
                            keep.shape)[keep]
        want = sorted(zip(r.tolist(), op.on_cols[d][keep].tolist(),
                          op.on_vals[d][keep].tolist()))
        assert _dia_entries(op, d) == want
    A2 = _drift(A)
    op.refresh_values(lambda d: A2)
    fresh = build_dist_operator(A2, n_pods, lanes, "standard",
                                dtype=np.float64)
    fresh.lower_dia(select_dia(fresh.on_cols))
    assert op.dia_offsets == fresh.dia_offsets
    np.testing.assert_array_equal(op.dia_vals, fresh.dia_vals)
    np.testing.assert_array_equal(op.off_vals, fresh.off_vals)


def test_refresh_keeps_the_remote_row_list():
    """A value refresh re-gathers the compacted off-part on the frozen row
    list, and gives the row list and values of a fresh lowering of the new
    values, for A, P and R."""
    from repro.amg.csr import CSR
    from repro.amg.dist_spmv import build_dist_operator

    h = setup(laplace_3d(12, 6, 6), solver="rs", max_coarse=30)
    topo = Topology(n_nodes=2, ppn=2)
    fp = Partition.balanced(h.levels[0].A.nrows, topo)
    cp = Partition.balanced(h.levels[0].P.ncols, topo)
    rng = np.random.default_rng(4)
    for name, rp_, cp_ in (("A", fp, fp), ("P", fp, cp), ("R", cp, fp)):
        M = getattr(h.levels[0], name)
        M2 = CSR(M.shape, M.indptr.copy(), M.indices.copy(),
                 M.data * (1 + rng.random(M.nnz)))
        op = build_dist_operator(M, 2, 2, "nap2", row_part=rp_,
                                 col_part=cp_, dtype=np.float64)
        rows = op.off_rows
        assert rows is not None and op.remote_rows < op.rows_local, name
        op.refresh_values(lambda d: M2)
        fresh = build_dist_operator(M2, 2, 2, "nap2", row_part=rp_,
                                    col_part=cp_, dtype=np.float64)
        assert op.off_rows is rows
        np.testing.assert_array_equal(op.off_rows, fresh.off_rows)
        np.testing.assert_array_equal(op.off_ccols, fresh.off_ccols)
        np.testing.assert_array_equal(op.off_cvals, fresh.off_cvals)


REMOTE_ON_FOUR = """
import json
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.amg import setup
from repro.amg.dist_solve import DistHierarchy
from repro.amg.problems import laplace_3d

h = setup(laplace_3d(16), solver="rs")
rng = np.random.default_rng(0)
out = []
for strategy in ("auto", "standard", "nap2", "nap3"):
    dh = DistHierarchy.build(h, 2, 2, strategy=strategy, dtype=jnp.float64)
    spec = dh._dev_spec
    for l, (lv, dl) in enumerate(zip(h.levels, dh.levels)):
        for name in ("A", "P", "R"):
            op = getattr(dl, name)
            if op is None:
                continue
            M = getattr(lv, name)
            for k in (1, 8):
                x = rng.standard_normal((M.ncols,) if k == 1 else (M.ncols, k))
                xd = jax.device_put(op.scatter_x(x), dh._sharding)
                def body(x, a, op=op):
                    a = jax.tree_util.tree_map(lambda v: v[0], a)
                    return op.apply(a, x[0])[None]
                fn = jax.jit(jax.shard_map(
                    body, mesh=dh.mesh, in_specs=(spec, spec),
                    out_specs=spec, check_vma=False))
                y = op.gather_y(np.asarray(fn(xd, dh._arrs[l][name])))
                want = M.to_dense() @ x
                scale = np.abs(want).max()
                out.append(dict(
                    strategy=strategy, level=l, op=name, k=k,
                    halo=not op.halo_empty, compacted=op.off_rows is not None,
                    rows=op.rows_local, remote_rows=op.remote_rows,
                    host=float(np.abs(y - want).max() / scale)))
print("REMOTE", json.dumps(out))
"""


@pytest.fixture(scope="module")
def remote_on_four():
    """Every level's A, P and R applied on a 2×2 mesh of host devices, in
    float64, for each strategy and k = 1, 8."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = str(pathlib.Path(__file__).parents[1] / "src")
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", REMOTE_ON_FOUR],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("REMOTE "))
    return json.loads(line[len("REMOTE "):])


@pytest.mark.parametrize("k", [1, 8])
def test_overlapped_apply_matches_host_on_2x2(remote_on_four, k):
    """The overlapped apply — its off-process product over the compacted
    row list where there is one — equals the host product, for A, P and R
    on every level; the fine level's A runs over a row list, and an
    operator with a halo entry in every row runs over every row."""
    rows = [r for r in remote_on_four if r["k"] == k]
    assert len({(r["level"], r["op"]) for r in rows}) >= 7
    for r in rows:
        assert r["host"] < 1e-13, r
        assert r["remote_rows"] <= r["rows"]
        assert r["compacted"] == (r["halo"] and r["remote_rows"] < r["rows"])
    assert all(r["compacted"] for r in rows
               if r["level"] == 0 and r["op"] == "A")
    assert any(r["compacted"] for r in rows if r["op"] == "P")
    assert any(r["compacted"] for r in rows if r["op"] == "R")
    assert any(r["halo"] and not r["compacted"] for r in rows)


def test_fine_stencil_level_lowers_to_dia_on_one_device():
    """laplace_3d on a 1x1 mesh: L0's A takes DIA on its 27 diagonals, the
    Galerkin levels keep ELL or BCSR, one ``amg.lower.layout`` span a level
    says so, and dist PCG agrees with the host; after a value refresh it
    agrees with a fresh lowering."""
    from repro.amg import spans
    from repro.amg.dist_solve import DistHierarchy
    from repro.amg.hierarchy import refresh_values
    from repro.kernels.spmv.ops import select_dia

    A = laplace_3d(16)
    h = setup(A, solver="rs")
    before = {s.id for s in spans.recent()}
    dh = DistHierarchy.build(h, 1, 1)
    layout = [s.attrs for s in spans.recent()
              if s.id not in before and s.name == "amg.lower.layout"]
    rows = dh.kernel_table()
    assert rows[0]["kernel"] == "dia" and rows[0]["diagonals"] == 27
    assert dh.levels[0].local_kernel["kernel"] == "dia"
    assert [r["kernel"] for r in rows[1:]] == [
        dl.local_kernel["kernel"] for dl in dh.levels[1:]]
    for dl in dh.levels[1:]:
        assert dl.A.local_kernel in ("ell", "bcsr")
        if dl.A.local_kernel == "ell":      # too many offsets for DIA
            assert select_dia(dl.A.on_cols) is None
    assert [a["level"] for a in layout] == list(range(len(dh.levels)))
    assert [a["layout"] for a in layout] == [r["kernel"] for r in rows]
    assert layout[0]["diagonals"] == 27
    assert layout[0]["nnz"] == layout[0]["dia_nnz"] == A.nnz
    assert all(a["dia_nnz"] == 0 for a in layout[1:])
    assert [a["nnz"] for a in layout] == [lv.A.nnz for lv in h.levels]

    b = A.matvec(np.ones(A.nrows))
    res_h = pcg(h, b, tol=1e-6, maxiter=30)
    res_d = pcg(h, b, tol=1e-6, maxiter=30, backend="dist", dist=dh)
    assert res_d.converged and res_d.iterations == res_h.iterations
    r0 = res_h.residuals[0]
    n = min(len(res_h.residuals), len(res_d.residuals))
    for a, c in zip(res_h.residuals[:n], res_d.residuals[:n]):
        assert abs(a - c) / r0 < 2e-4

    A2 = _drift(A)
    refresh_values(h, A2)
    dh.refresh_values(h.levels)
    fresh = DistHierarchy.build(h, 1, 1)
    for dl, fl in zip(dh.levels, fresh.levels):
        assert dl.A.local_kernel == fl.A.local_kernel
        if dl.A.dia_offsets is not None:
            assert dl.A.dia_offsets == fl.A.dia_offsets
            np.testing.assert_array_equal(dl.A.dia_vals, fl.A.dia_vals)
    b2 = A2.matvec(np.ones(A.nrows))
    x_r = pcg(h, b2, tol=0.0, maxiter=8, backend="dist", dist=dh).x
    x_f = pcg(h, b2, tol=0.0, maxiter=8, backend="dist", dist=fresh).x
    np.testing.assert_array_equal(np.asarray(x_r), np.asarray(x_f))


def test_unstructured_operator_keeps_ell():
    """A random sparse operator has more distinct offsets than ELL slots:
    its A lowers op for op as ELL."""
    from repro.amg.csr import CSR
    from repro.amg.dist_solve import DistHierarchy

    rng = np.random.default_rng(2)
    n = 300
    dense = np.where(rng.random((n, n)) < 0.02, -rng.random((n, n)), 0.0)
    dense = dense + dense.T
    np.fill_diagonal(dense, -dense.sum(axis=1) + 1.0)
    r, c = np.nonzero(dense)
    A = CSR.from_coo(r, c, dense[r, c], (n, n))
    h = setup(A, solver="rs")
    dh = DistHierarchy.build(h, 1, 1)
    assert all(r["kernel"] != "dia" for r in dh.kernel_table())
    assert all("on_cols" in a["A"] and "dia" not in a["A"]
               for a in dh._arrs)


def test_cycle_comm_stats_counts_and_smoothers():
    """cycle_comm_stats: W doubles the coarse-visit message counts vs V on
    a ≥3-level hierarchy, chebyshev multiplies the per-sweep SpMVs, and the
    block smoothers compile + run through the 1x1 fused program."""
    A = laplace_3d(8)
    h = setup(A, solver="rs", max_coarse=30)
    assert h.n_levels >= 3
    from repro.amg.dist_solve import DistHierarchy, cycle_comm_stats
    dh = DistHierarchy.build(h, 1, 1, params=BLUE_WATERS)
    stV = cycle_comm_stats(dh, SolveOptions(cycle="V"))
    stW = cycle_comm_stats(dh, SolveOptions(cycle="W"))
    stF = cycle_comm_stats(dh, SolveOptions(cycle="F"))
    assert [e["visits"] for e in stV["per_level"]] == [1, 1, 1]
    assert [e["visits"] for e in stW["per_level"]] == [1, 2, 4]
    assert [e["visits"] for e in stF["per_level"]] == [1, 2, 3]
    # a 1x1 mesh communicates nothing; the structure must still be there
    assert stW["coarse_inter_msgs"] == 2 * stV["coarse_inter_msgs"]
    cheb = cycle_comm_stats(dh, SolveOptions(smoother="chebyshev",
                                             cheby_degree=3))
    assert cheb["cycle"] == "V" and cheb["smoother"] == "chebyshev"
    # block smoothers run end-to-end on the single-device mesh and the
    # two option sets share the lowered dense factors via _arrs_ex
    b = A.matvec(np.ones(A.nrows))
    for sm in ("block_jacobi", "hybrid_gs"):
        res = solve(h, b, tol=0.0, maxiter=3,
                    opts=SolveOptions(cycle="F", smoother=sm),
                    backend="dist", dist=dh)
        assert res.residuals[-1] < res.residuals[0]
    assert set(dh._arrs_ex) == {("bj", 4), ("gs", 0)}


@pytest.mark.slow
def test_benchmark_smoke_mode(tmp_path):
    """benchmarks/dist_solve.py --smoke runs in seconds and emits both the
    CSV rows and the BENCH_dist_solve.json record file."""
    env = dict(os.environ)
    root = pathlib.Path(__file__).parents[1]
    env["PYTHONPATH"] = str(root / "src") + os.pathsep + env.get("PYTHONPATH", "")
    out_json = tmp_path / "BENCH_dist_solve.json"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.dist_solve", "--smoke",
         "--out", str(out_json)],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    for strat in ("standard", "nap2", "nap3", "auto"):
        assert f"dist_solve_{strat}," in out.stdout
    # cycle×smoother sweep rows with coarse-level message counts
    for cycle in ("V", "W", "F"):
        for sm in ("jacobi", "chebyshev", "block_jacobi", "hybrid_gs"):
            assert f"dist_cycle_{cycle}_{sm}," in out.stdout
    assert "coarse_inter_msgs=" in out.stdout
    import json
    data = json.loads(out_json.read_text())
    assert data["benchmark"] == "dist_solve"
    assert any(r["name"].startswith("dist_solve_auto_L") for r in data["rows"])
    # weak-scaling sweep: ≥3 problem sizes recorded
    assert sum(r["name"].startswith("dist_weak_n") for r in data["rows"]) >= 3
    # cached-vs-cold AMGSolver sessions: the cached call must not pay the
    # DistHierarchy rebuild + recompile
    by_name = {r["name"]: r for r in data["rows"]}
    assert by_name["amg_solver_cached"]["us_per_call"] < \
        by_name["amg_solver_cold"]["us_per_call"]
    # streaming drift sweep: the value-only refresh must beat the full
    # re-setup the injected regression triggers, and the solve accounting
    # must land in the derived string for the check_bench gate
    assert by_name["streaming_refresh"]["us_per_call"] < \
        by_name["streaming_resetup"]["us_per_call"]
    for field in ("solves=", "refreshes=", "resetups=", "cached=",
                  "max_iters=", "triggers="):
        assert field in by_name["streaming_refresh"]["derived"]


@pytest.mark.slow
def test_multidevice_dist_solve_subprocess():
    env = dict(os.environ)
    root = str(pathlib.Path(__file__).parents[1] / "src")
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                         text=True, env=env, timeout=1800)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    for marker in EXPECTED:
        assert marker in out.stdout, f"missing {marker!r} in:\n{out.stdout}"
