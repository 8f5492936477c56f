"""On/off-process operator splitting + overlap-aware selection (host side).

Everything here runs on the host or a 1×1 mesh — the split itself is pure
numpy lowering, and the overlap-aware cost model is arithmetic.  The
multi-device end-to-end parity (the overlapped apply against the host
cycle across all 15 cycle×smoother pairs) and the 1-device-per-node
empty-halo no-collective check run in the 8-device subprocess
(tests/dist_solve_script.py, "OK overlap_parity" / "OK empty_halo").
"""
import numpy as np
import pytest

from repro.amg.csr import CSR
from repro.amg.dist_spmv import build_dist_operator
from repro.core.perf_model import (BLUE_WATERS, MachineParams,
                                   overlap_efficiency, overlap_time,
                                   spmv_compute_times)
from repro.core.selector import select
from repro.core.topology import Partition, Topology
from repro.amg.dist import rect_vector_graph

N_PODS, LANES = 2, 4


def _random_csr(n=96, seed=0, fill=0.08):
    """A band of half-width 3 plus random fill: with fill every row of a
    device reaches another device's columns, without it only the three
    rows next to each device boundary do."""
    rng = np.random.default_rng(seed)
    band = (np.abs(np.subtract.outer(np.arange(n), np.arange(n))) <= 3)
    dense = band * rng.normal(size=(n, n))
    dense += np.where(rng.random((n, n)) < fill, rng.normal(size=(n, n)), 0.0)
    r, c = np.nonzero(dense)
    return CSR.from_coo(r, c, dense[r, c], (n, n)), dense


def _ell_entries(cols, vals, rows=None):
    """Multiset of (row, col, val) triples of one device's ELL block; row
    ``i`` of the block is local row ``rows[i]`` where a row list is given."""
    keep = cols >= 0
    ids = np.arange(cols.shape[0]) if rows is None else rows
    r = np.broadcast_to(ids[:, None], cols.shape)[keep]
    return sorted(zip(r.tolist(), cols[keep].tolist(), vals[keep].tolist()))


@pytest.mark.parametrize("fill", [0.08, 0.0])
@pytest.mark.parametrize("strategy", ["standard", "nap2", "nap3"])
def test_split_partitions_fused_entries_exactly(strategy, fill):
    """A_on (local ids) + A_off (halo ids, rebased) must hold *exactly* the
    fused block's entries: on = fused entries with col < x_local, off = the
    rest shifted by x_local — per device, as multisets.  Where some row
    holds no halo entry the off-part is also compacted: its row list holds
    every row with a halo entry, padded with rows that hold none, sorted
    and without repeats, and the compacted rows mapped back through it hold
    exactly the off entries."""
    A, _ = _random_csr(fill=fill)
    op = build_dist_operator(A, N_PODS, LANES, strategy, dtype=np.float64)
    x_local = op.plan.local_n
    has = (op.off_cols >= 0).any(axis=2)
    assert (op.off_rows is None) == bool(fill)
    R_off = op.remote_rows
    assert R_off == (op.rows_local if fill else has.sum(axis=1).max())
    for d in range(op.n_devices):
        fused = _ell_entries(op.ell_cols[d], op.ell_vals[d])
        want_on = [e for e in fused if e[1] < x_local]
        want_off = [(r, c - x_local, v) for r, c, v in fused if c >= x_local]
        assert _ell_entries(op.on_cols[d], op.on_vals[d]) == want_on
        assert _ell_entries(op.off_cols[d], op.off_vals[d]) == want_off
        if op.off_rows is None:
            continue
        rows = op.off_rows[d]
        assert rows.shape == (R_off,) and np.all(np.diff(rows) > 0)
        assert set(np.flatnonzero(has[d])) <= set(rows.tolist())
        assert _ell_entries(op.off_ccols[d], op.off_cvals[d], rows) \
            == want_off


def test_split_numeric_parity_per_device():
    """A_on·x + A_off·halo == A_local·[x | halo] (host arithmetic, fp64)."""
    A, dense = _random_csr(seed=3)
    op = build_dist_operator(A, N_PODS, LANES, "standard", dtype=np.float64)
    part = op.col_part
    rng = np.random.default_rng(7)
    x = rng.normal(size=A.ncols)

    def ell_apply(cols, vals, src):
        keep = cols >= 0
        return np.where(keep, vals * src[np.maximum(cols, 0)], 0.0).sum(axis=1)

    graph = rect_vector_graph(A, part, part)
    for d in range(op.n_devices):
        lo, hi = part.local_range(d)
        x_loc = np.zeros(op.plan.local_n)
        x_loc[: hi - lo] = x[lo:hi]
        halo = np.zeros(op.plan.halo_len)
        need = np.sort(graph.need[d])
        halo[: need.size] = x[need]
        fused = ell_apply(op.ell_cols[d], op.ell_vals[d],
                          np.concatenate([x_loc, halo]))
        split = (ell_apply(op.on_cols[d], op.on_vals[d], x_loc)
                 + ell_apply(op.off_cols[d], op.off_vals[d], halo))
        np.testing.assert_allclose(split, fused, rtol=0, atol=1e-13)
        # and both match the dense row block
        y = dense[lo:hi] @ x
        np.testing.assert_allclose(split[: hi - lo], y, rtol=0, atol=1e-12)


@pytest.mark.parametrize("strategy", ["standard", "nap2", "nap3"])
def test_remote_rows_add_what_every_row_adds(strategy):
    """The compacted off-process product equals the full-row one bit for
    bit, in float32, on every device: the end devices' padded rows and
    every row without a halo entry add nothing.  Integer values keep every
    sum exact, so the two agree whether or not the compiler fuses a
    multiply into an add."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.amg.dist_spmv import remote_apply
    A, _ = _random_csr(seed=17, fill=0.0)
    A = CSR(A.shape, A.indptr, A.indices, np.round(4 * A.data))
    op = build_dist_operator(A, N_PODS, LANES, strategy)
    counts = (op.off_cols >= 0).any(axis=2).sum(axis=1)
    assert counts.min() < op.remote_rows < op.rows_local   # end devices pad
    rng = np.random.default_rng(2)
    for k in ((), (8,)):
        for d in range(op.n_devices):
            y = jnp.asarray(rng.integers(-9, 10, (op.rows_local,) + k),
                            jnp.float32)
            halo = jnp.asarray(rng.integers(-9, 10, (op.plan.halo_len,) + k),
                               jnp.float32)
            full = remote_apply(y, op.off_cols[d], op.off_vals[d], halo)
            rows = remote_apply(y, op.off_ccols[d], op.off_cvals[d], halo,
                                op.off_rows[d])
            np.testing.assert_array_equal(np.asarray(rows), np.asarray(full))
            pad = ~(op.off_ccols[d] >= 0).any(axis=1)
            assert pad.sum() == op.remote_rows - counts[d]
            assert not op.off_cvals[d][pad].any()


def test_operator_with_halo_in_every_row_keeps_full_rows():
    """A row list saves nothing where some device has a halo entry in
    every row: the off-part stays full-row and ships as such."""
    A, _ = _random_csr(seed=5)
    op = build_dist_operator(A, N_PODS, LANES, "standard", dtype=np.float64)
    assert (op.off_cols >= 0).any(axis=2).all(axis=1).any()
    assert op.off_rows is None and op.off_ccols is None
    assert op.remote_rows == op.rows_local
    arrs = op.device_arrays()
    assert "off_rows" not in arrs and arrs["off_cols"] is op.off_cols


SHARED_KEYS = {"send", "recv", "psel", "off_cols", "off_vals"}


@pytest.mark.parametrize("strategy", ["standard", "nap2", "nap3"])
def test_device_arrays_hold_no_fused_copy(strategy):
    """The device gets what the overlapped apply reads and nothing more:
    the exchange plan, the off-part and one form of the on-part — the ELL
    split, or once lowered to BCSR only its blocks, which still hold
    exactly A_on.  The fused block stays on the host."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.spmv.bcsr import bcsr_apply
    A, _ = _random_csr(seed=19, fill=0.0)
    op = build_dist_operator(A, N_PODS, LANES, strategy, dtype=np.float64)
    assert op.off_rows is not None
    shared = SHARED_KEYS | {"off_rows"}
    assert set(op.device_arrays()) == shared | {"on_cols", "on_vals"}
    op.lower_bcsr(4)
    assert op.local_kernel == "bcsr"
    arrs = op.device_arrays()
    assert set(arrs) == shared | {"on_bcols", "on_bvals"}
    x = np.random.default_rng(3).normal(size=(op.plan.local_n, 2))
    for d in range(op.n_devices):
        keep = op.on_cols[d] >= 0
        want = np.where(keep[..., None],
                        op.on_vals[d][..., None]
                        * x[np.maximum(op.on_cols[d], 0)], 0.0).sum(axis=1)
        got = np.asarray(bcsr_apply(
            jnp.asarray(arrs["on_bcols"][d]),
            jnp.asarray(arrs["on_bvals"][d], jnp.float32),
            jnp.asarray(x, jnp.float32)))
        np.testing.assert_allclose(got[: op.rows_local], want,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cycle", ["V", "W", "F"])
def test_multi_rhs_vcycle_equals_columns_1x1(cycle):
    """One [n, 4] cycle on a 1×1 mesh in float64 equals the four
    single-RHS cycles column for column and the host cycle of each
    column: the multi-RHS programs run the same apply on [local, k]."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.amg import SolveOptions, setup
    from repro.amg.dist_solve import DistHierarchy, dist_vcycle
    from repro.amg.problems import laplace_3d
    from repro.amg.solve import host_cycle
    A = laplace_3d(8)
    h = setup(A, solver="rs", max_coarse=30)
    assert h.n_levels >= 3                       # so W and F differ from V
    B = np.random.default_rng(5).normal(size=(A.nrows, 4))
    o = SolveOptions(cycle=cycle)
    with jax.enable_x64(True):
        dh = DistHierarchy.build(h, 1, 1, dtype=jnp.float64)
        assert dh.dtype == np.float64
        xm = dist_vcycle(dh, B, o)
        xc = np.stack([dist_vcycle(dh, B[:, j], o) for j in range(4)], 1)
    xh = np.stack([host_cycle(h, B[:, j], opts=o) for j in range(4)], 1)
    scale = np.abs(xh).max()
    assert np.abs(xm - xc).max() / scale < 1e-12
    assert np.abs(xm - xh).max() / scale < 1e-12


def test_onoff_nnz_partitions_local_nnz():
    A, _ = _random_csr(seed=5)
    op = build_dist_operator(A, N_PODS, LANES, "nap2", dtype=np.float64)
    stats = op.onoff_nnz()
    assert stats["on_nnz"] + stats["off_nnz"] == int((op.ell_cols >= 0).sum())
    assert stats["on_nnz"] + stats["off_nnz"] == A.nnz


def test_block_diagonal_operator_has_empty_halo():
    """A block-diagonal matrix aligned to the partition moves zero halo
    entries — total_halo records it even though halo_len is floored to 1."""
    topo = Topology(n_nodes=N_PODS, ppn=LANES)
    n = 96
    part = Partition.balanced(n, topo)
    rng = np.random.default_rng(0)
    dense = np.zeros((n, n))
    for d in range(topo.n_procs):
        lo, hi = part.local_range(d)
        dense[lo:hi, lo:hi] = rng.normal(size=(hi - lo, hi - lo))
    r, c = np.nonzero(dense)
    B = CSR.from_coo(r, c, dense[r, c], (n, n))
    op = build_dist_operator(B, N_PODS, LANES, "standard", dtype=np.float64)
    assert op.plan.total_halo == 0
    assert op.halo_empty
    assert op.onoff_nnz()["off_nnz"] == 0
    # a coupled operator is not empty
    A, _ = _random_csr()
    op2 = build_dist_operator(A, N_PODS, LANES, "standard", dtype=np.float64)
    assert op2.plan.total_halo > 0 and not op2.halo_empty


def test_empty_halo_apply_emits_no_collective_1x1():
    """On a 1×1 mesh every operator is halo-free: the jitted apply must
    contain no collective primitive at all — checked structurally with the
    comm-audit walker, not by substring-matching the jaxpr repr.  (The
    8-device 1-device-per-node variant runs in the dist_solve subprocess.)"""
    jax = pytest.importorskip("jax")
    from repro.amg.dist_spmv import build_dist_spmv
    from repro.analysis import audit_jaxpr, collect_collectives
    A, dense = _random_csr(seed=11)
    sp = build_dist_spmv(A, 1, 1, "standard", dtype=np.float64)
    assert sp.op.halo_empty
    assert sp.op.expected_signature == ()
    import jax.numpy as jnp
    jxp = jax.make_jaxpr(sp.fn)(jnp.zeros((1, sp.op.plan.local_n)))
    assert collect_collectives(jxp) == []
    audit = audit_jaxpr(jxp, "apply_A", expected_signature=())
    assert audit.ok and audit.n_collectives == 0
    x = np.random.default_rng(1).normal(size=A.ncols)
    # fp32 on this in-process run (jax x64 stays off in the main pytest
    # process); the fp64 parity lives in the subprocess script
    np.testing.assert_allclose(sp.matvec(x), dense @ x, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ cost model


def test_from_measurements_recovers_postal_fit():
    """lstsq on exact postal-model samples recovers alpha and R_b."""
    alpha, rb = 2.5e-6, 8.0e8
    samples = [(n, alpha + n / rb) for n in (1024., 8192., 65536., 524288.)]
    p = MachineParams.from_measurements(
        "fit_test", ppn=4, inter=samples, intra=samples, Rf=1e9)
    got = p.inter[0]
    assert got.alpha == pytest.approx(alpha, rel=1e-6)
    assert got.Rb == pytest.approx(rb, rel=1e-6)
    assert p.Rf == 1e9
    assert p.RN == pytest.approx(4 * rb, rel=1e-6)
    # all three protocol slots share the single fitted curve
    assert p.inter[1] == got and p.inter[2] == got


def test_from_measurements_floors_noisy_fit():
    """A fit driven negative by noise is floored, never unphysical."""
    samples = [(1024., 5e-6), (2048., 1e-6), (4096., 8e-6)]
    p = MachineParams.from_measurements("noisy", ppn=2, inter=samples,
                                        intra=samples)
    assert p.inter[0].alpha >= 1e-9
    assert 0 < p.inter[0].Rb < float("inf")
    with pytest.raises(ValueError):
        MachineParams.from_measurements("bad", ppn=2, inter=[(1., 1.)],
                                        intra=samples)


def test_overlap_time_and_efficiency():
    assert overlap_time(10.0, 4.0, 1.0) == 11.0      # comm dominates
    assert overlap_time(3.0, 4.0, 1.0) == 5.0        # compute hides comm
    assert overlap_efficiency(0.0, 0.0, 0.0) == 0.0
    # fully hidden exchange: serial 3+3+0=6, overlapped max(3,3)+0=3
    assert overlap_efficiency(3.0, 3.0, 0.0) == pytest.approx(0.5)
    # overlap-unaware machines yield zero compute → zero efficiency
    assert spmv_compute_times(BLUE_WATERS, 10**6, 10**6) == (0.0, 0.0)


def test_selection_accounts_for_hidden_latency():
    """With a compute split supplied, select() ranks strategies by
    max(T_comm, T_on) + T_off; a large t_on can erase the comm differences
    so the cheapest-comm strategy no longer wins automatically."""
    A, _ = _random_csr(seed=13)
    topo = Topology(n_nodes=N_PODS, ppn=LANES)
    part = Partition.balanced(A.nrows, topo)
    g = rect_vector_graph(A, part, part)
    base = select(g, BLUE_WATERS)
    assert base.compute == (0.0, 0.0)
    assert base.times == base.comm_times           # serial reduction
    t_on = 10.0 * max(base.comm_times.values())    # compute dwarfs comm
    sel = select(g, BLUE_WATERS, compute=(t_on, 0.0))
    assert sel.compute == (t_on, 0.0)
    for s, t in sel.times.items():
        assert t == pytest.approx(
            overlap_time(sel.comm_times[s], t_on, 0.0))
        assert t == pytest.approx(t_on)            # everything fully hidden
    # comm_times preserve the raw exchange model for reporting
    assert sel.comm_times == base.comm_times
