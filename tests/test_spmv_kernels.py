"""Validation of the local products every distributed apply runs.

Covers the ELL product's multi-RHS form against both its vmapped
single-RHS form and the host CSR oracle (fp32/fp64, ragged K, padded
rows), BCSR round-trips and the block contraction's dense equivalence,
the DIA product against the ELL oracles on random offset sets and on the
27- and 7-point stencils, the degenerate shapes (K == 0, n == 0, empty
x, k == 0), and hypothesis-style random-sparsity sweeps under the
deterministic stub."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.amg.csr import CSR, csr_to_bcsr
from repro.amg.problems import laplace_3d, laplace_3d_7pt
from repro.kernels.spmv.bcsr import BLOCK_SIZES, bcsr_apply
from repro.kernels.spmv.dia import LANES, dia_apply, fold
from repro.kernels.spmv.ops import (select_dia, select_dist_kernel,
                                    select_local_kernel)
from repro.kernels.spmv.ref import ell_spmm_ref, ell_spmv_ref
from repro.kernels.spmv.spmv import ell_apply

# ell_apply accumulates the K slots one at a time while the oracles reduce
# over K in one sum, and XLA may contract a multiply-add into an FMA in one
# program and not the other: agreement is to rounding, not to the bit.
# 27 slots of O(1) products keep the float32 rounding under 1e-5.
TOL = {np.dtype(np.float32): 1e-5, np.dtype(np.float64): 1e-12}


def _random_ell(rng, n, m, K, dtype, pad_rows=0):
    """Random ELL block; ``pad_rows`` trailing rows are all-padding."""
    cols = rng.integers(0, m, size=(n, K)).astype(np.int32)
    mask = rng.random((n, K)) < 0.3
    cols[mask] = -1
    if pad_rows:
        cols[n - pad_rows:] = -1
    vals = rng.standard_normal((n, K)).astype(dtype)
    vals[cols == -1] = 0.0
    return jnp.asarray(cols), jnp.asarray(vals)


def _ell_to_csr(cols, vals, m):
    cols = np.asarray(cols)
    vals = np.asarray(vals, dtype=np.float64)
    keep = cols >= 0
    r = np.broadcast_to(np.arange(cols.shape[0])[:, None], cols.shape)[keep]
    return CSR.from_coo(r, cols[keep], vals[keep], (cols.shape[0], m))


# ---------------------------------------------------------------- ELL SpMM
@pytest.mark.parametrize("n,m,K,k", [(8, 16, 3, 2), (100, 64, 7, 4),
                                     (257, 300, 27, 8), (64, 64, 1, 5)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_ell_spmm_matches_vmapped_spmv_and_csr(n, m, K, k, dtype):
    if dtype == np.float64 and not jax.config.jax_enable_x64:
        dtype = np.float32     # x64 disabled in-process: still run the shape
    rng = np.random.default_rng(n * K + k)
    cols, vals = _random_ell(rng, n, m, K, dtype, pad_rows=3)
    X = jnp.asarray(rng.standard_normal((m, k)).astype(dtype))
    out = ell_apply(cols, vals, X)
    assert out.shape == (n, k)
    tol = TOL[np.dtype(np.asarray(vals).dtype)]
    # vs the vmapped single-RHS product — the parity the native multi-RHS
    # routing in dist_solve relies on
    vmapped = jax.vmap(lambda xc: ell_apply(cols, vals, xc),
                       in_axes=1, out_axes=1)(X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(vmapped),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ell_spmm_ref(cols, vals, X)),
                               rtol=tol, atol=tol)
    # vs the host CSR oracle, column by column
    Acsr = _ell_to_csr(cols, vals, m)
    ref = np.stack([Acsr.matvec(np.asarray(X[:, j], dtype=np.float64))
                    for j in range(k)], axis=1)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               rtol=tol, atol=tol)


# --------------------------------------------------------- degenerate shapes
def test_ell_spmv_degenerate_shapes():
    """K == 0 / n == 0 / empty x give exact zeros of the right shape."""
    f32 = jnp.float32
    y = ell_apply(jnp.zeros((5, 0), jnp.int32), jnp.zeros((5, 0), f32),
                  jnp.ones((7,), f32))
    np.testing.assert_array_equal(np.asarray(y), np.zeros(5))
    y = ell_apply(jnp.zeros((0, 3), jnp.int32), jnp.zeros((0, 3), f32),
                  jnp.ones((7,), f32))
    assert y.shape == (0,)
    y = ell_apply(jnp.full((4, 2), -1, jnp.int32), jnp.zeros((4, 2), f32),
                  jnp.zeros((0,), f32))
    np.testing.assert_array_equal(np.asarray(y), np.zeros(4))


def test_ell_spmm_degenerate_shapes():
    f32 = jnp.float32
    for cols_s, x_s, out_s in [((5, 0), (7, 3), (5, 3)),   # K == 0
                               ((0, 3), (7, 2), (0, 2)),   # n == 0
                               ((4, 2), (0, 3), (4, 3)),   # empty x
                               ((4, 2), (7, 0), (4, 0))]:  # k == 0
        y = ell_apply(jnp.zeros(cols_s, jnp.int32) - 1,
                      jnp.zeros(cols_s, f32), jnp.zeros(x_s, f32))
        assert y.shape == out_s
        np.testing.assert_array_equal(np.asarray(y), np.zeros(out_s))


def test_ell_spmv_tiny_n_no_overpadding():
    """A handful of rows gives exactly the rows asked for."""
    rng = np.random.default_rng(0)
    for n in (1, 3, 7):
        cols, vals = _random_ell(rng, n, 10, 4, np.float32)
        x = jnp.asarray(rng.standard_normal(10).astype(np.float32))
        y = ell_apply(cols, vals, x)
        assert y.shape == (n,)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ell_spmv_ref(cols, vals, x)),
                                   rtol=TOL[np.dtype(np.float32)],
                                   atol=TOL[np.dtype(np.float32)])


# -------------------------------------------------------------------- BCSR
@pytest.mark.parametrize("bs", BLOCK_SIZES)
def test_csr_to_bcsr_round_trip(bs):
    A = laplace_3d(5)
    B = csr_to_bcsr(A, bs)
    dense = A.to_dense()
    np.testing.assert_array_equal(B.to_dense(), dense)
    assert B.bcols.shape[0] == -(-A.nrows // bs)
    assert 0.0 < B.fill <= 1.0
    # every stored block id in range, padding all -1-terminated per row
    assert B.bcols.max() < -(-A.ncols // bs)


def test_csr_to_bcsr_empty():
    B = csr_to_bcsr(CSR.from_coo([], [], [], (10, 10)), 8)
    assert B.bcols.shape == (2, 0)
    np.testing.assert_array_equal(B.to_dense(), np.zeros((10, 10)))


@pytest.mark.parametrize("bs", BLOCK_SIZES)
def test_bcsr_spmm_matches_dense(bs):
    A = laplace_3d(5)
    B = csr_to_bcsr(A, bs)
    rng = np.random.default_rng(bs)
    X = rng.standard_normal((A.ncols, 4)).astype(np.float32)
    bcols = jnp.asarray(B.bcols)
    bvals = jnp.asarray(B.bvals, dtype=jnp.float32)
    out = bcsr_apply(bcols, bvals, jnp.asarray(X))
    ref = A.to_dense().astype(np.float32) @ X
    np.testing.assert_allclose(np.asarray(out)[: A.nrows], ref,
                               rtol=2e-5, atol=2e-5)
    # padded block rows past A.nrows stay exactly zero
    np.testing.assert_array_equal(np.asarray(out)[A.nrows:], 0.0)
    # single-RHS form
    y = bcsr_apply(bcols, bvals, jnp.asarray(X[:, 0]))
    np.testing.assert_allclose(np.asarray(y)[: A.nrows], ref[:, 0],
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ layout heuristic
def test_select_local_kernel_shapes():
    A = laplace_3d(5)
    K = int(np.diff(A.indptr).max())
    cols = np.full((A.nrows, K), -1, dtype=np.int32)
    lens = np.diff(A.indptr)
    r = A.rows_expanded()
    slot = np.arange(A.nnz) - np.repeat(A.indptr[:-1], lens)
    cols[r, slot] = A.indices
    sel = select_local_kernel(cols)
    assert sel["kernel"] in ("ell", "bcsr")
    assert 0.0 < sel["ell_fill"] <= 1.0
    if sel["kernel"] == "bcsr":
        assert sel["block_size"] in BLOCK_SIZES
        assert sel["bcsr_cost"] < sel["ell_cost"]
    # empty block → ELL trivially
    assert select_local_kernel(
        np.full((4, 2), -1, np.int32))["kernel"] == "ell"
    # the stacked form agrees with per-device aggregation
    sel_d = select_dist_kernel(cols[None])
    assert sel_d["kernel"] == sel["kernel"]


# --------------------------------- hypothesis-style random sparsity sweeps
@settings(max_examples=12, deadline=None)
@given(st.integers(1, 120), st.integers(1, 90), st.integers(1, 12),
       st.integers(1, 6), st.integers(0, 10 ** 6))
def test_ell_spmm_random_sparsity(n, m, K, k, seed):
    rng = np.random.default_rng(seed)
    cols, vals = _random_ell(rng, n, m, K, np.float32,
                             pad_rows=int(rng.integers(0, n)))
    X = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    out = ell_apply(cols, vals, X)
    tol = TOL[np.dtype(np.float32)]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ell_spmm_ref(cols, vals, X)),
                               rtol=tol, atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.integers(6, 40), st.sampled_from(list(BLOCK_SIZES)),
       st.integers(0, 10 ** 6))
def test_bcsr_random_round_trip(n, bs, seed):
    rng = np.random.default_rng(seed)
    dense = np.where(rng.random((n, n)) < 0.15,
                     rng.standard_normal((n, n)), 0.0)
    A = CSR.from_dense(dense)
    B = csr_to_bcsr(A, bs)
    np.testing.assert_array_equal(B.to_dense(), dense)
    X = rng.standard_normal((n, 3))
    out = np.asarray(bcsr_apply(jnp.asarray(B.bcols),
                                jnp.asarray(B.bvals),
                                jnp.asarray(X, dtype=jnp.float64)
                                if jax.config.jax_enable_x64
                                else jnp.asarray(X, dtype=jnp.float32)))
    ref = dense @ X
    np.testing.assert_allclose(out[:n], ref, rtol=2e-4, atol=2e-4)


def test_spmv_kernel_on_7pt_operator():
    """The laplace_3d_7pt operator through the SpMM form."""
    A = laplace_3d_7pt(6)
    K = int(np.diff(A.indptr).max())
    cols = np.full((A.nrows, K), -1, dtype=np.int32)
    vals = np.zeros((A.nrows, K), dtype=np.float32)
    lens = np.diff(A.indptr)
    r = A.rows_expanded()
    slot = np.arange(A.nnz) - np.repeat(A.indptr[:-1], lens)
    cols[r, slot] = A.indices
    vals[r, slot] = A.data
    X = np.random.default_rng(0).standard_normal(
        (A.ncols, 4)).astype(np.float32)
    out = ell_apply(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(X))
    ref = np.stack([A.matvec(X[:, j].astype(np.float64)) for j in range(4)],
                   axis=1)
    np.testing.assert_allclose(np.asarray(out, np.float64), ref,
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------- DIA
def _random_dia(rng, n, m, offsets, dtype, missing=0.2):
    """A random operator on ``offsets`` as ELL (columns ascending, so slot
    order is offset order) and as DIA [n_diag, n]; a ``missing`` share of
    the in-range entries is left out, so rows lack diagonals."""
    offsets = sorted(offsets)
    i = np.arange(n)[:, None]
    c = i + np.asarray(offsets)[None, :]
    keep = (c >= 0) & (c < m) & (rng.random(c.shape) >= missing)
    v = np.where(keep, rng.standard_normal(c.shape), 0.0).astype(dtype)
    dia = np.ascontiguousarray(v.T)
    K = max(int(keep.sum(axis=1).max(initial=0)), 1)
    cols = np.full((n, K), -1, dtype=np.int32)
    vals = np.zeros((n, K), dtype=dtype)
    r, j = np.nonzero(keep)
    slot = np.arange(r.size) - np.repeat(
        np.concatenate([[0], np.cumsum(keep.sum(axis=1))[:-1]]),
        keep.sum(axis=1))
    cols[r, slot] = c[r, j]
    vals[r, slot] = v[r, j]
    return tuple(offsets), dia, jnp.asarray(cols), jnp.asarray(vals)


def _dtype(dtype):
    if dtype == np.float64 and not jax.config.jax_enable_x64:
        return np.float32     # x64 disabled in-process: still run the shape
    return dtype


DIA_CASES = {
    "single-main": (50, 50, [0]),
    "single-off": (50, 50, [3]),
    "stencil-1d": (64, 64, [-1, 0, 1]),
    "reach-n-1": (40, 40, [-39, -5, 0, 7, 39]),
    "rect-wide": (30, 70, [-2, 0, 11, 45]),
    "rect-narrow": (90, 33, [-50, -1, 0, 2]),
    "fold-exact": (2 * LANES, 2 * LANES, [-LANES, -1, 0, 1, LANES]),
}


@pytest.mark.parametrize("case", sorted(DIA_CASES))
@pytest.mark.parametrize("k", [0, 3])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_dia_matches_ell_and_csr(case, k, dtype):
    """y = Σ_d vals[d]·x[i + offsets[d]] against the ELL oracles and the
    host CSR product, one right-hand side (k=0) or three."""
    n, m, offs = DIA_CASES[case]
    dtype = _dtype(dtype)
    rng = np.random.default_rng(n + m + k)
    offsets, dia, cols, vals = _random_dia(rng, n, m, offs, dtype)
    x = rng.standard_normal((m,) if k == 0 else (m, k)).astype(dtype)
    out = dia_apply(offsets, jnp.asarray(fold(dia)), jnp.asarray(x))
    assert out.shape == ((-(-n // LANES) * LANES,) + x.shape[1:])
    # the folded rows past n hold stored zeros: they read exactly zero
    np.testing.assert_array_equal(np.asarray(out)[n:], 0.0)
    out = np.asarray(out)[:n]
    tol = TOL[np.dtype(dtype)]
    ref = (ell_spmv_ref if k == 0 else ell_spmm_ref)(cols, vals,
                                                     jnp.asarray(x))
    np.testing.assert_allclose(out, np.asarray(ref), rtol=tol, atol=tol)
    np.testing.assert_allclose(out, np.asarray(ell_apply(cols, vals,
                                                         jnp.asarray(x))),
                               rtol=tol, atol=tol)
    A = _ell_to_csr(cols, vals, m)
    x64 = x.astype(np.float64).reshape(m, -1)
    csr = np.stack([A.matvec(x64[:, j]) for j in range(x64.shape[1])], 1)
    np.testing.assert_allclose(out.reshape(n, -1), csr, rtol=tol, atol=tol)


def test_dia_degenerate_shapes():
    """No diagonals, or an empty source, give exact zeros."""
    f32 = jnp.float32
    y = dia_apply((), jnp.zeros((0, 1, LANES), f32), jnp.ones((7,), f32))
    np.testing.assert_array_equal(np.asarray(y), np.zeros(LANES))
    y = dia_apply((-1, 0), jnp.zeros((2, 1, LANES), f32),
                  jnp.zeros((0, 3), f32))
    np.testing.assert_array_equal(np.asarray(y), np.zeros((LANES, 3)))


def test_fold_pads_rows_with_zeros():
    v = np.arange(6.0).reshape(2, 3)
    f = fold(v)
    assert f.shape == (2, 1, LANES)
    np.testing.assert_array_equal(f.reshape(2, -1)[:, :3], v)
    np.testing.assert_array_equal(f.reshape(2, -1)[:, 3:], 0.0)


def _csr_ell(A, dtype=np.float32):
    K = int(np.diff(A.indptr).max())
    cols = np.full((A.nrows, K), -1, dtype=np.int32)
    vals = np.zeros((A.nrows, K), dtype=dtype)
    lens = np.diff(A.indptr)
    slot = np.arange(A.nnz) - np.repeat(A.indptr[:-1], lens)
    cols[A.rows_expanded(), slot] = A.indices
    vals[A.rows_expanded(), slot] = A.data
    return cols, vals


@pytest.mark.parametrize("problem,n_diag", [(laplace_3d, 27),
                                            (laplace_3d_7pt, 7)])
def test_dia_equals_ell_on_stencils(problem, n_diag):
    """The stencils in natural order: every row's columns sit on n_diag
    fixed offsets, so select_dia takes them, and the DIA product equals
    the ELL one, for one and for four right-hand sides."""
    A = problem(7)
    cols, vals = _csr_ell(A)
    offsets = select_dia(cols[None])
    assert offsets is not None and len(offsets) == n_diag
    assert list(offsets) == sorted(offsets) and 0 in offsets
    dia = np.zeros((n_diag, A.nrows), dtype=np.float32)
    keep = cols >= 0
    r = np.broadcast_to(np.arange(A.nrows)[:, None], cols.shape)[keep]
    dia[np.searchsorted(offsets, cols[keep] - r), r] = vals[keep]
    X = np.random.default_rng(n_diag).standard_normal(
        (A.nrows, 4)).astype(np.float32)
    tol = TOL[np.dtype(np.float32)]
    for x in (X[:, 0], X):
        out = dia_apply(offsets, jnp.asarray(fold(dia)), jnp.asarray(x))
        ell = ell_apply(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out)[: A.nrows],
                                   np.asarray(ell), rtol=tol, atol=tol)


def test_select_dia_rule():
    """DIA is taken only where its diagonals number no more than the ELL
    width; an unstructured block keeps ELL."""
    rng = np.random.default_rng(5)
    cols, _ = _random_ell(rng, 200, 200, 6, np.float32)
    assert select_dia(np.asarray(cols)[None]) is None
    assert select_dia(np.full((1, 4, 2), -1, np.int32)) is None
    # a tridiagonal block over two devices: offsets are the union
    tri = np.array([[-1, 0, 1], [0, 1, 2], [1, 2, -1]], np.int32)
    two = np.stack([tri, np.array([[0, 1, -1], [0, 1, 2], [1, 2, -1]],
                                  np.int32)])
    assert select_dia(two) == (-1, 0, 1)
    # four offsets on a width-3 block: DIA would store more than ELL
    assert select_dia(np.array([[[0, 1, 2], [0, -1, -1]]], np.int32)) is None


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 150), st.integers(1, 150), st.integers(1, 6),
       st.integers(1, 4), st.integers(0, 10 ** 6))
def test_dia_random_offsets(n, m, n_diag, k, seed):
    rng = np.random.default_rng(seed)
    offs = rng.choice(np.arange(-(n - 1), m), size=min(n_diag, n + m - 1),
                      replace=False)
    offsets, dia, cols, vals = _random_dia(rng, n, m, offs.tolist(),
                                           np.float32,
                                           missing=float(rng.random()) * 0.5)
    X = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    out = np.asarray(dia_apply(offsets, jnp.asarray(fold(dia)), X))[:n]
    tol = TOL[np.dtype(np.float32)]
    np.testing.assert_allclose(out, np.asarray(ell_spmm_ref(cols, vals, X)),
                               rtol=tol, atol=tol)
