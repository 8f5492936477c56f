"""The benchmark's copy of the matrix and its float64 reference."""
import numpy as np
import pytest

import reference
import spec


def _build(nx, ny, nz):
    cell = spec.load_cell("hpcg104.pcg1")
    return spec.problem_module(cell).build(nx, ny, nz)


@pytest.mark.parametrize("dims", [(4, 4, 4), (5, 6, 7), (3, 9, 4)])
def test_copy_makes_the_programs_csr(dims):
    from repro.amg.problems import laplace_3d

    mine, theirs = _build(*dims), laplace_3d(*dims)
    assert mine.shape == theirs.shape
    np.testing.assert_array_equal(mine.indptr, theirs.indptr)
    np.testing.assert_array_equal(mine.indices, theirs.indices)
    np.testing.assert_array_equal(mine.data, theirs.data)


def test_hpcg_size_counts():
    """HPCG's 104³ grid: 1,124,864 rows and 29,791,000 nonzeros, as the
    configuration states (counted here without building the matrix)."""
    n, inner = 104, 102
    rows = n ** 3
    # every row has 27 entries less those whose neighbour falls outside
    per_axis = np.array([2] * 2 + [3] * inner)   # neighbours incl. itself
    nnz = int(per_axis.sum()) ** 3
    conf = spec.load_cell("hpcg104.pcg1").config
    assert (rows, nnz) == (conf["rows"], conf["nnz"]) == (1124864, 29791000)


def test_rel_residual_matches_dense():
    A = _build(3, 4, 5)
    dense = np.zeros(A.shape)
    rows = np.repeat(np.arange(A.nrows), np.diff(A.indptr))
    dense[rows, A.indices] = A.data
    rng = np.random.default_rng(0)
    x = rng.standard_normal(A.nrows).astype(np.float32)
    b = rng.standard_normal(A.nrows)
    want = np.linalg.norm(b - dense @ x.astype(np.float64)) / np.linalg.norm(b)
    assert reference.rel_residual(A, x, b) == pytest.approx(want, rel=1e-12)
    exact = np.linalg.solve(dense, b)
    assert reference.rel_residual(A, exact, b) < 1e-13
