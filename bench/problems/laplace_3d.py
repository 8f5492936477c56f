"""HPCG's 27-point Poisson operator on an nx × ny × nz grid.

Every grid point couples to itself with 26 and to each of its up to 26
neighbours with −1; neighbours outside the grid are left out (homogeneous
Dirichlet truncation).  This is the stencil HPCG's reference code
generates, and the same CSR as the system's own ``laplace_3d``
(``tests/bench`` checks that at a small size), kept here so that no change
to the program can change the matrix a cell solves.

Rows are in row-major grid order (x slowest, z fastest).  The 27 offsets
are taken in lexicographic order, so their linear column offsets rise
strictly and each row's columns come out sorted without a sort.
"""
from __future__ import annotations

import itertools

import numpy as np

from reference import Matrix

CENTER = 26.0
NEIGHBOUR = -1.0


def build(nx: int, ny: int, nz: int) -> Matrix:
    """The 27-point operator as a float64 CSR :class:`~reference.Matrix`."""
    n = nx * ny * nz
    ix, iy, iz = (g.ravel() for g in np.meshgrid(
        np.arange(nx), np.arange(ny), np.arange(nz), indexing="ij"))
    rows = np.arange(n, dtype=np.int64)
    cols = np.empty((n, 27), dtype=np.int64)
    keep = np.empty((n, 27), dtype=bool)
    vals = np.empty(27)
    for s, (dx, dy, dz) in enumerate(itertools.product((-1, 0, 1),
                                                       repeat=3)):
        cols[:, s] = rows + (dx * ny + dy) * nz + dz
        keep[:, s] = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                      & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
        vals[s] = CENTER if (dx, dy, dz) == (0, 0, 0) else NEIGHBOUR
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    indices = cols[keep]
    data = np.broadcast_to(vals, (n, 27))[keep]
    return Matrix((n, n), indptr, indices, np.ascontiguousarray(data))
