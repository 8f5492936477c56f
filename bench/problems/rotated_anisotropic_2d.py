"""Rotated anisotropic diffusion on an nx × nx grid, pyamg's 9-point
finite-difference stencil.

The operator is −∇·(Q D Qᵀ ∇u) with D = diag(1, ε) rotated by θ, as
``pyamg.gallery.diffusion_stencil_2d(epsilon, theta, type='FD')`` writes
it, with C = cos θ, S = sin θ:

    a = ½(ε − 1)·C·S      b = −(C² + ε·S²)      d = −(S² + ε·C²)

    [[ a, d, −a],
     [ b, e,  b],
     [−a, d,  a]]         e = −(sum of the other eight) = −2b − 2d

the system of Bienz, Gropp & Olson 2019 (arXiv:1904.05838), Fig. 21.
Neighbours outside the grid are left out (homogeneous Dirichlet
truncation).  It is the same CSR as the system's own
``rotated_anisotropic_2d`` (``tests/bench`` checks that at a small size),
kept here so that no change to the program can change the matrix a cell
solves.

Rows are in row-major grid order (the stencil's row axis slowest).  The
nine offsets are taken in lexicographic order, so their linear column
offsets rise strictly and each row's columns come out sorted without a
sort.
"""
from __future__ import annotations

import itertools
import math

import numpy as np

from reference import Matrix


def stencil(eps: float, theta: float) -> np.ndarray:
    """The 3 × 3 stencil above, in float64."""
    c, s = math.cos(theta), math.sin(theta)
    a = 0.5 * (eps - 1.0) * c * s
    b = -(c * c + eps * s * s)
    d = -(s * s + eps * c * c)
    return np.array([[a, d, -a],
                     [b, -2.0 * b - 2.0 * d, b],
                     [-a, d, a]])


def build(nx: int, eps: float, theta: float) -> Matrix:
    """The operator as a float64 CSR :class:`~reference.Matrix`."""
    n = nx * nx
    st = stencil(eps, theta)
    ix, iy = (g.ravel() for g in np.meshgrid(np.arange(nx), np.arange(nx),
                                             indexing="ij"))
    rows = np.arange(n, dtype=np.int64)
    cols = np.empty((n, 9), dtype=np.int64)
    keep = np.empty((n, 9), dtype=bool)
    vals = np.empty(9)
    for k, (dx, dy) in enumerate(itertools.product((-1, 0, 1), repeat=2)):
        cols[:, k] = rows + dx * nx + dy
        keep[:, k] = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                      & (iy + dy < nx))
        vals[k] = st[dx + 1, dy + 1]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    data = np.broadcast_to(vals, (n, 9))[keep]
    return Matrix((n, n), indptr, cols[keep], np.ascontiguousarray(data))
