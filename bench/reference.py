"""The plain reference the answer check holds every solve to.

A matrix made by the benchmark's own generator (``bench/problems/``) and
the float64 true residual ‖b − A·x‖ / ‖b‖ of an answer, worked out on the
host.  Nothing here imports the program under test or takes anything it
made: the matrix is the benchmark's, and an answer is only read.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Matrix:
    """A CSR matrix in float64, columns sorted within each row."""

    shape: tuple[int, int]
    indptr: np.ndarray   # (nrows + 1,) int64
    indices: np.ndarray  # (nnz,) int64
    data: np.ndarray     # (nnz,) float64

    @property
    def nrows(self) -> int:
        return self.shape[0]

    @property
    def nnz(self) -> int:
        return int(self.indices.size)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        """A·x in float64.  Every row holds its diagonal, so no row is
        empty and each row's products sum in one ``reduceat`` segment."""
        x = np.asarray(x, dtype=np.float64)
        return np.add.reduceat(self.data * x[self.indices], self.indptr[:-1])


def rel_residual(A: Matrix, x, b) -> float:
    """‖b − A·x‖ / ‖b‖ in float64 on the host."""
    b = np.asarray(b, dtype=np.float64)
    r = b - A.matvec(x)
    return float(np.linalg.norm(r) / np.linalg.norm(b))
