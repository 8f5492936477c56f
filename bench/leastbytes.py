"""The least work one PCG iteration asks of the chip.

Counted from the host hierarchy's operators (rows, columns and stored
values of every level's A, P and R) and from the cycle's definition, never
from the arrays the program lowered, so the count is the same whatever
layout, index width or kernel carries the work:

* one application of an m × n operator with z stored values reads each
  value once, with no index bytes, reads its input vector once and writes
  its output once: ``w·(z + n + m)`` bytes and ``2·z`` operations, at
  ``w`` bytes per value of the session's precision;
* the coarsest level is solved by a dense n_c × n_c inverse:
  ``w·(n_c² + 2·n_c)`` bytes and ``2·n_c²`` operations;
* a V(ν₁, ν₂) cycle with a Jacobi smoother applies, on each level above
  the coarsest, A ``ν₁ + ν₂ + 1`` times (the sweeps and the residual), R
  once and P once;
* a PCG iteration is one fine-level A (the search direction's product)
  and one cycle.  ``pcg_init`` does the same work once per solve: the
  initial residual's product and one cycle.

Vector updates, dot products and the smoother's diagonal are left out, so
the count is a floor and a share of the roofline taken from it cannot pass
100 %.
"""
from __future__ import annotations

import dataclasses

import numpy as np

BYTES_PER_VALUE = {"float32": 4, "bfloat16": 2, "float64": 8}


@dataclasses.dataclass(frozen=True)
class Work:
    bytes: float
    flops: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.bytes + other.bytes, self.flops + other.flops)

    def __mul__(self, times: float) -> "Work":
        return Work(self.bytes * times, self.flops * times)

    def seconds(self, peaks: dict) -> float:
        """The least time on a chip with ``peaks``: the larger of bytes
        over bandwidth and operations over peak rate."""
        return max(self.bytes / peaks["hbm_bytes_per_s"],
                   self.flops / peaks["flops_per_s"])


def apply_work(nrows: int, ncols: int, nnz: int, width: int) -> Work:
    return Work(width * (nnz + ncols + nrows), 2.0 * nnz)


def _csr_work(M, width: int) -> Work:
    return apply_work(M.shape[0], M.shape[1], int(np.asarray(M.indptr)[-1]),
                      width)


def iteration_work(levels, opts: dict, dtype: str) -> Work:
    """Least work of one PCG iteration over ``levels`` (each with ``A``,
    and ``P``/``R`` on every level above the coarsest), for a cycle of
    ``opts`` (``cycle``, ``smoother``, ``presweeps``, ``postsweeps``)."""
    if opts.get("cycle", "V") != "V" or opts.get("smoother",
                                                 "jacobi") != "jacobi":
        raise ValueError("least work is defined for the Jacobi V-cycle "
                         f"only, got {opts}")
    width = BYTES_PER_VALUE[dtype]
    a_per_visit = opts.get("presweeps", 1) + opts.get("postsweeps", 1) + 1
    work = _csr_work(levels[0].A, width)            # A·p of the CG step
    for lv in levels[:-1]:
        work = (work + _csr_work(lv.A, width) * a_per_visit
                + _csr_work(lv.R, width) + _csr_work(lv.P, width))
    nc = levels[-1].A.shape[0]
    return work + Work(width * (nc * nc + 2 * nc), 2.0 * nc * nc)
