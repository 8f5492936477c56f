"""Diagonal-offset (DIA) local product: one contiguous shifted read per
stored diagonal.

An operator whose nonzeros all sit on a few fixed offsets ``col − row`` —
a stencil on a grid in its natural row-major order, such as HPCG's
27-point Laplacian — stores one value row per offset (the DIA format of
Bell & Garland, "Efficient Sparse Matrix-Vector Multiplication on CUDA",
NVIDIA TR 2008).  Each term of the product is then a static slice of the
source instead of a gather: the source is read as a stream, and no column
ids are read at all.

Layout:

  * ``offsets``: a static tuple of Python ints, ascending — the distinct
    ``col − row`` of the stored entries,
  * ``vals``: [n_diag, nb, 128] — diagonal ``d`` holds
    ``A[i, i + offsets[d]]`` at ``vals[d].reshape(-1)[i]``, with stored
    zeros where a row lacks that diagonal and on the rows past n
    (:func:`fold` builds it from [n_diag, n]).  Each diagonal is one
    contiguous row folded onto the TPU's 128 lanes, so it is whole
    (8, 128) tiles and reads in the same layout as the 1-D source.  A
    plain [n_diag, n] array tiles 8 diagonals together and costs a
    relayout copy of every diagonal each call: compiled for one v5e at
    HPCG's 104³ and 27 diagonals, 373.6 MB moved against 130.5 MB folded
    (``tests/test_tpu_compile.py``); [n, n_diag] would pad 27 to 128 lanes.

:func:`repro.kernels.spmv.ops.select_dia` decides when an operator is
lowered this way.  Like :func:`repro.kernels.spmv.spmv.ell_apply` the
product is plain XLA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


def fold(vals):
    """[n_diag, n] → [n_diag, ceil(n / 128), 128], zero-padded past n."""
    n_diag, n = vals.shape
    nb = -(-n // LANES)
    out = np.zeros((n_diag, nb * LANES), dtype=vals.dtype)
    out[:, :n] = vals
    return out.reshape(n_diag, nb, LANES)


def dia_apply(offsets: tuple[int, ...], vals: jnp.ndarray,
              x: jnp.ndarray) -> jnp.ndarray:
    """y = A·x with A in DIA form: ``y[i] = Σ_d vals[d]·x[i + offsets[d]]``
    over the folded rows ``i < n = nb·128`` (see the module doc).

    ``x`` is [m] or [m, k]; every ``i + offsets[d]`` outside [0, m) must
    carry a stored zero.  The source is zero-padded by the reach of the
    offsets so each term is a static slice, and the terms are summed in
    ascending offset order — for sorted rows, the slot order of
    :func:`~repro.kernels.spmv.spmv.ell_apply`.  Returns [n] or [n, k];
    callers slice back to their true row count.
    """
    n_diag, nb, lanes = vals.shape
    assert len(offsets) == n_diag, (offsets, vals.shape)
    n = nb * lanes
    y = jnp.zeros((n,) + x.shape[1:], dtype=vals.dtype)
    if n_diag == 0 or x.shape[0] == 0:
        return y
    lo = max(0, -offsets[0])
    hi = max(0, n + offsets[-1] - x.shape[0])
    xp = jnp.pad(x, ((lo, hi),) + ((0, 0),) * (x.ndim - 1))
    for d, off in enumerate(offsets):
        v = vals[d].reshape(n)
        if x.ndim == 2:
            v = v[:, None]
        y = y + v * jax.lax.slice_in_dim(xp, lo + off, lo + off + n, axis=0)
    return y
