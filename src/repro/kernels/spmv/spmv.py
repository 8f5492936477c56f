"""The local ELL product the distributed applies run on the device.

The sparse block is stored in ELL form (fixed K slots per padded row,
``cols == -1`` padding) — the layout the distributed solve path lowers
every operator to first.  A stencil-structured on-process part (the fine
level's ``A`` of a grid problem) is then moved to the diagonal product
(:mod:`repro.kernels.spmv.dia`), which reads the source as shifted
slices; the Galerkin levels, ``P``, ``R`` and every off-process part
stay here or on block-ELL (:mod:`repro.kernels.spmv.bcsr`).  :func:`ell_apply` contracts it against a source of
shape ``[m]`` (one right-hand side) or ``[m, k]`` (the native multi-RHS
form: one pass over ``cols``/``vals`` serves all k columns).

The product is plain XLA: a ``fori_loop`` over the K slots, each step
gathering one ``[n(, k)]`` slab of the source and accumulating it.  The
obvious one-shot form ``(vals[..., None] * x[cols]).sum(1)`` materialises
``[n, K(, k)]``; compiled for one v5e chip at 1,124,864 rows × K=27 that
needed 1.73 GB of scratch for k=1 and 5.83 GB for k=8, while the
slot-at-a-time form needs about 6 MB for either (``tests/
test_tpu_compile.py`` holds it there).

A Pallas version of this product does not lower for the TPU: Mosaic has
no arbitrary gather from a VMEM-resident vector (``jnp.take`` inside a
kernel is refused with "Only 2D gather is supported").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ell_apply(cols: jnp.ndarray, vals: jnp.ndarray,
              x: jnp.ndarray) -> jnp.ndarray:
    """y = A·x with A in padded ELL form: ``cols``/``vals`` are [n, K],
    ``x`` is [m] or [m, k] (m covers every valid column id).  Returns
    [n] or [n, k]."""
    n, K = cols.shape
    y = jnp.zeros((n,) + x.shape[1:], dtype=vals.dtype)
    if K == 0 or x.shape[0] == 0:
        return y

    def slot(j, y):
        c = jax.lax.dynamic_index_in_dim(cols, j, axis=1, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vals, j, axis=1, keepdims=False)
        valid = c >= 0
        g = x[jnp.maximum(c, 0)]
        if x.ndim == 2:
            v, valid = v[:, None], valid[:, None]
        return y + jnp.where(valid, v * g, 0.0)

    return jax.lax.fori_loop(0, K, slot, y)
