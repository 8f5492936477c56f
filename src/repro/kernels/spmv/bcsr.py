"""Block-ELL (BCSR) local product: dense ``bs×bs`` blocks per stored slot.

Operators with block structure (vector problems from
:mod:`repro.amg.problems`, dense coarse levels) can store dense ``bs×bs``
blocks (bs ∈ {8, 16}) in a block-ELL layout and contract each block
against a ``bs×k`` slab of the source — dense math instead of one scalar
gather per nonzero.  :func:`repro.kernels.spmv.ops.select_dist_kernel`
decides per level whether a level is lowered this way; a level it keeps
on ELL may still take the diagonal product for its on-process part
(:mod:`repro.kernels.spmv.dia`) when that part is stencil-structured.

Layout (produced by :func:`repro.amg.csr.csr_to_bcsr`):

  * ``bcols``: [mb, Kb] int32 — block-column ids per padded block row
    (-1 padding), where mb = ceil(n / bs) and Kb is the max number of
    nonzero blocks in any block row,
  * ``bvals``: [mb, Kb, bs, bs] — the dense blocks (explicit zero fill
    inside a stored block),
  * the source ``x`` is reshaped to [nb, bs(, k)] blocks; gathering block
    ``bcols[r, j]`` yields the ``bs(×k)`` slab the block multiplies.

Like :func:`repro.kernels.spmv.spmv.ell_apply` the product is plain XLA
and accumulates one block slot at a time, so the gathered source is
never materialised for all Kb slots at once.  It has no Pallas form:
gathering blocks of a resident source inside a kernel is refused by
Mosaic ("Only 2D gather is supported").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_SIZES = (8, 16)


def _block_x(x: jnp.ndarray, bs: int) -> jnp.ndarray:
    """[m(, k)] → [nb, bs, k] zero-padded blocked source (k=1 for vectors)."""
    if x.ndim == 1:
        x = x[:, None]
    m, k = x.shape
    pad = (-m) % bs
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x.reshape(-1, bs, k)


def bcsr_apply(bcols: jnp.ndarray, bvals: jnp.ndarray,
               x: jnp.ndarray) -> jnp.ndarray:
    """Y = A·X with A in block-ELL form (``bcols`` [mb, Kb], ``bvals``
    [mb, Kb, bs, bs]) and X of shape [m] or [m, k].  Returns
    [mb·bs] or [mb·bs, k] — callers slice back to the true row count."""
    single = x.ndim == 1
    mb, Kb = bcols.shape
    bs = bvals.shape[-1]
    xb = _block_x(x, bs)
    y = jnp.zeros((mb, bs, xb.shape[-1]), dtype=bvals.dtype)
    if Kb and xb.shape[0]:
        def slot(j, y):
            c = jax.lax.dynamic_index_in_dim(bcols, j, axis=1, keepdims=False)
            blk = jax.lax.dynamic_index_in_dim(bvals, j, axis=1,
                                               keepdims=False)
            g = xb[jnp.maximum(c, 0)]                        # [mb, bs, k]
            contrib = jnp.einsum("rij,rjk->rik", blk, g)
            return y + jnp.where((c >= 0)[:, None, None], contrib, 0.0)

        y = jax.lax.fori_loop(0, Kb, slot, y)
    y = y.reshape(mb * bs, -1)
    return y[:, 0] if single else y
