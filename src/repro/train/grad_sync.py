"""Hierarchical (node-aware) gradient synchronization — the paper's NAP-3
applied to data-parallel training, with optional int8 compression + error
feedback on the pod-crossing leg.

Inside shard_map:  reduce-scatter(fast/ICI) → [quantize] all-reduce(slow/DCI)
→ all-gather(fast).  Compared to a flat all-reduce over (pod × data), the
expensive axis carries 1/|fast| of the bytes — and 1/4 of those with int8.

Error feedback keeps the quantization unbiased over time: the residual of
each quantization is added to the next step's gradient (Karimireddy et al.
style), so compression does not change the fixed point.
"""
# comm-audit: allow-file raw-collective — this module IS a hierarchical
# collective implementation (the int8 variant of nap_collectives.hier_psum
# with error feedback); its RS/AR/AG legs are the primitives themselves.
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.nap_collectives import hier_psum


def quantize_int8(x: jnp.ndarray):
    """Symmetric per-tensor int8.  Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def hier_grad_sync(grads, slow_axis: str, fast_axis: str,
                   strategy: str = "nap3", compress_slow: bool = False,
                   error_feedback=None):
    """Mean-reduce a gradient pytree over (slow × fast) data parallelism.

    Returns (synced_grads, new_error_feedback).  Call inside shard_map with
    per-device grads.  ``error_feedback`` must match ``grads`` (zeros to
    start) when ``compress_slow``.
    """
    n_slow = jax.lax.axis_size(slow_axis)
    n_fast = jax.lax.axis_size(fast_axis)
    denom = float(n_slow * n_fast)

    if strategy == "flat" or not compress_slow:
        synced = jax.tree.map(
            lambda g: hier_psum(g.astype(jnp.float32), slow_axis, fast_axis,
                                strategy) / denom, grads)
        return synced, error_feedback

    # NAP-3 with int8 pod-crossing leg + error feedback
    def one(g, ef):
        g = g.astype(jnp.float32)
        shape = g.shape
        flat = g.reshape(-1)
        pad = (-flat.size) % n_fast
        if pad:
            flat = jnp.pad(flat, (0, pad))
        piece = jax.lax.psum_scatter(flat, fast_axis, scatter_dimension=0,
                                     tiled=True)                # [n/|fast|]
        piece = piece + ef
        q, scale = quantize_int8(piece)
        residual = piece - dequantize_int8(q, scale)            # new EF
        # int8 payload crosses the slow axis (all-gather int8 + local sum —
        # 4× fewer DCI bytes than an f32 ring all-reduce, visible in HLO);
        # per-device scales are one f32 each.
        qg = jax.lax.all_gather(q, slow_axis, axis=0)           # [n_slow, L] i8
        sg = jax.lax.all_gather(scale, slow_axis, axis=0)       # [n_slow]
        summed = jnp.sum(qg.astype(jnp.float32) * sg[:, None], axis=0)
        full = jax.lax.all_gather(summed, fast_axis, axis=0, tiled=True)
        if pad:
            full = full[:-pad]
        return full.reshape(shape) / denom, residual

    leaves_g, treedef = jax.tree.flatten(grads)
    leaves_e = (treedef.flatten_up_to(error_feedback)
                if error_feedback is not None else
                [jnp.zeros(((l.size + (-l.size) % n_fast) // n_fast,),
                           jnp.float32) for l in leaves_g])
    out = [one(g, e) for g, e in zip(leaves_g, leaves_e)]
    return (treedef.unflatten([o[0] for o in out]),
            treedef.unflatten([o[1] for o in out]))


def init_error_feedback(grads, n_fast: int):
    return jax.tree.map(
        lambda g: jnp.zeros(((g.size + (-g.size) % n_fast) // n_fast,),
                            jnp.float32), grads)
