"""Mixture-of-Experts block with explicit expert-parallel dispatch.

Two sharding regimes, chosen by divisibility (DESIGN.md §7):

* **EP** (E % expert_shards == 0, e.g. qwen3-moe 128e over 16): expert
  weights sharded over the expert axis; tokens dispatched by a capacity-
  bounded all-to-all.  The all-to-all is routed through
  :func:`repro.core.nap_collectives.hier_all_to_all` when the expert shards
  span the pod axis — the paper's NAP-3 applied to MoE dispatch.
* **TP** (otherwise, e.g. mixtral 8e over 16): every expert's d_ff sharded
  over the model axis; tokens stay local; partial sums reduced by the
  standard TP psum (GSPMD inserts it).

Routing: full-softmax → top-k → renormalize (qwen-style); capacity factor
drops overflow tokens (their combine weight is zero), standard for TPU MoE.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from .layers import dense_init


def moe_params(key, cfg, dtype):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], d, (d, e), jnp.float32),
        "gate": dense_init(ks[1], d, (e, d, f), dtype),
        "up": dense_init(ks[2], d, (e, d, f), dtype),
        "down": dense_init(ks[3], f, (e, f, d), dtype),
    }


def _route(x2, router, top_k):
    """x2: [T, d] → (probs [T,k] f32, sel [T,k] i32)."""
    logits = (x2.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    pv, sel = jax.lax.top_k(probs, top_k)
    pv = pv / jnp.maximum(pv.sum(-1, keepdims=True), 1e-9)
    return pv, sel


def _dispatch_indices(sel, n_experts, capacity):
    """Per (token, slot): expert id, position within expert (or >=capacity
    if dropped).  Sort-based, no [T, E, C] tensor."""
    T, k = sel.shape
    flat_e = sel.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position within expert among sorted entries
    counts = jnp.bincount(sorted_e, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(T * k) - starts[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    return flat_e.reshape(T, k), pos.reshape(T, k)


def _expert_ffn(w, h, act, tp_axis: str | None = None):
    """Batched expert FFN; ``tp_axis``: d_ff is sharded over this mesh axis
    (inside shard_map) — the down-projection partial sums are psum'd."""
    g = jnp.einsum("ecd,edf->ecf", h, w["gate"])
    u = jnp.einsum("ecd,edf->ecf", h, w["up"])
    gated = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
    out = jnp.einsum("ecf,efd->ecd", gated, w["down"])
    if tp_axis is not None:
        # single-axis TP reduce: no (slow, fast) split exists to aggregate
        # over, so the flat form IS the strategy here
        out = jax.lax.psum(out, tp_axis)  # comm-audit: allow flat-psum
    return out


def moe_ffn_tp(p, cfg, x):
    """TP regime: all experts on every device, d_ff sharded by GSPMD."""
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    T = x2.shape[0]
    probs, sel = _route(x2, p["router"], cfg.top_k)
    cap = max(int(T * cfg.top_k / cfg.n_experts * cfg.capacity_factor), 1)
    e_id, pos = _dispatch_indices(sel, cfg.n_experts, cap)
    keep = pos < cap
    # scatter tokens into [E, cap, d]
    buf = jnp.zeros((cfg.n_experts, cap, d), x.dtype)
    safe_pos = jnp.where(keep, pos, cap - 1)
    buf = buf.at[e_id.reshape(-1), safe_pos.reshape(-1)].add(
        jnp.where(keep.reshape(-1, 1), jnp.repeat(x2, cfg.top_k, axis=0), 0))
    from .act_sharding import constrain_moe_buf
    buf = constrain_moe_buf(buf)   # keep capacity dim dp-sharded
    out_buf = _expert_ffn(p, buf, cfg.act)
    out_buf = constrain_moe_buf(out_buf)
    # combine
    y = out_buf[e_id.reshape(-1), safe_pos.reshape(-1)]
    y = y * (probs.reshape(-1, 1) * keep.reshape(-1, 1)).astype(y.dtype)
    y = y.reshape(T, cfg.top_k, d).sum(axis=1)
    return y.reshape(b, s, d)


def moe_ep_shardmap(p, cfg, x, mesh, dp_axes=("data",), ep_axes=("data",),
                    tp_axis="model", nap: bool = False, seq_axis=None):
    """Expert-parallel MoE as an explicit shard_map region (production path).

    Experts sharded over ``ep_axes`` (default: the intra-pod "data" axis →
    dispatch all-to-all never crosses pods; expert weights replicated across
    pods, synced by the hierarchical gradient path).  d_ff sharded over
    ``tp_axis``.  x: [B, S, d] (batch over dp_axes)."""
    from jax.sharding import PartitionSpec as P
    B, S, d = x.shape

    def body(xl, router, gate, up, down):
        xl2 = xl.reshape(-1, d)
        pl = {"router": router[0] if router.ndim == 3 else router,
              "gate": gate, "up": up, "down": down}
        out = moe_ffn_ep(pl, cfg, xl2, mesh_axes=ep_axes, nap=nap,
                         tp_axis=tp_axis)
        return out.reshape(xl.shape)

    x_spec = P(dp_axes if dp_axes else None, seq_axis, None)
    w_spec = P(ep_axes if len(ep_axes) > 1 else ep_axes[0], None, None)
    wd_spec = P(ep_axes if len(ep_axes) > 1 else ep_axes[0], None, None)
    # d_ff sharding over tp_axis rides on dims 2 (gate/up) and 1 (down)
    w_spec = P(w_spec[0], None, tp_axis)
    wd_spec = P(wd_spec[0], tp_axis, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(), w_spec, w_spec, wd_spec),
        out_specs=x_spec, check_vma=False,
    )(x, p["router"], p["gate"], p["up"], p["down"])


def moe_ffn_ep(p, cfg, x, mesh_axes=("model",), nap: bool = False,
               tp_axis: str | None = None):
    """EP regime inside shard_map: dispatch local tokens to expert shards.

    ``x``: the per-device token block [Tloc, d]; ``p`` holds the LOCAL
    expert slab [e_loc, d, f] (already sharded by the caller's in_specs).
    ``mesh_axes``: axes the experts are sharded over; if it includes the pod
    axis and ``nap`` is set, the dispatch uses the NAP-3 two-hop all-to-all.
    """
    T, d = x.shape
    m = 1
    for ax in mesh_axes:
        m *= jax.lax.axis_size(ax)
    E = cfg.n_experts
    e_loc = E // m
    probs, sel = _route(x, p["router"], cfg.top_k)
    cap = max(int(T * cfg.top_k / E * cfg.capacity_factor), 1)
    e_id, pos = _dispatch_indices(sel, E, cap)
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)
    send = jnp.zeros((E, cap, d), x.dtype)
    send = send.at[e_id.reshape(-1), safe_pos.reshape(-1)].add(
        jnp.where(keep.reshape(-1, 1), jnp.repeat(x, cfg.top_k, axis=0), 0))
    send = send.reshape(m, e_loc * cap * d)

    def a2a(buf):
        if len(mesh_axes) == 2 and nap:
            from ..core.nap_collectives import hier_all_to_all
            return hier_all_to_all(buf, mesh_axes[0], mesh_axes[1], "nap3")
        if len(mesh_axes) == 2:
            from ..core.nap_collectives import hier_all_to_all
            return hier_all_to_all(buf, mesh_axes[0], mesh_axes[1], "flat")
        # single expert-parallel axis: nothing hierarchical to route
        return jax.lax.all_to_all(buf, mesh_axes[0],  # comm-audit: allow flat-a2a
                                  split_axis=0, concat_axis=0, tiled=True)

    recv = a2a(send).reshape(m, e_loc, cap, d)          # [peers, e_loc, cap, d]
    h = recv.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, d)
    y = _expert_ffn(p, h, cfg.act, tp_axis=tp_axis)      # [e_loc, m*cap, d]
    y = y.reshape(e_loc, m, cap, d).transpose(1, 0, 2, 3).reshape(
        m, e_loc * cap * d)
    back = a2a(y).reshape(E, cap, d)                     # same layout as send
    out = back[e_id.reshape(-1), safe_pos.reshape(-1)]
    out = out * (probs.reshape(-1, 1) * keep.reshape(-1, 1)).astype(out.dtype)
    return out.reshape(T, cfg.top_k, d).sum(axis=1)
