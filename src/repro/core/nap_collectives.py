"""Node-aware collectives for hierarchical TPU meshes (the paper's NAP-2 /
NAP-3, recast as axis-decomposed XLA collectives — DESIGN.md §2).

"slow" axis = the expensive domain (inter-pod DCI); "fast" axis = the cheap
domain (intra-pod ICI).  All functions are written for use *inside*
``jax.shard_map`` bodies (they operate on per-device shards and named axes).

* :func:`hier_psum`       — NAP-3 all-reduce: reduce-scatter(fast) →
  psum(slow) → all-gather(fast).  Inter-pod bytes drop from s to s/|fast|.
* :func:`hier_all_gather` — all-gather(fast) then all-gather(slow): one large
  slow-axis transfer instead of |mesh| small ones (α·n reduction).
* :func:`hier_all_to_all` — 2-hop all-to-all: regroup(fast) → a2a(slow) →
  a2a(fast); slow axis carries each byte once, aggregated per pod pair.
* :class:`HaloPlan` / :func:`halo_exchange` — the paper's SpMV vector
  communication with selectable strategy (standard / nap2 / nap3), built
  host-side from a :class:`~repro.core.comm_graph.CommGraph` exactly the way
  an MPI AMG code builds its communicators, then executed as static-shape
  collectives.
* :class:`MatrixHaloPlan` / :func:`matrix_halo_exchange` — the paper's
  *matrix* communication (setup-phase SpGEMMs): whole CSR rows of B move
  under the same §3 schedules.  Rows are ragged and the setup phase runs
  once per hierarchy build, so the exchange executes host-side and
  rank-faithfully (phase by phase, message by message) rather than as
  static-shape device collectives.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from .comm_graph import CommGraph
from .schedules import Schedule, build as build_schedule

# --------------------------------------------------------------------------
# Expected-primitive signatures (the static-analysis contract)
# --------------------------------------------------------------------------
# Ordered canonical collective-primitive names each strategy lowers to, in
# trace order.  :mod:`repro.analysis.comm_audit` walks the jaxpr of every
# compiled program and checks the collectives it finds against these tables
# — change a lowering in this module and the auditor fails until the
# matching signature is updated, which is the point: the schedule the §4
# model *selected* and the schedule the program *contains* can never
# silently diverge.  "psum_scatter" is the canonical name for the jaxpr's
# ``reduce_scatter`` primitive (see repro.analysis.jaxpr_walk.CANONICAL).

# halo_exchange: per executed exchange (a plan with total_halo == 0 skips
# the exchange entirely — see halo_signature)
HALO_SIGNATURES: dict[str, tuple[str, ...]] = {
    "standard": ("all_to_all", "all_to_all"),
    "nap2": ("all_to_all", "all_gather"),
    "nap3": ("all_gather", "all_to_all", "all_gather"),
}
# hier_psum: per all-reduce (the solver's dots and norms)
REDUCE_SIGNATURES: dict[str, tuple[str, ...]] = {
    "flat": ("psum",),
    "nap3": ("psum_scatter", "psum", "all_gather"),
}
# hier_all_gather: per gather (the coarsest-level direct solve)
GATHER_SIGNATURES: dict[str, tuple[str, ...]] = {
    "flat": ("all_gather",),
    "nap3": ("all_gather", "all_gather"),
}
# hier_all_to_all: per shuffle (the MoE dispatch consumer)
ALL_TO_ALL_SIGNATURES: dict[str, tuple[str, ...]] = {
    "flat": ("all_to_all",),
    "nap3": ("all_to_all", "all_to_all"),
}


def halo_signature(plan: "HaloPlan") -> tuple[str, ...]:
    """Collectives ONE :func:`halo_exchange` under ``plan`` must lower to —
    empty when the plan moves nothing (``total_halo == 0``: the apply skips
    the exchange and the program must contain no collective for it)."""
    if plan.total_halo == 0:
        return ()
    return HALO_SIGNATURES[plan.strategy]


def reduce_signature(strategy: str) -> tuple[str, ...]:
    """Collectives one :func:`hier_psum` call with ``strategy`` lowers to."""
    return REDUCE_SIGNATURES[strategy]


def gather_signature(strategy: str = "nap3") -> tuple[str, ...]:
    """Collectives one :func:`hier_all_gather` call lowers to."""
    return GATHER_SIGNATURES[strategy]


# --------------------------------------------------------------------------
# Generic hierarchical collectives (LM training / MoE consumers)
# --------------------------------------------------------------------------


def hier_psum(x: jnp.ndarray, slow_axis: str, fast_axis: str,
              strategy: str = "nap3") -> jnp.ndarray:
    """All-reduce over (slow × fast).  ``nap3`` = RS(fast) → AR(slow) →
    AG(fast): the slow axis carries 1/|fast| of the bytes (paper Fig. 12)."""
    if strategy == "flat":
        return jax.lax.psum(x, (slow_axis, fast_axis))
    if strategy != "nap3":
        raise ValueError(f"hier_psum: unknown strategy {strategy!r}")
    fast = jax.lax.axis_size(fast_axis)
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % fast
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # 1) gather step: reduce-scatter inside the pod (cheap ICI)
    piece = jax.lax.psum_scatter(flat, fast_axis, scatter_dimension=0, tiled=True)
    # 2) single aggregated inter-pod reduction (expensive axis, 1/|fast| bytes)
    piece = jax.lax.psum(piece, slow_axis)
    # 3) redistribute inside the pod
    full = jax.lax.all_gather(piece, fast_axis, axis=0, tiled=True)
    if pad:
        full = full[:-pad]
    return full.reshape(shape)


def hier_all_gather(x: jnp.ndarray, slow_axis: str, fast_axis: str,
                    strategy: str = "nap3", axis: int = 0) -> jnp.ndarray:
    """All-gather over (slow × fast) with pod-major result layout."""
    if strategy == "flat":
        g = jax.lax.all_gather(x, (slow_axis, fast_axis), axis=axis, tiled=True)
        return g
    # gather the pod's shard first (cheap), then one aggregated slow transfer
    pod = jax.lax.all_gather(x, fast_axis, axis=axis, tiled=True)
    return jax.lax.all_gather(pod, slow_axis, axis=axis, tiled=True)


def hier_all_to_all(x: jnp.ndarray, slow_axis: str, fast_axis: str,
                    strategy: str = "nap3") -> jnp.ndarray:
    """All-to-all over the combined (slow × fast) device axis.

    ``x``: [n_slow * n_fast, ...] — chunk ``d`` goes to combined device ``d``
    (slow-major order).  Returns the received [n_slow * n_fast, ...].

    ``nap3`` routes pod-crossing chunks as ONE aggregated message per pod
    pair (split over lanes), exactly the paper's three-step scheme:
    a2a(fast) regroup → a2a(slow) inter-pod → a2a(fast) redistribute.
    """
    n_slow, n_fast = jax.lax.axis_size(slow_axis), jax.lax.axis_size(fast_axis)
    total = n_slow * n_fast
    assert x.shape[0] == total, (x.shape, total)
    if strategy == "flat":
        # one-hop: direct chunks to every device (paper's "standard") — a
        # single all-to-all whose replica groups span the slow axis.
        return jax.lax.all_to_all(x, (slow_axis, fast_axis),
                                  split_axis=0, concat_axis=0, tiled=True)
    if strategy != "nap3":
        raise ValueError(f"hier_all_to_all: unknown strategy {strategy!r}")
    # -- step 1 (intra-pod regroup): lane ℓ collects everyone's chunks for
    #    the pods it will forward to.  [dst_slow, dst_fast, ...] → group by
    #    dst_fast over the fast axis.
    x = x.reshape((n_slow, n_fast) + x.shape[1:])          # [dst_slow, dst_fast, ...]
    x = jnp.swapaxes(x, 0, 1)                               # [dst_fast, dst_slow, ...]
    x = jax.lax.all_to_all(x, fast_axis, split_axis=0, concat_axis=0, tiled=False)
    # now this lane holds, from every lane of its pod, the chunks whose
    # dst_fast == this lane: [src_fast, dst_slow, ...] — aggregated pod-pair
    # payload, 1/|fast| per lane (the paper's balanced NAP-3).
    # -- step 2 (single aggregated inter-pod transfer per pod pair)
    x = jnp.swapaxes(x, 0, 1)                               # [dst_slow, src_fast, ...]
    x = jax.lax.all_to_all(x, slow_axis, split_axis=0, concat_axis=0, tiled=False)
    # [src_slow, src_fast, ...] for traffic destined to this (pod, lane).
    return x.reshape((total,) + x.shape[2:])


# --------------------------------------------------------------------------
# Matrix-row halo exchange for distributed SpGEMM (the paper's matrix
# communication: "retains the same communication pattern as vectors, but
# requires entire rows")
# --------------------------------------------------------------------------


@dataclasses.dataclass
class MatrixHaloPlan:
    """Host-side plan for exchanging off-process CSR **rows**.

    Built from a :class:`~repro.core.comm_graph.CommGraph` whose indices are
    rows of B and whose weights are per-row byte sizes (see
    :func:`repro.amg.dist.matrix_comm_graph`: header + entries).  The
    ``schedule`` is the §3 message list for the chosen strategy — the same
    object the max-rate models price, so what :func:`repro.core.selector.
    select` selects is exactly what executes.
    """

    strategy: str
    graph: CommGraph
    schedule: Schedule

    @property
    def n_ranks(self) -> int:
        return self.graph.topo.n_procs


def build_matrix_halo_plan(graph: CommGraph, strategy: str) -> MatrixHaloPlan:
    return MatrixHaloPlan(strategy, graph, build_schedule(strategy, graph))


@dataclasses.dataclass
class MatrixExchangeResult:
    """Measured outcome of one matrix-row exchange.

    ``halo[q]`` maps each global B-row index rank ``q`` needed to the payload
    the provider returned for it; the message/byte counters are the measured
    counterparts of the modeled :class:`~repro.core.schedules.ScheduleStats`.
    """

    halo: list[dict[int, object]]
    inter_msgs: int
    inter_bytes: float
    intra_msgs: int
    intra_bytes: float
    seconds: float


def matrix_halo_exchange(plan: MatrixHaloPlan, get_row) -> MatrixExchangeResult:
    """Execute the plan rank-faithfully on the host.

    ``get_row(owner_rank, global_row) -> payload`` supplies an owned row
    (payload is opaque — e.g. a ``(cols, vals)`` pair).  Intermediate ranks
    (NAP gather/redist hops) forward rows they do not themselves need, as in
    :mod:`repro.core.simulator`; messages within a phase are concurrent and
    read from pre-phase stores.
    """
    t0 = time.perf_counter()
    g = plan.graph
    topo = g.topo
    part = g.partition
    D = topo.n_procs
    owner_lo = [part.local_range(p)[0] for p in range(D)]
    owner_hi = [part.local_range(p)[1] for p in range(D)]
    store: list[dict[int, object]] = [dict() for _ in range(D)]
    inter_msgs = intra_msgs = 0
    inter_bytes = intra_bytes = 0.0

    def serve(src: int, i: int):
        if owner_lo[src] <= i < owner_hi[src]:
            return get_row(src, i)
        try:
            return store[src][i]
        except KeyError:
            raise AssertionError(
                f"rank {src} asked to send row {i} it does not hold "
                f"(strategy {plan.strategy})") from None

    for phase in plan.schedule.phases:
        staged: list[tuple[int, dict[int, object]]] = []
        for m in phase.messages:
            payload = {int(i): serve(m.src, int(i)) for i in m.indices}
            staged.append((m.dst, payload))
            b = g.bytes_of(m.indices)
            if topo.on_same_node(m.src, m.dst):
                intra_msgs += 1
                intra_bytes += b
            else:
                inter_msgs += 1
                inter_bytes += b
        for dst, payload in staged:
            store[dst].update(payload)

    halo: list[dict[int, object]] = []
    for q in range(D):
        rows = {}
        for i in map(int, g.need[q]):
            if i not in store[q]:
                raise AssertionError(
                    f"{plan.strategy}: rank {q} never received row {i}")
            rows[i] = store[q][i]
        halo.append(rows)
    return MatrixExchangeResult(halo, inter_msgs, inter_bytes, intra_msgs,
                                intra_bytes, time.perf_counter() - t0)


# --------------------------------------------------------------------------
# Halo exchange for distributed SpMV (the paper's vector communication)
# --------------------------------------------------------------------------


def _pad_to(arrs: list[np.ndarray], width: int, fill: int) -> np.ndarray:
    out = np.full((len(arrs), width), fill, dtype=np.int32)
    for i, a in enumerate(arrs):
        out[i, : a.size] = a
    return out


@dataclasses.dataclass
class HaloPlan:
    """Static-shape device plan for one CommGraph + one (pods × lanes) mesh.

    Built on host at setup time (like an MPI communicator build); executed
    inside shard_map.  Device d = pod * lanes + lane owns the row block of
    ``partition`` for rank d; the halo buffer layout is the rank's sorted
    ``need`` array.

    standard : flat all_to_all of per-peer padded buffers (direct sends).
    nap2     : per-(device → dst pod) de-duplicated buffers, a2a over the pod
               axis between lane-peers, then an intra-pod all-gather.
    nap3     : per-(pod → pod) de-duplicated union buffers, split over lanes
               (balanced), a2a over the pod axis, then intra-pod all-gather.
    """

    strategy: str
    n_pods: int
    lanes: int
    local_n: int                 # padded local row count per device
    halo_len: int                # per-device halo width (max over devices)
    # device-stacked numpy index arrays (first dim = n_devices):
    send_idx: np.ndarray         # [D, n_targets, K] local indices to pack (-1 pad)
    recv_sel: np.ndarray         # [D, halo_len] flat index into received pool (-1 pad)
    pool_len: int                # flattened receive-pool length per device
    # nap3 only: pre-a2a lane pool selection
    pool_sel: np.ndarray | None = None   # [D, n_pods, K3] into intra-gathered pool
    contrib_len: int = 0
    # TRUE total halo entries across all devices.  ``halo_len`` is floored
    # to 1 for static shapes, so emptiness must be read here: a plan with
    # ``total_halo == 0`` moves nothing and the overlapped apply skips the
    # exchange (no ppermute/all_to_all emitted at all).
    total_halo: int = 0

    @property
    def n_devices(self) -> int:
        return self.n_pods * self.lanes


def build_halo_plan(graph: CommGraph, n_pods: int, lanes: int,
                    strategy: str) -> HaloPlan:
    topo = graph.topo
    assert topo.n_nodes == n_pods and topo.ppn == lanes, "graph topo must match mesh"
    part = graph.partition
    D = n_pods * lanes
    local_n = part.max_local_size
    need_sorted = [np.sort(graph.need[d]).astype(np.int64) for d in range(D)]
    total_halo = int(sum(n.size for n in need_sorted))
    halo_len = max((n.size for n in need_sorted), default=0) or 1

    def local_of(d, gidx):
        lo, _ = part.local_range(d)
        return (gidx - lo).astype(np.int32)

    owners = [part.owner_of_rows(need_sorted[d]) if need_sorted[d].size else
              np.zeros(0, dtype=np.int64) for d in range(D)]

    if strategy == "standard":
        # per (src d, dst e) message: what e needs from d
        msgs = [[np.zeros(0, dtype=np.int64) for _ in range(D)] for _ in range(D)]
        for e in range(D):
            for d, g in zip(owners[e], need_sorted[e]):
                msgs[int(d)][e] = np.append(msgs[int(d)][e], g)
        K = max((m.size for row in msgs for m in row), default=0) or 1
        send_idx = np.stack([
            _pad_to([local_of(d, m) if m.size else np.zeros(0, np.int64)
                     for m in msgs[d]], K, -1) for d in range(D)])
        # receive pool for device e: [D, K] from each source (flat D*K)
        pool_len = D * K
        recv_sel = np.full((D, halo_len), -1, dtype=np.int32)
        for e in range(D):
            # position of each needed gidx inside msgs[d][e]
            for j, (d, g) in enumerate(zip(owners[e], need_sorted[e])):
                d = int(d)
                k = int(np.searchsorted(msgs[d][e], g))
                recv_sel[e, j] = d * K + k
        return HaloPlan(strategy, n_pods, lanes, local_n, halo_len,
                        send_idx, recv_sel, pool_len, total_halo=total_halo)

    if strategy == "nap2":
        # per (src d, dst pod m): union of what pod m needs from d
        msgs = [[np.zeros(0, dtype=np.int64) for _ in range(n_pods)] for _ in range(D)]
        for e in range(D):
            m = e // lanes
            for d, g in zip(owners[e], need_sorted[e]):
                msgs[int(d)][m] = np.append(msgs[int(d)][m], g)
        msgs = [[np.unique(m) for m in row] for row in msgs]
        K = max((m.size for row in msgs for m in row), default=0) or 1
        send_idx = np.stack([
            _pad_to([local_of(d, m) if m.size else np.zeros(0, np.int64)
                     for m in msgs[d]], K, -1) for d in range(D)])
        # after a2a(pod) lane-peer exchange + all_gather(lane):
        # pool at device e (pod m): for lane ℓ, for src pod n:
        # msgs[n*lanes + ℓ][m]  → flat [lanes, n_pods, K]
        pool_len = lanes * n_pods * K
        recv_sel = np.full((D, halo_len), -1, dtype=np.int32)
        for e in range(D):
            m = e // lanes
            for j, (d, g) in enumerate(zip(owners[e], need_sorted[e])):
                d = int(d)
                n_src, lane_src = d // lanes, d % lanes
                k = int(np.searchsorted(msgs[d][m], g))
                recv_sel[e, j] = (lane_src * n_pods + n_src) * K + k
        return HaloPlan(strategy, n_pods, lanes, local_n, halo_len,
                        send_idx, recv_sel, pool_len, total_halo=total_halo)

    if strategy == "nap3":
        # pod-pair unions, split across lanes (balanced NAP-3)
        pair = [[np.zeros(0, dtype=np.int64) for _ in range(n_pods)]
                for _ in range(n_pods)]
        for e in range(D):
            m = e // lanes
            for d, g in zip(owners[e], need_sorted[e]):
                pair[int(d) // lanes][m] = np.append(pair[int(d) // lanes][m], g)
        pair = [[np.unique(m) for m in row] for row in pair]
        # contribution step: device d provides its owned entries of every
        # union pair[n][*]; all_gather(lane) builds the pod's pool.
        contrib = [[np.zeros(0, dtype=np.int64) for _ in range(n_pods)]
                   for _ in range(D)]
        for n in range(n_pods):
            for m in range(n_pods):
                # n == m included: same-pod traffic rides the a2a self-slab
                # (local, never crosses the network) — the TPU analogue of
                # the paper's on-node direct sends.
                own = part.owner_of_rows(pair[n][m])
                for d in range(n * lanes, (n + 1) * lanes):
                    contrib[d][m] = np.unique(np.append(
                        contrib[d][m], pair[n][m][own == d]))
        Kc = max((c.size for row in contrib for c in row), default=0) or 1
        send_idx = np.stack([
            _pad_to([local_of(d, c) if c.size else np.zeros(0, np.int64)
                     for c in contrib[d]], Kc, -1) for d in range(D)])
        contrib_len = n_pods * Kc
        # lane split of each pod-pair union
        K3 = 0
        shares: dict[tuple[int, int, int], np.ndarray] = {}
        for n in range(n_pods):
            for m in range(n_pods):
                u = pair[n][m]
                for l in range(lanes):
                    sh = u[l::lanes]
                    shares[(n, m, l)] = sh
                    K3 = max(K3, sh.size)
        K3 = K3 or 1
        # pool_sel: device d=(n,l) selects, for each dst pod m, its share out
        # of the intra-gathered pool [lanes, n_pods, Kc] (flat).
        pool_sel = np.full((D, n_pods, K3), -1, dtype=np.int32)
        for n in range(n_pods):
            for l in range(lanes):
                d = n * lanes + l
                for m in range(n_pods):
                    sh = shares[(n, m, l)]
                    own = part.owner_of_rows(sh)
                    for t, (o, g) in enumerate(zip(own, sh)):
                        o = int(o)
                        k = int(np.searchsorted(contrib[o][m], g))
                        pool_sel[d, m, t] = ((o % lanes) * n_pods + m) * Kc + k
        # receive: after a2a(pod) each device (m,l) holds shares[(n,m,l)] for
        # all n → all_gather(lane) → pool [lanes, n_pods, K3] flat.
        pool_len = lanes * n_pods * K3
        recv_sel = np.full((D, halo_len), -1, dtype=np.int32)
        for e in range(D):
            m = e // lanes
            # index of g within shares[(n, m, l)]: g is at position p in
            # pair[n][m] with lane l = p % lanes, slot p // lanes.
            for j, (d, g) in enumerate(zip(owners[e], need_sorted[e])):
                n = int(d) // lanes
                p = int(np.searchsorted(pair[n][m], g))
                l, slot = p % lanes, p // lanes
                recv_sel[e, j] = (l * n_pods + n) * K3 + slot
        return HaloPlan(strategy, n_pods, lanes, local_n, halo_len,
                        send_idx, recv_sel, pool_len,
                        pool_sel=pool_sel, contrib_len=contrib_len,
                        total_halo=total_halo)

    raise ValueError(f"unknown strategy {strategy!r}")


def halo_exchange(x_local: jnp.ndarray, plan: HaloPlan,
                  send_idx: jnp.ndarray, recv_sel: jnp.ndarray,
                  pool_sel: jnp.ndarray | None,
                  pod_axis: str = "pod", lane_axis: str = "lane") -> jnp.ndarray:
    """Inside shard_map: return this device's halo values.

    ``send_idx``/``recv_sel``/``pool_sel`` are the *per-device* slices of the
    plan arrays (sharded over the device axis ahead of time).

    ``x_local`` may carry trailing dimensions — ``[local]`` for one RHS or
    ``[local, k]`` for a multi-RHS batch; the halo is exchanged with the
    trailing dims riding along (shape ``[halo_len] + ext``), so the fused
    SpMM path moves one buffer for all k columns instead of k buffers.
    """
    ext = x_local.shape[1:]

    def _mask(idx):
        return (idx >= 0).reshape(idx.shape + (1,) * len(ext))

    safe = jnp.maximum(send_idx, 0)
    if plan.strategy == "standard":
        buf = jnp.where(_mask(send_idx), x_local[safe], 0.0)   # [D, K] + ext
        n_pods, lanes = plan.n_pods, plan.lanes
        K = send_idx.shape[-1]
        buf = buf.reshape((n_pods, lanes, K) + ext)
        buf = jax.lax.all_to_all(buf, pod_axis, split_axis=0, concat_axis=0)
        buf = jax.lax.all_to_all(buf, lane_axis, split_axis=1, concat_axis=1)
        pool = buf.reshape((plan.pool_len,) + ext)
    elif plan.strategy == "nap2":
        buf = jnp.where(_mask(send_idx), x_local[safe], 0.0)   # [n_pods, K] + ext
        buf = jax.lax.all_to_all(buf, pod_axis, split_axis=0, concat_axis=0)
        # buf now [n_pods(src), K]+ext at the lane-peer; share within the pod
        pool = jax.lax.all_gather(buf, lane_axis, axis=0)      # [lanes, n_pods, K] + ext
        pool = pool.reshape((plan.pool_len,) + ext)
    elif plan.strategy == "nap3":
        contrib = jnp.where(_mask(send_idx), x_local[safe], 0.0)  # [n_pods, Kc] + ext
        pod_pool = jax.lax.all_gather(contrib, lane_axis, axis=0)  # [lanes, n_pods, Kc] + ext
        pod_pool = pod_pool.reshape((-1,) + ext)
        sel_safe = jnp.maximum(pool_sel, 0)
        out_buf = jnp.where(_mask(pool_sel), pod_pool[sel_safe], 0.0)  # [n_pods, K3] + ext
        out_buf = jax.lax.all_to_all(out_buf, pod_axis, split_axis=0, concat_axis=0)
        pool = jax.lax.all_gather(out_buf, lane_axis, axis=0)   # [lanes, n_pods, K3] + ext
        pool = pool.reshape((plan.pool_len,) + ext)
    else:
        raise ValueError(plan.strategy)
    safe_r = jnp.maximum(recv_sel, 0)
    return jnp.where(_mask(recv_sel), pool[safe_r], 0.0)
