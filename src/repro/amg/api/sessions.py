"""Bound solvers, the session store, and its eviction policies.

A **session** is one (matrix fingerprint, :class:`AMGConfig`) pair bound to
a backend: the object that owns the expensive state — the host
``Hierarchy``, the lowered ``DistHierarchy`` (comm graphs, per-level
strategy selection, halo plans) and its compiled shard_map programs.
Sessions live in a :class:`SessionStore`, an instantiable cache with a
pluggable :class:`EvictionPolicy` (:class:`LRUPolicy`, :class:`TTLPolicy`,
:class:`BytesBudgetPolicy`) and per-entry setup-cost / hit-count accounting
(:meth:`SessionStore.stats`) — the knobs a serving deployment needs to keep
hot sessions pinned and evict cold ones *deliberately* instead of through a
fixed module-global FIFO.

:class:`AMGSolver` is the session entrypoint (``AMGSolver(cfg).setup(A)``),
defaulting to module-level stores so independent callers share sessions;
:class:`~repro.amg.api.service.AMGService` instantiates its own store so
its eviction budget and counters are service-scoped.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict

import numpy as np

from ..csr import CSR
from ..hierarchy import (Hierarchy, refresh_values as _hierarchy_refresh,
                         setup as _hierarchy_setup)
from ..solve import (MultiSolveResult, SolveOptions, SolveResult, host_pcg,
                     host_solve, host_vcycle)
from ..spans import span
from .config import (AMGConfig, PatternMismatch, RequestOptions, apply_update,
                     matrix_fingerprint, pattern_fingerprint)
from .registry import backend_class, register_backend


# --------------------------------------------------------------------------
# Session store + eviction policies
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CacheEntry:
    """One stored session with the accounting eviction policies consume."""

    value: object
    nbytes: int = 0
    setup_cost: float = 0.0       # seconds it took to build the value
    hits: int = 0
    created: float = 0.0
    last_used: float = 0.0
    # optional re-measure hook: a dist session lowers its device arrays
    # lazily on first solve, so resident bytes grow after the put — the
    # store refreshes nbytes through this before evicting or reporting
    nbytes_fn: object = dataclasses.field(default=None, repr=False,
                                          compare=False)

    def refresh_nbytes(self) -> None:
        if self.nbytes_fn is not None:
            self.nbytes = int(self.nbytes_fn())


class EvictionPolicy:
    """Decides what a :class:`SessionStore` drops.  Two hooks:

    * :meth:`expired` — per-entry staleness (checked on every access).
    * :meth:`victims` — which keys to evict after an insert (called until
      it yields nothing).
    """

    name = "none"

    def expired(self, entry: CacheEntry, now: float) -> bool:
        return False

    def victims(self, entries: "OrderedDict[object, CacheEntry]",
                now: float) -> list:
        return []


class LRUPolicy(EvictionPolicy):
    """Bounded entry count, least-recently-used first — the behavior of the
    old module-global cache (inserts and hits refresh recency)."""

    name = "lru"

    def __init__(self, max_entries: int = 16):
        self.max_entries = max(1, int(max_entries))

    def victims(self, entries, now):
        n_over = len(entries) - self.max_entries
        return list(entries)[:n_over] if n_over > 0 else []


class TTLPolicy(EvictionPolicy):
    """Idle-time-to-live: an entry not touched for ``ttl`` seconds is
    expired on its next access (plus an optional LRU entry bound)."""

    name = "ttl"

    def __init__(self, ttl: float, max_entries: int | None = None):
        self.ttl = float(ttl)
        self.max_entries = max_entries

    def expired(self, entry, now):
        return now - entry.last_used > self.ttl

    def victims(self, entries, now):
        if self.max_entries is None:
            return []
        n_over = len(entries) - self.max_entries
        return list(entries)[:n_over] if n_over > 0 else []


class BytesBudgetPolicy(EvictionPolicy):
    """Cost-aware bytes budget: while the resident total exceeds
    ``max_bytes``, evict the entry with the lowest *retention value*

        ``setup_cost * (1 + hits) / max(nbytes, 1)``

    — i.e. prefer dropping sessions that are cheap to rebuild, rarely hit,
    or disproportionately large (ties broken least-recently-used)."""

    name = "bytes_budget"

    def __init__(self, max_bytes: int, max_entries: int | None = None):
        self.max_bytes = int(max_bytes)
        self.max_entries = max_entries

    @staticmethod
    def retention_value(entry: CacheEntry) -> float:
        return entry.setup_cost * (1 + entry.hits) / max(entry.nbytes, 1)

    def victims(self, entries, now):
        out = []
        if self.max_entries is not None:
            n_over = len(entries) - self.max_entries
            if n_over > 0:
                out.extend(list(entries)[:n_over])
        # recency-ordered iteration makes the min() tie-break LRU
        live = [(k, e) for k, e in entries.items() if k not in out]
        total = sum(e.nbytes for _, e in live)
        while total > self.max_bytes and live:
            k, e = min(live, key=lambda ke: self.retention_value(ke[1]))
            out.append(k)
            live.remove((k, e))
            total -= e.nbytes
        return out


class SessionStore:
    """Keyed session cache with pluggable eviction and full accounting.

    Thread-safe (the service's admission worker and foreground callers may
    touch it concurrently).  ``clock`` is injectable for deterministic TTL
    tests."""

    def __init__(self, policy: EvictionPolicy | None = None,
                 clock=time.monotonic):
        self.policy = policy or LRUPolicy(SESSION_CACHE_SIZE)
        self._clock = clock
        self._entries: "OrderedDict[object, CacheEntry]" = OrderedDict()
        self._lock = threading.RLock()
        self._counters = {"hits": 0, "misses": 0, "puts": 0, "evictions": 0,
                          "expirations": 0, "setup_cost_evicted": 0.0,
                          "refreshes": 0, "resetups": 0}
        # streaming-update trigger reasons ("drift", "regression",
        # "pattern", "evicted", …) -> count
        self._triggers: dict[str, int] = {}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def keys(self) -> list:
        with self._lock:
            return list(self._entries)

    def get(self, key, default=None):
        now = self._clock()
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and self.policy.expired(entry, now):
                self._drop(key, entry, "expirations")
                entry = None
            if entry is None:
                self._counters["misses"] += 1
                return default
            entry.hits += 1
            entry.last_used = now
            self._counters["hits"] += 1
            self._entries.move_to_end(key)
            return entry.value

    def put(self, key, value, *, nbytes: int = 0, setup_cost: float = 0.0,
            nbytes_fn=None) -> None:
        now = self._clock()
        with self._lock:
            self._entries[key] = CacheEntry(value, int(nbytes),
                                            float(setup_cost), 0, now, now,
                                            nbytes_fn)
            self._entries.move_to_end(key)
            self._counters["puts"] += 1
            for e in self._entries.values():     # lazy lowerings may have
                e.refresh_nbytes()               # grown since their put
            for k, e in [(k, e) for k, e in self._entries.items()
                         if self.policy.expired(e, now)]:
                self._drop(k, e, "expirations")
            for k in self.policy.victims(self._entries, now):
                if k in self._entries:
                    self._drop(k, self._entries[k], "evictions")

    def _drop(self, key, entry: CacheEntry, counter: str) -> None:
        del self._entries[key]
        self._counters[counter] += 1
        self._counters["setup_cost_evicted"] += entry.setup_cost

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def rekey(self, old_key, new_key) -> None:
        """Move an entry to a new key without touching its accounting —
        a streamed update changed the value fingerprint, but the session
        object (and its setup cost / hit history) is the same."""
        with self._lock:
            entry = self._entries.pop(old_key, None)
            if entry is not None:
                self._entries[new_key] = entry
                self._entries.move_to_end(new_key)

    def note_update(self, action: str, reason: str) -> None:
        """Record a streaming update: ``action`` is ``"refresh"`` (value-only
        hierarchy reuse) or ``"resetup"`` (full node-aware re-setup),
        ``reason`` the trigger ("drift", "regression", "pattern", …)."""
        if action not in ("refresh", "resetup"):
            raise ValueError(f"unknown update action {action!r}")
        with self._lock:
            self._counters[action + "es" if action == "refresh"
                           else action + "s"] += 1
            self._triggers[reason] = self._triggers.get(reason, 0) + 1

    def stats(self) -> dict:
        """Counters + resident totals (hit/evict/setup-cost accounting)."""
        with self._lock:
            for e in self._entries.values():
                e.refresh_nbytes()
            return {**self._counters, "policy": self.policy.name,
                    "triggers": dict(self._triggers),
                    "entries": len(self._entries),
                    "bytes": sum(e.nbytes for e in self._entries.values()),
                    "setup_cost_total": sum(e.setup_cost for e in
                                            self._entries.values())}

    def entry_table(self) -> list[dict]:
        """Per-entry accounting rows (for reports / the demo's stats table)."""
        now = self._clock()
        with self._lock:
            for e in self._entries.values():
                e.refresh_nbytes()
            return [{"key": k, "nbytes": e.nbytes,
                     "setup_cost": e.setup_cost, "hits": e.hits,
                     "idle_s": now - e.last_used}
                    for k, e in self._entries.items()]


def _csr_nbytes(M) -> int:
    return int(M.indptr.nbytes + M.indices.nbytes + M.data.nbytes)


def session_nbytes(value) -> int:
    """Best-effort resident-bytes estimate for store accounting: CSR bytes
    of a host hierarchy, device-array bytes of a lowered DistHierarchy."""
    if value is None:
        return 0
    if isinstance(value, Hierarchy):
        total = 0
        for lv in value.levels:
            for M in (lv.A, lv.P, lv.R):
                if M is not None:
                    total += _csr_nbytes(M)
        return total
    if isinstance(value, BoundSolver):
        return (session_nbytes(value.hierarchy)
                + session_nbytes(getattr(value, "_dist", None)))
    arrs = getattr(value, "_arrs", None)        # DistHierarchy (duck-typed)
    if arrs is not None:
        try:
            import jax
            return int(sum(getattr(leaf, "nbytes", 0)
                           for leaf in jax.tree_util.tree_leaves(arrs)))
        except Exception:
            return 0
    return int(getattr(value, "nbytes", 0))


# --------------------------------------------------------------------------
# Bound solvers
# --------------------------------------------------------------------------


class BoundSolver:
    """A hierarchy bound to one backend: the object that owns all caching.

    Created by :meth:`AMGSolver.setup` (full session: matrix → hierarchy →
    backend lowering) or :func:`bind_hierarchy` (wrap an existing
    hierarchy).  ``solve``/``pcg`` accept ``b`` of shape ``[n]`` or
    ``[n, k]``; the multi-RHS form returns a
    :class:`~repro.amg.solve.MultiSolveResult`.
    """

    backend_name = "?"
    # ---- streaming-session state, populated by AMGSolver.setup.  A solver
    # made through bind_hierarchy has none of it and cannot stream updates.
    _fine: CSR | None = None          # canonical fine-grid CSR of the session
    pattern_fp: str | None = None     # frozen sparsity-pattern fingerprint
    _fingerprint: str | None = None   # full (values) fingerprint = store key
    _store = None                     # SessionStore holding this session
    _store_key = None
    _plevels = None                   # partitioned levels (dist-born setup)
    # convergence tracking for RefreshPolicy: baseline is the first solve
    # after the most recent (re-)setup, last the most recent solve
    baseline_iterations: int | None = None
    last_iterations: int | None = None
    last_update_reason: str | None = None   # trigger of the latest update()

    def __init__(self, config: AMGConfig, hierarchy: Hierarchy | None):
        # ``hierarchy`` is None on the setup_backend="dist" path: the levels
        # were born partitioned and no host Hierarchy ever existed.
        self.config = config
        self.hierarchy = hierarchy

    @classmethod
    def from_hierarchy(cls, h: Hierarchy, dist=None,
                       opts: SolveOptions | None = None) -> "BoundSolver":
        return cls(AMGConfig(backend=cls.backend_name,
                             opts=opts or SolveOptions()), h)

    # ------------------------------------------------------------ properties
    @property
    def A(self) -> CSR:
        if self.hierarchy is None:
            raise ValueError(
                "this solver was set up with setup_backend='dist': levels "
                "are partitioned across the mesh and no global fine-grid "
                "CSR exists")
        return self.hierarchy.levels[0].A

    @property
    def n(self) -> int:
        return self.A.nrows

    @property
    def opts(self) -> SolveOptions:
        return self.config.opts

    def staging_dtype(self) -> np.dtype:
        """Host dtype right-hand sides are staged in — the single
        conversion point between user arrays and the session's compute
        dtype.  float64 sessions stage in float64; float32/bfloat16
        sessions stage in float32 (numpy has no native bfloat16; the device
        transfer downcasts from fp32)."""
        return np.dtype(np.float64 if self.config.dtype == "float64"
                        else np.float32)

    def _check_b(self, b) -> np.ndarray:
        """Validate shape and convert ``b`` ONCE to :meth:`staging_dtype`
        (an array already in the staging dtype passes through un-copied —
        no silent float64 round-trip for fp32/bf16 sessions)."""
        b = np.asarray(b)
        if b.ndim not in (1, 2) or b.shape[0] != self.n:
            raise ValueError(f"b must be [{self.n}] or [{self.n}, k], "
                             f"got shape {b.shape}")
        return np.asarray(b, dtype=self.staging_dtype())

    # -------------------------------------------------------------- methods
    def solve(self, b, *, tol: float | None = None,
              maxiter: int | None = None, x0=None):
        res = self._solve(b, tol=tol, maxiter=maxiter, x0=x0)
        self._observe(res)
        return res

    def pcg(self, b, *, tol: float | None = None,
            maxiter: int | None = None, x0=None):
        res = self._pcg(b, tol=tol, maxiter=maxiter, x0=x0)
        self._observe(res)
        return res

    def run(self, b, options: RequestOptions | None = None):
        """One request through the unified knob set: dispatches
        ``options.method`` with its ``tol``/``maxiter``/``x0`` (``None``
        knobs resolve to the session config's defaults)."""
        o = (options or RequestOptions()).resolve(self.config)
        fn = self.pcg if o.method == "pcg" else self.solve
        return fn(b, tol=o.tol, maxiter=o.maxiter, x0=o.x0)

    def _solve(self, b, *, tol: float | None = None,
               maxiter: int | None = None, x0=None):
        raise NotImplementedError

    def _pcg(self, b, *, tol: float | None = None,
             maxiter: int | None = None, x0=None):
        raise NotImplementedError

    def vcycle(self, b, x0=None):
        raise NotImplementedError

    def _observe(self, result) -> None:
        """Track iteration counts for the adaptive re-setup policy."""
        it = getattr(result, "iterations", None)
        if it is None:
            return
        self.last_iterations = int(it)
        if self.baseline_iterations is None:
            self.baseline_iterations = int(it)

    # ---------------------------------------------------- streaming updates
    def update(self, A_new: CSR | None = None, *, data=None,
               delta=None) -> str:
        """Streaming matrix update on the session's frozen pattern.

        Exactly one of ``A_new`` (full replacement CSR), ``data`` (new
        values in CSR order) or ``delta`` (additive ΔA values).  On a
        pattern match the session performs a **value-only refresh**: the
        fine values are re-lowered onto the frozen layouts, the Galerkin
        products re-run numerically through the already-selected NAP
        schedules, and smoother factors refreshed in place — compiled
        programs are reused verbatim.  When the config's
        :class:`~repro.amg.api.config.RefreshPolicy` says convergence has
        regressed past the post-setup baseline, the update escalates to a
        full node-aware re-setup instead.  Returns the action taken
        (``"refresh"`` | ``"resetup"``).  A changed sparsity pattern
        raises :class:`~repro.amg.api.config.PatternMismatch` — callers
        escalate explicitly (the service re-runs ``setup``)."""
        if self._fine is None:
            raise ValueError(
                "streaming updates need a session created by "
                "AMGSolver.setup; this solver wraps a bare hierarchy")
        if A_new is None:
            A_new = apply_update(self._fine, data=data, delta=delta)
        elif data is not None or delta is not None:
            raise ValueError("pass A_new or data=/delta=, not both")
        fp_pat = pattern_fingerprint(A_new)
        if fp_pat != self.pattern_fp:
            raise PatternMismatch(
                f"update pattern {fp_pat[:12]} does not match the session's "
                f"frozen pattern {self.pattern_fp[:12]}; a value-only "
                f"refresh is impossible — re-run setup(A_new) for "
                f"structural changes")
        regressed = (self.last_iterations is not None and
                     self.config.refresh.regressed(self.baseline_iterations,
                                                   self.last_iterations))
        if regressed or not self._can_refresh():
            action = "resetup"
            reason = "regression" if regressed else "evicted"
            self._resetup(A_new)
            self.baseline_iterations = None
            self.last_iterations = None
        else:
            action, reason = "refresh", "drift"
            self._refresh(A_new)
        self.last_update_reason = reason
        if self._store is not None:
            self._store.note_update(action, reason)
            self._rekey(A_new)
        return action

    def _rekey(self, A_new: CSR) -> None:
        """Move the store entry onto the updated value fingerprint, so a
        later ``setup(A_new)`` under the same config hits this session."""
        fp = matrix_fingerprint(A_new)
        new_key = (fp,) + tuple(self._store_key[1:])
        self._store.rekey(self._store_key, new_key)
        self._store_key = new_key
        self._fingerprint = fp

    def _can_refresh(self) -> bool:
        return True

    def _refresh(self, A_new: CSR) -> None:
        _hierarchy_refresh(self.hierarchy, A_new)
        self._fine = self.hierarchy.levels[0].A    # re-pointed by refresh

    def _resetup(self, A_new: CSR) -> None:
        self.hierarchy = _hierarchy_setup(A_new,
                                          **self.config.setup_kwargs())
        self._fine = self.hierarchy.levels[0].A


@register_backend("host")
class HostBoundSolver(BoundSolver):
    """Reference numpy backend; multi-RHS runs k independent column solves."""

    def staging_dtype(self) -> np.dtype:
        # the numpy reference always computes in float64 (CSR data is
        # float64) — staging lower would lose precision without saving a
        # conversion, so config.dtype only matters to device backends
        return np.dtype(np.float64)

    def _per_column(self, fn, b, x0):
        cols, xs = [], []
        for j in range(b.shape[1]):
            r = fn(b[:, j], None if x0 is None else x0[:, j])
            cols.append(r)
            xs.append(r.x)
        return MultiSolveResult(np.stack(xs, axis=1), cols)

    def _solve(self, b, *, tol=None, maxiter=None, x0=None):
        b = self._check_b(b)
        tol = self.config.tol if tol is None else tol
        maxiter = self.config.maxiter if maxiter is None else maxiter
        run = lambda bc, xc: host_solve(self.hierarchy, bc, tol=tol,
                                        maxiter=maxiter, opts=self.opts,
                                        x0=xc)
        if b.ndim == 2:
            return self._per_column(run, b, x0)
        return run(b, x0)

    def _pcg(self, b, *, tol=None, maxiter=None, x0=None):
        b = self._check_b(b)
        tol = self.config.tol if tol is None else tol
        maxiter = self.config.pcg_maxiter if maxiter is None else maxiter
        run = lambda bc, xc: host_pcg(self.hierarchy, bc, tol=tol,
                                      maxiter=maxiter, opts=self.opts, x0=xc)
        if b.ndim == 2:
            return self._per_column(run, b, x0)
        return run(b, x0)

    def vcycle(self, b, x0=None):
        b = self._check_b(b)
        if b.ndim == 2:
            x0c = (lambda j: None) if x0 is None else (lambda j: x0[:, j])
            return np.stack([host_vcycle(self.hierarchy, b[:, j], x0c(j),
                                         self.opts)
                             for j in range(b.shape[1])], axis=1)
        return host_vcycle(self.hierarchy, b, x0, self.opts)


@register_backend("dist")
class DistBoundSolver(BoundSolver):
    """Device-resident backend: lazily lowers the hierarchy onto the mesh
    ONCE and reuses the ``DistHierarchy`` (and its compiled programs, cached
    inside it per option set) for every subsequent call."""

    def __init__(self, config: AMGConfig, hierarchy: Hierarchy):
        super().__init__(config, hierarchy)
        self._dist = None

    @classmethod
    def from_hierarchy(cls, h, dist=None, opts=None):
        from ..dist_solve import _ensure_dist
        self = cls(AMGConfig(backend=cls.backend_name,
                             opts=opts or SolveOptions()), h)
        self._dist = _ensure_dist(h, dist)     # raises when dist is missing
        return self

    @classmethod
    def from_dist_setup(cls, config: AMGConfig, dh) -> "DistBoundSolver":
        """Bind a hierarchy that was **born partitioned** (the
        ``setup_backend="dist"`` path): there is no host ``Hierarchy``, only
        the already-lowered ``DistHierarchy``."""
        self = cls(config, None)
        self._dist = dh
        return self

    @property
    def n(self) -> int:
        if self.hierarchy is None:
            return self._dist.levels[0].A.row_part.n
        return self.A.nrows

    def staging_dtype(self) -> np.dtype:
        # a lowering that holds float64 stages float64 (the legacy
        # bind_hierarchy path carries a default float32 config); a float64
        # session whose device holds float32 stages float64 too, for the
        # refinement around it
        if self._dist is not None and self._dist.dtype == np.float64:
            return np.dtype(np.float64)
        return super().staging_dtype()

    def _refines(self) -> bool:
        """True when the session's dtype is wider than what its device
        arrays hold: ``dtype="float64"`` with ``jax_enable_x64`` off, as on
        a TPU.  Such a session answers by :func:`refine`."""
        import jax.numpy as jnp
        return (jnp.dtype(self.config.dtype).itemsize
                > self.dist_hierarchy.dtype.itemsize)

    @property
    def dist_hierarchy(self):
        """The lowered hierarchy; built on first access, then reused.

        The build goes through the per-hierarchy ``dist_cache``, so bound
        solvers that share a hierarchy (configs differing only in iteration
        defaults, say) also share one lowering.
        """
        if self._dist is None:
            from ..dist_solve import _ensure_dist
            self._dist = _ensure_dist(self.hierarchy,
                                      self.config.dist_build_kwargs())
        return self._dist

    def _solve(self, b, *, tol=None, maxiter=None, x0=None):
        from ..dist_solve import dist_solve
        b = self._check_b(b)
        tol = self.config.tol if tol is None else tol
        maxiter = self.config.maxiter if maxiter is None else maxiter
        return self._run(dist_solve, b, tol, maxiter, x0)

    def _pcg(self, b, *, tol=None, maxiter=None, x0=None):
        from ..dist_solve import dist_pcg
        b = self._check_b(b)
        tol = self.config.tol if tol is None else tol
        maxiter = self.config.pcg_maxiter if maxiter is None else maxiter
        return self._run(dist_pcg, b, tol, maxiter, x0)

    def _run(self, method, b, tol, maxiter, x0):
        """``method`` on the device, or refined around it in float64."""
        dh = self.dist_hierarchy
        if not self._refines():
            return method(dh, b, tol=tol, maxiter=maxiter, opts=self.opts,
                          x0=x0)

        def inner(rhs, inner_tol, inner_maxiter):
            return method(dh, rhs.astype(dh.dtype), tol=inner_tol,
                          maxiter=inner_maxiter, opts=self.opts)
        A = self._fine if self._fine is not None else self.A
        return refine(inner, A, b, tol=tol, maxiter=maxiter, x0=x0)

    def vcycle(self, b, x0=None):
        from ..dist_solve import dist_vcycle
        if x0 is not None:
            raise ValueError("dist vcycle starts from x=0; x0= is not "
                             "supported on the dist backend")
        return dist_vcycle(self.dist_hierarchy, self._check_b(b), self.opts)

    # ---------------------------------------------------- streaming updates
    def _can_refresh(self) -> bool:
        # a dist-born session refreshes through its partitioned levels; if
        # they were evicted from the setup store, only a full re-setup can
        # honor the update
        return self.hierarchy is not None or self._plevels is not None

    def _refresh(self, A_new: CSR) -> None:
        if self.hierarchy is not None:
            # refreshes every lowering in the hierarchy's dist_cache; a
            # prebuilt lowering that bypassed the cache (unhashable build
            # kwargs) is refreshed explicitly
            _hierarchy_refresh(self.hierarchy, A_new)
            self._fine = self.hierarchy.levels[0].A
            cached = self.hierarchy.dist_cache.values()
            if self._dist is not None and \
                    all(dh is not self._dist for dh in cached):
                self._dist.refresh_values(self.hierarchy.levels)
            return
        from ..dist_setup import refresh_partitioned_values
        refresh_partitioned_values(self._plevels, A_new)
        if self._dist is not None:
            self._dist.refresh_values(self._plevels)
        # copy-on-write, same as the host path: never mutate the caller's A
        self._fine = CSR(self._fine.shape, self._fine.indptr,
                         self._fine.indices,
                         np.array(A_new.data, dtype=np.float64))

    def _resetup(self, A_new: CSR) -> None:
        if self.hierarchy is not None:
            super()._resetup(A_new)
            self._dist = None            # re-lowered lazily on next solve
            return
        from ...core import MACHINES
        from ..dist_setup import dist_setup_partitioned
        from ..dist_solve import DistHierarchy
        c = self.config
        plevels, records = dist_setup_partitioned(
            A_new, c.n_pods, c.lanes, params=MACHINES[c.machine],
            strategy=c.strategy, **c.setup_kwargs())
        bk = c.dist_build_kwargs()
        self._dist = DistHierarchy.from_partitioned(
            plevels, bk.pop("n_pods"), bk.pop("lanes"),
            setup_records=records, **bk)
        self._plevels = plevels
        self._fine = A_new


# --------------------------------------------------------------------------
# Float64 answers from a narrower device
# --------------------------------------------------------------------------

# A refinement segment's device solve stops once its own residual has
# fallen by this factor.  Measured with the float32 PCG on rotated
# anisotropic diffusion at 512² (ε = 0.001): at 1e-3 the float64 true
# residual at a segment's end tracks the recursive one within 1.0× and a
# solve to 1e-6 takes 137 inner iterations in 2 segments; at 1e-4 it drifts
# to 1.3–1.5× the recursive one and takes 181; at 1e-2, 142–144.
REFINE_DROP = 1e-3


def refine(inner, A: CSR, b: np.ndarray, *, tol: float, maxiter: int,
           x0=None):
    """Iterative refinement to a float64 true residual of ``tol``.

    ``inner(rhs, tol, maxiter)`` solves ``A·d = rhs`` in the device's
    precision and returns a :class:`~repro.amg.solve.SolveResult`.  Each
    segment solves for the normalised residual r/‖r‖ until its own residual
    has fallen by :data:`REFINE_DROP`; between segments the host updates
    x += ‖r‖·d and takes r = b − A·x, both in float64.  ``maxiter`` caps
    the inner iterations of all segments together (``tol=0`` runs exactly
    ``maxiter``); the result's ``residuals`` are the float64 true residual
    norms at the start and after each segment.  ``b`` of shape ``[n, k]``
    is refined column by column.

    One ``amg.refine`` span a call (``segments``, ``iterations`` and the
    largest final ``rel_residual``), holding the segments' own spans and
    one ``amg.refine.residual`` span a segment around the update and the
    residual (``rel``: the true relative residual ‖r‖/‖b‖; ``rec_rel``: the
    one the segment's own residual predicts).
    """
    multi = b.ndim == 2
    bs = list(b.T) if multi else [b]
    if x0 is None:
        x0s = [None] * len(bs)
    else:
        x0s = list(np.asarray(x0).T) if multi else [x0]
    with span("amg.refine", n=b.shape[0], columns=len(bs)) as attrs:
        runs = [_refine_column(inner, A, bj, tol, maxiter, xj)
                for bj, xj in zip(bs, x0s)]
        attrs.update(segments=sum(n for _, n, _ in runs),
                     iterations=sum(res.iterations for res, _, _ in runs),
                     rel_residual=max(rel for _, _, rel in runs))
    cols = [res for res, _, _ in runs]
    if not multi:
        return cols[0]
    return MultiSolveResult(np.stack([c.x for c in cols], axis=1), cols)


def _refine_column(inner, A: CSR, b, tol, maxiter, x0):
    """One column of :func:`refine`: its result, its number of segments
    and its final true relative residual."""
    nb = float(np.linalg.norm(b)) or 1.0
    if x0 is None:
        x, r = np.zeros_like(b), b
    else:
        x = np.array(x0, dtype=np.float64)
        r = b - A.matvec(x)
    rn = float(np.linalg.norm(r))
    hist, its, segments = [rn], 0, 0
    while its < maxiter and rn > 0 and not rn / nb < tol:
        seg = inner(r / rn, REFINE_DROP, maxiter - its)
        its += seg.iterations
        segments += 1
        with span("amg.refine.residual", nnz=A.nnz) as attrs:
            rec = rn * seg.residuals[-1] / seg.residuals[0]
            x = x + rn * np.asarray(seg.x, dtype=np.float64)
            r = b - A.matvec(x)
            rn = float(np.linalg.norm(r))
            attrs.update(rel=rn / nb, rec_rel=rec / nb)
        hist.append(rn)
    return SolveResult(x, hist, its, bool(rn / nb < tol)), segments, rn / nb


# --------------------------------------------------------------------------
# The session object + default stores
# --------------------------------------------------------------------------

SESSION_CACHE_SIZE = 16
# module-level defaults: independent AMGSolver callers share sessions, the
# way the old module-global OrderedDicts did — but these are SessionStores,
# so the same LRU behavior now comes with accounting, and services that
# want their own budget simply instantiate their own store.
_SESSIONS = SessionStore(LRUPolicy(SESSION_CACHE_SIZE))
# hierarchies keyed by (matrix fingerprint, setup kwargs) only, so configs
# that differ in solve/backend knobs share one setup (and, through the
# hierarchy's dist_cache, one lowering).  setup_backend="dist" entries hold
# a born-partitioned DistHierarchy instead of a host Hierarchy (keyed with
# the mesh/strategy/dtype knobs the lowering depends on).
_SETUPS = SessionStore(LRUPolicy(SESSION_CACHE_SIZE))


def clear_sessions() -> None:
    _SESSIONS.clear()
    _SETUPS.clear()


def session_count() -> int:
    return len(_SESSIONS)


class AMGSolver:
    """The session entrypoint: ``AMGSolver(config).setup(A)`` returns a
    :class:`BoundSolver` cached per (matrix fingerprint, config) — repeated
    setup of the same matrix under the same config is free, and every solve
    through the bound object reuses the lowered hierarchy and its compiled
    programs.  Configs that differ only in knobs irrelevant to the setup
    phase (tol/maxiter, backend, mesh, …) get distinct bound solvers that
    share ONE host hierarchy.

    ``store`` / ``setup_store`` override the module-level default
    :class:`SessionStore` s (a :class:`~repro.amg.api.service.AMGService`
    passes its own so eviction budgets and hit counters are
    service-scoped)."""

    def __init__(self, config: AMGConfig | None = None, *,
                 store: SessionStore | None = None,
                 setup_store: SessionStore | None = None, **overrides):
        if config is None:
            config = AMGConfig(**overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        backend_class(config.backend)        # fail fast on unknown backend
        self.config = config
        self.store = store if store is not None else _SESSIONS
        self.setup_store = (setup_store if setup_store is not None
                            else _SETUPS)

    def setup(self, A: CSR, *, fingerprint: str | None = None) -> BoundSolver:
        """Bind ``A`` under this config (cached).  ``fingerprint=`` skips
        re-hashing when the caller already knows the matrix fingerprint
        (the service computes it once at registration)."""
        fp = fingerprint or matrix_fingerprint(A)
        key = (fp, self.config)
        bound = self.store.get(key)
        if bound is not None:
            return bound
        t0 = time.perf_counter()
        if self.config.setup_backend == "dist":
            bound = self._setup_dist(A, fp)
        else:
            skw = self.config.setup_kwargs()
            skey = (fp, tuple(sorted(skw.items())))
            h = self.setup_store.get(skey)
            if h is None:
                t1 = time.perf_counter()
                h = _hierarchy_setup(A, **skw)
                self.setup_store.put(skey, h,
                                     nbytes=session_nbytes(h),
                                     setup_cost=time.perf_counter() - t1)
            bound = backend_class(self.config.backend)(self.config, h)
        # streaming-session state: the canonical fine CSR (the hierarchy's
        # own level-0 object on host paths, so delta updates compose), the
        # frozen pattern fingerprint and the store linkage update() re-keys
        bound._fine = (bound.hierarchy.levels[0].A
                       if bound.hierarchy is not None else A)
        bound._fingerprint = fp
        bound.pattern_fp = pattern_fingerprint(A)
        bound._store = self.store
        bound._store_key = key
        # nbytes_fn: a dist session's device arrays are lowered lazily on
        # first solve, so resident bytes are re-measured at eviction time
        self.store.put(key, bound, nbytes=session_nbytes(bound),
                       setup_cost=time.perf_counter() - t0,
                       nbytes_fn=lambda: session_nbytes(bound))
        return bound

    def _setup_dist(self, A: CSR, fp: str) -> BoundSolver:
        """The setup_backend="dist" path: run the partitioned node-aware
        setup (NAP SpGEMM Galerkin products) and bind the resulting
        DistHierarchy.  Two cache tiers mirror the host path's setup/lower
        split: the partitioned blocks are keyed by the knobs the setup loop
        depends on (setup kwargs + mesh + strategy + machine), the lowered
        DistHierarchy additionally by the lowering's one knob, dtype — so
        configs differing only in dtype re-lower but never re-run the setup
        loop, and solve-knob-only changes share both."""
        c = self.config
        base = (fp, tuple(sorted(c.setup_kwargs().items())),
                c.n_pods, c.lanes, c.strategy, c.machine)
        pkey = base + ("dist_partitioned",)
        skey = base + ("dist_lowered", c.dtype)
        dh = self.setup_store.get(skey)
        if dh is None:
            cached = self.setup_store.get(pkey)
            if cached is None:
                from ...core import MACHINES
                from ..dist_setup import dist_setup_partitioned
                t0 = time.perf_counter()
                plevels, records = dist_setup_partitioned(
                    A, c.n_pods, c.lanes, params=MACHINES[c.machine],
                    strategy=c.strategy, **c.setup_kwargs())
                self.setup_store.put(pkey, (plevels, records),
                                     setup_cost=time.perf_counter() - t0)
            else:
                plevels, records = cached
            from ..dist_solve import DistHierarchy
            bk = c.dist_build_kwargs()
            t0 = time.perf_counter()
            dh = DistHierarchy.from_partitioned(
                plevels, bk.pop("n_pods"), bk.pop("lanes"),
                setup_records=records, **bk)
            self.setup_store.put(skey, dh, nbytes=session_nbytes(dh),
                                 setup_cost=time.perf_counter() - t0)
        bound = backend_class(c.backend).from_dist_setup(c, dh)
        # partitioned blocks are the refresh target for streamed updates;
        # when they were evicted between setup and update, update()
        # escalates to a full re-setup instead
        part_cached = self.setup_store.get(pkey)
        if part_cached is not None:
            bound._plevels = part_cached[0]
        return bound
