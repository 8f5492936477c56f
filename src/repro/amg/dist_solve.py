"""Device-resident distributed AMG solve phase (paper §4 executed end-to-end).

This is the paper's central claim made runnable: node-aware communication
speeds up *every* component of the AMG solve phase — relaxation, residual,
restriction, interpolation — with the strategy chosen **per level** from the
performance models ("Optimal strategies ... are determined during the
formation of each matrix in the AMG hierarchy").

Per-level strategy-selection flow
---------------------------------
At :meth:`DistHierarchy.build` time, for every level ℓ and every solve-phase
operator — ``A_ℓ`` (smoother sweeps + residual), ``P_ℓ`` (interpolation) and
``R_ℓ`` (restriction) — we:

1. build the operator's vector communication graph
   (:func:`repro.amg.dist.vector_comm_graph` / ``rect_vector_graph``),
2. evaluate the max-rate models of Eqs. (4)–(6) for standard / NAP-2 / NAP-3
   via :func:`repro.core.selector.select`,
3. build a :class:`~repro.amg.dist_spmv.DistOperator` (padded ELL block +
   :class:`~repro.core.nap_collectives.HaloPlan`) for the winning strategy.

The coarsest level stores a dense pseudo-inverse, partitioned by rows so the
direct solve is itself distributed (all-gather of the tiny coarse residual +
a local dense matvec).

Execution
---------
The entire cycle — smoother sweeps, residual, restriction, coarse solve,
interpolation + correction — is traced into ONE jitted ``shard_map``
program (recursion unrolled over levels at trace time; W- and F-cycles
unroll their repeated coarse visits the same way, so a W-cycle is still a
single fused device program, just with 2^ℓ visits of level ℓ inlined).
Each matvec runs halo-exchange collectives for its operator's selected
strategy followed by the local product
(:func:`~repro.kernels.spmv.spmv.ell_apply`).  The block smoothers
(block-Jacobi, hybrid Gauss-Seidel) apply a per-device dense factor —
block-diagonal inverses / (D+L)⁻¹ of the device's diagonal block, lowered
alongside the ELL arrays — after the same halo'd residual, so their
communication is exactly one SpMV per sweep.  Norms and dot products for
stationary iteration and PCG use :func:`~repro.core.nap_collectives.hier_psum`
(NAP-3 all-reduce).  Only the convergence check touches the host: one scalar
residual norm per outer iteration.
"""
from __future__ import annotations

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from ..core.nap_collectives import (gather_signature, halo_signature,
                                    hier_all_gather, hier_psum,
                                    reduce_signature)
from ..core.perf_model import (TPU_V5E, MachineParams, overlap_efficiency,
                               spmv_compute_times)
from ..core.selector import select
from ..core.topology import Partition, Topology
from .dist import rect_vector_graph, schedule_comm_stats
from ..kernels.spmv.ops import select_dia, select_dist_kernel
from .dist_spmv import (DistOperator, build_dist_operator,
                        build_dist_operator_from_blocks, local_square_block)
from .hierarchy import Hierarchy
from .interpolation import estimate_rho_DinvA
from .smoothers import chebyshev_coeffs, chebyshev_recurrence
from .solve import (CYCLE_CHILDREN, MultiSolveResult, SolveOptions,
                    SolveResult, level_visits)
from .spans import span

DEV_AXES = ("pod", "lane")
SOLVE_STRATEGIES = ("standard", "nap2", "nap3")


@dataclasses.dataclass
class DistLevel:
    """Device form of one hierarchy level: operators + smoother data."""

    A: DistOperator
    dinv: np.ndarray                     # [D, rows_local] (0 on padded rows)
    P: DistOperator | None = None        # fine rows × coarse cols
    R: DistOperator | None = None        # coarse rows × fine cols
    rho: float = 1.0                     # ρ(D⁻¹A) for Chebyshev
    coarse_inv: np.ndarray | None = None  # [D, rows_local, D*rows_local]
    strategies: dict[str, str] = dataclasses.field(default_factory=dict)
    modeled: dict[str, dict[str, float]] = dataclasses.field(default_factory=dict)
    # local-kernel layout decision for A (select_dist_kernel dict: kernel,
    # block_size, ell/bcsr cost + fill) — reporting alongside the strategy
    local_kernel: dict = dataclasses.field(default_factory=dict)
    # per-op modeled message/byte counts for the selected strategy
    # (schedule_comm_stats), consumed by cycle_comm_stats
    comm_stats: dict[str, dict] = dataclasses.field(default_factory=dict)
    # on/off-process split of A (nnz counts, modeled t_on/t_off/t_comm and
    # overlap efficiency) — what the overlap-aware selector saw
    onoff: dict = dataclasses.field(default_factory=dict)
    # per-device diagonal square blocks of A (local column ids) — the
    # source the block smoothers' dense factors are lowered from
    local_A: list | None = None
    _minv_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    def smoother_minv(self, kind: str, block_size: int = 0) -> np.ndarray:
        """[D, m, m] dense smoother factor M⁻¹ (m = padded local rows).

        ``kind="bj"``: inverse of the block-diagonal of the local block
        (``block_size`` grid restarting at the device's first row — blocks
        never straddle devices).  ``kind="gs"``: inverse of the local
        (D + L) factor, i.e. hybrid forward Gauss-Seidel; ``kind="gsu"``:
        the (D + U) inverse for the backward half of the symmetric sweep.
        Padded/empty diagonals become 1 so padded rows update by exactly
        zero.
        """
        key = (kind, block_size)
        got = self._minv_cache.get(key)
        if got is not None:
            return got
        assert self.local_A is not None, "no local blocks on this level"
        m = self.A.rows_local
        out = np.zeros((len(self.local_A), m, m))
        idx = np.arange(m)
        for d, blk in enumerate(self.local_A):
            dense = np.zeros((m, m))
            dense[: blk.nrows, : blk.nrows] = blk.to_dense()
            if kind == "bj":
                same = (idx[:, None] // block_size) == (idx[None, :] // block_size)
                dense = np.where(same, dense, 0.0)
            elif kind == "gs":
                dense = np.tril(dense)
            elif kind == "gsu":
                dense = np.triu(dense)
            else:
                raise ValueError(f"unknown smoother factor kind {kind!r}")
            diag = np.diagonal(dense).copy()
            np.fill_diagonal(dense, np.where(diag == 0, 1.0, diag))
            out[d] = np.linalg.inv(dense)
        self._minv_cache[key] = out
        return out


def _dinv_blocks(A, part: Partition) -> np.ndarray:
    """[D, rows_local] D⁻¹ of ``A`` in ``part``'s device layout (an empty
    diagonal reads 1; padded rows 0)."""
    d = A.diagonal()
    dinv = 1.0 / np.where(d == 0, 1.0, d)
    out = np.zeros((part.topo.n_procs, part.max_local_size))
    for q in range(part.topo.n_procs):
        lo, hi = part.local_range(q)
        out[q, : hi - lo] = dinv[lo:hi]
    return out


def _coarse_inverse(A, part: Partition) -> np.ndarray:
    """[D, m, D·m] row blocks of the coarsest ``A``'s dense pseudo-inverse,
    columns in the all-gathered layout of the distributed direct solve."""
    pinv = np.linalg.pinv(A.to_dense())
    D, m = part.topo.n_procs, part.max_local_size
    cinv = np.zeros((D, m, D * m))
    for q in range(D):
        lo, hi = part.local_range(q)
        for e in range(D):
            elo, ehi = part.local_range(e)
            cinv[q, : hi - lo, e * m: e * m + ehi - elo] = pinv[lo:hi, elo:ehi]
    return cinv


class DistHierarchy:
    """An AMG hierarchy lowered onto a (pods × lanes) device mesh.

    Built once per hierarchy (like the MPI communicator build of a parallel
    AMG code); reusable across any number of :func:`dist_solve` /
    :func:`dist_pcg` calls.  Compiled V-cycle programs are cached per solver
    option set.  ``dtype`` is what the device arrays really hold, whatever
    precision was asked for: float64 narrows to float32 unless
    ``jax_enable_x64`` is on (a TPU has no native float64).
    """

    def __init__(self, h: Hierarchy | None, n_pods: int, lanes: int,
                 levels: list[DistLevel], mesh, dtype):
        # ``h`` is None when the hierarchy was born partitioned
        # (repro.amg.dist_setup): no host Hierarchy ever existed.
        self.h = h
        self.setup_records: list = []
        self.n_pods, self.lanes = n_pods, lanes
        self.levels = levels
        self.mesh = mesh
        self.dtype = np.dtype(jax.dtypes.canonicalize_dtype(dtype))
        # program key (traced-knob subset of opts) -> (programs dict,
        # run arrays); see :meth:`programs`
        self._programs: dict[tuple, tuple] = {}
        # (smoother kind, block_size) -> level arrays extended with the
        # lowered dense smoother factors ("minv")
        self._arrs_ex: dict[tuple, list] = {}
        spec = jax.sharding.PartitionSpec(DEV_AXES)
        sharding = jax.sharding.NamedSharding(mesh, spec)
        self._dev_spec = spec
        self._sharding = sharding
        # level arrays, transferred (and sharded) once at build time
        with span("amg.lower.place"):
            self._arrs = jax.device_put(
                [self._level_arrays(lv) for lv in levels], sharding)

    # ------------------------------------------------------------------ build
    @classmethod
    def build(cls, h: Hierarchy, n_pods: int, lanes: int, *,
              params: MachineParams = TPU_V5E,
              strategy: str = "auto",
              strategies: tuple[str, ...] = SOLVE_STRATEGIES,
              dtype=jnp.float32, mesh=None) -> "DistHierarchy":
        """Lower ``h`` onto the mesh, selecting each operator's strategy.

        ``strategy="auto"`` picks per level and per operator from the
        performance models; any explicit strategy name forces it everywhere.
        """
        if mesh is None:
            mesh = jax.make_mesh((n_pods, lanes), DEV_AXES)
        levels = cls._lower_levels(h.levels, n_pods, lanes, params=params,
                                   strategy=strategy, strategies=strategies,
                                   dtype=dtype)
        return cls(h, n_pods, lanes, levels, mesh, dtype)

    @classmethod
    def from_partitioned(cls, plevels, n_pods: int, lanes: int, *,
                         setup_records=None,
                         params: MachineParams = TPU_V5E,
                         strategy: str = "auto",
                         strategies: tuple[str, ...] = SOLVE_STRATEGIES,
                         dtype=jnp.float32, mesh=None) -> "DistHierarchy":
        """Lower levels that are **already partitioned** (born on the mesh).

        ``plevels`` mirror :class:`~repro.amg.hierarchy.Level` but each
        operator is a :class:`~repro.amg.dist_setup.BlockMatrix` (per-device
        global-shape row blocks) — the output of the distributed setup
        phase.  No host gather/re-scatter happens between setup and solve;
        ``setup_records`` (per-level SpGEMM strategy selections + measured
        exchange stats) are merged into the selection table.
        """
        if mesh is None:
            mesh = jax.make_mesh((n_pods, lanes), DEV_AXES)
        levels = cls._lower_levels(plevels, n_pods, lanes, params=params,
                                   strategy=strategy, strategies=strategies,
                                   dtype=dtype)
        for rec in setup_records or ():
            levels[rec.level].strategies[rec.op] = rec.strategy
            levels[rec.level].modeled[rec.op] = dict(rec.modeled)
        self = cls(None, n_pods, lanes, levels, mesh, dtype)
        self.setup_records = list(setup_records or ())
        return self

    @classmethod
    def _lower_levels(cls, src_levels, n_pods: int, lanes: int, *, params,
                      strategy, strategies, dtype) -> list[DistLevel]:
        """Per-level lowering shared by :meth:`build` (host ``Level`` s with
        global CSRs) and :meth:`from_partitioned` (``BlockMatrix`` levels):
        comm graphs, strategy selection, halo plans, ELL blocks."""
        topo = Topology(n_nodes=n_pods, ppn=lanes)
        D = topo.n_procs

        def choose(graph, op_name, compute=(0.0, 0.0)):
            # ``compute=(t_on, t_off)`` makes the ranking overlap-aware:
            # max(T_comm, T_on) + T_off — zero (the default, and always when
            # params.Rf is unset) reduces to the serial comm-only model
            if strategy != "auto":
                return strategy, {}, {}
            sel = select(graph, params, strategies, compute=compute)
            return sel.strategy, dict(sel.times), dict(sel.comm_times)

        def make_op(M, strat, row_part, col_part, graph):
            blocks = getattr(M, "blocks", None)
            if blocks is not None:
                return build_dist_operator_from_blocks(
                    blocks, n_pods, lanes, strat, row_part=row_part,
                    col_part=col_part, graph=graph, dtype=dtype)
            return build_dist_operator(M, n_pods, lanes, strat,
                                       row_part=row_part, col_part=col_part,
                                       graph=graph, dtype=dtype)

        def part_of(lv):
            # a BlockMatrix level carries the partition its blocks were
            # built on — reuse it rather than assuming balanced rows
            p = getattr(lv.A, "part", None)
            if p is not None:
                assert p.topo == topo, (p.topo, topo)
                return p
            return Partition.balanced(lv.A.nrows, topo)

        def onoff_compute(M, row_part, col_part):
            """Per-device max on/off nnz → modeled (t_on, t_off) split.

            Column locality (not the halo plan) decides on vs off, so the
            split is strategy-independent and can feed selection *before*
            any operator is built.
            """
            on_max = off_max = 0
            for q in range(D):
                rlo, rhi = row_part.local_range(q)
                clo, chi = col_part.local_range(q)
                sub = M.submatrix_rows(rlo, rhi)
                on = int(((sub.indices >= clo) & (sub.indices < chi)).sum())
                on_max = max(on_max, on)
                off_max = max(off_max, sub.nnz - on)
            return spmv_compute_times(params, on_max, off_max)

        parts = [part_of(lv) for lv in src_levels]
        levels: list[DistLevel] = []
        for l, lv in enumerate(src_levels):
            part = parts[l]
            has_coarser = lv.P is not None and l + 1 < len(src_levels)
            if lv.P is not None and not has_coarser:
                # a stall-pop in setup leaves a dangling P on the last
                # level; its A is by construction too large to treat as the
                # coarsest grid, so fail loudly rather than dense-solving it
                raise ValueError(
                    f"level {l} has P but no coarser level (coarsening "
                    f"stalled); refusing the dense coarse solve at "
                    f"n={lv.A.nrows}")
            with span("amg.lower.plan", level=l):
                gA = rect_vector_graph(lv.A, part, part)
                compA = onoff_compute(lv.A, part, part)
                sA, tA, cA = choose(gA, "spmv_A", compA)
                Aop = make_op(lv.A, sA, part, part, gA)
                nnz = Aop.onoff_nnz()
                with span("amg.lower.layout", level=l) as layout:
                    sel = cls._lower_layout(Aop, l + 1 < len(src_levels))
                    dia = Aop.dia_offsets is not None
                    layout.update(
                        layout=Aop.local_kernel,
                        diagonals=len(Aop.dia_offsets) if dia else 0,
                        nnz=nnz["on_nnz"] + nnz["off_nnz"],
                        dia_nnz=nnz["on_nnz"] if dia else 0)
                chosen, modeled = {"spmv_A": sA}, {"spmv_A": tA}
                comm_stats = {"spmv_A": schedule_comm_stats(gA, sA)}
                t_on, t_off = compA
                t_comm = cA.get(sA, 0.0)
                onoff = {**nnz, "local_nnz": nnz["on_nnz"] + nnz["off_nnz"],
                         "halo_empty": Aop.halo_empty,
                         "t_on": t_on, "t_off": t_off, "t_comm": t_comm,
                         "eff_modeled": overlap_efficiency(t_comm, t_on,
                                                           t_off)}
                Pop = Rop = None
                if has_coarser:
                    cpart = parts[l + 1]
                    gP = rect_vector_graph(lv.P, part, cpart)
                    sP, tP, _ = choose(gP, "interp",
                                       onoff_compute(lv.P, part, cpart))
                    Pop = make_op(lv.P, sP, part, cpart, gP)
                    gR = rect_vector_graph(lv.R, cpart, part)
                    sR, tR, _ = choose(gR, "restrict",
                                       onoff_compute(lv.R, cpart, part))
                    Rop = make_op(lv.R, sR, cpart, part, gR)
                    chosen.update(interp=sP, restrict=sR)
                    modeled.update(interp=tP, restrict=tR)
                    comm_stats["interp"] = schedule_comm_stats(gP, sP)
                    comm_stats["restrict"] = schedule_comm_stats(gR, sR)
                with span("amg.lower.remote", level=l) as remote:
                    # the rows the off-process products run over, of the
                    # level's operators that exchange a halo
                    haloed = [op for op in (Aop, Pop, Rop)
                              if op is not None and not op.halo_empty]
                    remote.update(
                        rows=sum(op.rows_local for op in haloed),
                        remote_rows=sum(op.remote_rows for op in haloed))
            with span("amg.lower.factors", level=l):
                dl = DistLevel(A=Aop, dinv=_dinv_blocks(lv.A, part), P=Pop,
                               R=Rop, strategies=chosen, modeled=modeled,
                               local_kernel=sel, comm_stats=comm_stats,
                               onoff=onoff)
                if has_coarser:
                    dl.rho = estimate_rho_DinvA(lv.A)
                    # diagonal square blocks feed the block smoothers' dense
                    # factors (the coarsest level never smooths)
                    dl.local_A = [local_square_block(lv.A, part, q)
                                  for q in range(D)]
                else:
                    # coarsest: distributed dense pseudo-inverse solve
                    dl.coarse_inv = _coarse_inverse(lv.A, part)
            levels.append(dl)
        return levels

    @staticmethod
    def _lower_layout(Aop: DistOperator, smooths: bool) -> dict:
        """Choose and lower ``A``'s local-product layout; returns the
        :func:`~repro.kernels.spmv.ops.select_dist_kernel` dict with the
        layout taken.

        On a level that smooths (A only — P/R are too rectangular or
        scattered, and the coarsest A never runs a SpMV, its solve being
        dense): MXU-blocked BCSR where the heuristic picks it; else DIA
        for the on-part when its distinct offsets ``col − row`` number no
        more than its ELL width (:func:`~repro.kernels.spmv.ops.select_dia`);
        else ELL.
        """
        sel = select_dist_kernel(Aop.ell_cols)
        if not smooths:
            return dict(sel, kernel="ell", block_size=0)
        if sel["kernel"] == "bcsr":
            Aop.lower_bcsr(sel["block_size"])
            return sel
        offsets = select_dia(Aop.on_cols)
        if offsets is not None:
            Aop.lower_dia(offsets)
            return dict(sel, kernel="dia", block_size=0)
        return dict(sel, kernel="ell", block_size=0)

    # ------------------------------------------------------------- reporting
    def selection_table(self) -> list[dict]:
        """One row per (level, op): chosen strategy + modeled seconds."""
        rows = []
        for l, dl in enumerate(self.levels):
            for op, s in dl.strategies.items():
                rows.append({"level": l, "op": op, "strategy": s,
                             "modeled": dict(dl.modeled.get(op, {}))})
        return rows

    def summary(self) -> str:
        out = [f"dist hierarchy: {len(self.levels)} levels on "
               f"{self.n_pods}x{self.lanes} mesh"]
        for row in self.selection_table():
            times = row["modeled"]
            ts = " ".join(f"{k}={v * 1e6:.1f}us" for k, v in times.items())
            out.append(f"  L{row['level']:<2d} {row['op']:<8s} -> "
                       f"{row['strategy']:<8s} {ts}")
        return "\n".join(out)

    def kernel_table(self) -> list[dict]:
        """One row per level: the local-kernel layout decision for A.

        ``kernel`` is what actually runs ('bcsr' only when the operator was
        lowered); the cost/fill columns are the heuristic's inputs
        (:func:`repro.kernels.spmv.ops.select_dist_kernel`), kept so
        reports can show *why* a level picked its layout.
        """
        rows = []
        for l, dl in enumerate(self.levels):
            sel = dl.local_kernel
            oo = dl.onoff
            rows.append({
                "level": l,
                "kernel": dl.A.local_kernel,
                "block_size": dl.A.block_size,
                "diagonals": len(dl.A.dia_offsets or ()),
                "rows_local": dl.A.rows_local,
                "ell_fill": sel.get("ell_fill", 0.0),
                "bcsr_fill": sel.get("bcsr_fill", 0.0),
                "ell_cost": sel.get("ell_cost", 0.0),
                "bcsr_cost": sel.get("bcsr_cost", float("inf")),
                "on_nnz": oo.get("on_nnz", 0),
                "off_nnz": oo.get("off_nnz", 0),
                "halo_empty": oo.get("halo_empty", False),
                "overlap_eff_modeled": oo.get("eff_modeled", 0.0),
            })
        return rows

    # ----------------------------------------------------- streaming refresh
    def refresh_values(self, src_levels) -> None:
        """Value-only refresh onto the frozen lowered layouts.

        ``src_levels`` are the refreshed source levels (host ``Level`` s or
        partitioned ``BlockMatrix`` levels — the same two shapes
        :meth:`_lower_levels` accepts) whose sparsity patterns must match
        what this hierarchy was lowered from.  Every structural artifact —
        comm graphs, selected strategies, halo plans, ELL/BCSR column maps,
        shardings — is reused verbatim; only value planes, diagonals,
        smoother factors, Chebyshev bounds and the coarse pseudo-inverse
        are recomputed.  The per-level device dicts are mutated **in
        place** because every cached ``(progs, run_arrs)`` tuple holds
        those same dict objects: compiled programs pick up the new
        operands on their next call without retracing.  Chebyshev programs
        are the one exception — they bake ``chebyshev_coeffs(rho)`` as
        trace-time constants, so their cache entries are dropped.
        """
        def block_of(M):
            blocks = getattr(M, "blocks", None)
            if blocks is not None:
                return lambda d: blocks[d]
            return lambda d: M

        D = self.n_pods * self.lanes
        for lv, dl in zip(src_levels, self.levels):
            part = dl.A.row_part
            dl.A.refresh_values(block_of(lv.A))
            dl.dinv = _dinv_blocks(lv.A, part)
            if dl.P is not None:
                dl.P.refresh_values(block_of(lv.P))
                dl.R.refresh_values(block_of(lv.R))
                dl.rho = estimate_rho_DinvA(lv.A)
                dl.local_A = [local_square_block(lv.A, part, q)
                              for q in range(D)]
                dl._minv_cache.clear()
            else:
                dl.coarse_inv = _coarse_inverse(lv.A, part)
        placed = jax.device_put(
            [self._level_arrays(dl) for dl in self.levels], self._sharding)
        for old, new in zip(self._arrs, placed):
            old.update(new)
        for key, lst in self._arrs_ex.items():
            for dl, base, a in zip(self.levels, self._arrs, lst):
                a.update(base)
                if dl.coarse_inv is None:
                    for name, kind in self._MINV_ARRS[key[0]]:
                        mv = dl.smoother_minv(kind, key[1]).astype(self.dtype)
                        a[name] = jax.device_put(mv, self._sharding)
        for key in [k for k in self._programs if k[1] == "chebyshev"]:
            del self._programs[key]

    # ----------------------------------------------------------- host layout
    def scatter(self, x: np.ndarray, level: int = 0) -> jnp.ndarray:
        arr = self.levels[level].A.scatter_x(np.asarray(x), dtype=self.dtype)
        return jax.device_put(arr, self._sharding)

    def gather(self, x_dev, level: int = 0) -> np.ndarray:
        return self.levels[level].A.gather_y(np.asarray(x_dev))

    # --------------------------------------------------------- device pieces
    def _level_arrays(self, dl: DistLevel) -> dict:
        a = {"A": dl.A.device_arrays(),
             "dinv": dl.dinv.astype(self.dtype)}
        if dl.P is not None:
            a["P"] = dl.P.device_arrays()
            a["R"] = dl.R.device_arrays()
        if dl.coarse_inv is not None:
            a["cinv"] = dl.coarse_inv.astype(self.dtype)
        return a

    def _psum(self, part):
        return hier_psum(part, *DEV_AXES, strategy="nap3")

    def _pdot(self, a, b, axis=None):
        """Global dot, replicated; ``axis=0`` gives the per-column dots
        [k] of [local, k] operands."""
        with jax.named_scope("pcg.dot"):
            return self._psum(jnp.sum(a * b, axis=axis))

    def _pnorm(self, r, axis=None):
        with jax.named_scope("pcg.dot"):
            return jnp.sqrt(self._psum(jnp.sum(r * r, axis=axis)))

    def _relax(self, dl: DistLevel, arrs: dict, x, b, opts, sweeps: int):
        if sweeps == 0:
            return x
        aA = arrs["A"]
        # [local, k] operands on the native SpMM path: the elementwise D⁻¹
        # scaling broadcasts over the trailing RHS axis
        dinv = arrs["dinv"]
        if x.ndim == 2:
            dinv = dinv[:, None]
        if opts.smoother == "jacobi":
            for _ in range(sweeps):
                x = x + opts.omega * dinv * (b - dl.A.apply(aA, x))
            return x
        if opts.smoother in ("block_jacobi", "hybrid_gs"):
            # x += w · M⁻¹ (b − A x): the halo'd residual carries every
            # off-device coupling, the dense local factor does the rest
            minv = arrs["minv"]
            w = opts.omega if opts.smoother == "block_jacobi" else 1.0
            for _ in range(sweeps):
                x = x + w * (minv @ (b - dl.A.apply(aA, x)))
            return x
        if opts.smoother == "hybrid_gs_sym":
            # forward (D+L)⁻¹ then backward (D+U)⁻¹ half-sweep, each with a
            # freshly halo'd residual — 2 SpMVs/sweep, symmetric smoother
            minv, minv_u = arrs["minv"], arrs["minv_u"]
            for _ in range(sweeps):
                x = x + (minv @ (b - dl.A.apply(aA, x)))
                x = x + (minv_u @ (b - dl.A.apply(aA, x)))
            return x
        # Chebyshev via the recurrence shared with the host backend, the
        # matvec swapped for the level's distributed SpMV
        degree = opts.cheby_degree * sweeps
        theta, delta, sigma = chebyshev_coeffs(dl.rho)
        return chebyshev_recurrence(
            lambda v: dl.A.apply(aA, v), dinv, x, b, degree,
            theta, delta, sigma)

    def _cycle_dev(self, arrs, b, x, opts, level: int = 0,
                   shape: str | None = None):
        """One cycle, fully on device.  The per-shape coarse revisits of
        :data:`~repro.amg.solve.CYCLE_CHILDREN` are unrolled at trace time,
        so W/F-cycles stay ONE jitted shard_map program."""
        shape = shape or opts.cycle
        dl = self.levels[level]
        a = arrs[level]
        tag = f"L{level}."                            # the ops' scope names
        if dl.coarse_inv is not None:                 # coarsest: direct solve
            with jax.named_scope(tag + "coarse"):
                full = hier_all_gather(b, *DEV_AXES)  # [D * rows_local]
                return a["cinv"] @ full
        with jax.named_scope(tag + "presmooth"):
            if x is None:
                x = jnp.zeros_like(b)
            x = self._relax(dl, a, x, b, opts, opts.presweeps)
        with jax.named_scope(tag + "residual"):
            r = b - dl.A.apply(a["A"], x)
        with jax.named_scope(tag + "restrict"):
            rc = dl.R.apply(a["R"], r)
        ec = None
        # the coarse-grid solve(s) stay outside this level's phase scopes,
        # so an op carries exactly one level's name
        for child in CYCLE_CHILDREN[shape]:
            ec = self._cycle_dev(arrs, rc, ec, opts, level + 1, shape=child)
        with jax.named_scope(tag + "interp"):
            x = x + dl.P.apply(a["P"], ec)
        with jax.named_scope(tag + "postsmooth"):
            x = self._relax(dl, a, x, b, opts, opts.postsweeps)
        return x

    # ------------------------------------------------------------- programs
    # extra dense factors per smoother: array name -> minv kind
    _MINV_ARRS = {"bj": (("minv", "bj"),),
                  "gs": (("minv", "gs"),),
                  "gs_sym": (("minv", "gs"), ("minv_u", "gsu"))}

    def _smoother_arrs_key(self, opts) -> tuple | None:
        """Key of the extra lowered arrays ``opts``'s smoother needs."""
        if opts.smoother == "block_jacobi":
            return ("bj", opts.block_size)
        if opts.smoother == "hybrid_gs":
            return ("gs", 0)
        if opts.smoother == "hybrid_gs_sym":
            return ("gs_sym", 0)
        return None

    def run_arrays(self, opts) -> list:
        """Per-level device arrays for one option set.

        Jacobi/Chebyshev run on the base arrays; the block smoothers get the
        base dicts extended with their dense local factor (``minv``, lowered
        lazily once per (kind, block_size) and shared across option sets —
        the base ELL/halo arrays are shared by reference, never re-placed).
        """
        key = self._smoother_arrs_key(opts)
        if key is None:
            return self._arrs
        got = self._arrs_ex.get(key)
        if got is None:
            got = []
            for dl, base in zip(self.levels, self._arrs):
                a = dict(base)
                if dl.coarse_inv is None:
                    for name, kind in self._MINV_ARRS[key[0]]:
                        mv = dl.smoother_minv(kind, key[1]).astype(self.dtype)
                        a[name] = jax.device_put(mv, self._sharding)
                got.append(a)
            self._arrs_ex[key] = got
        return got

    def programs(self, opts) -> tuple:
        """``(progs, arrs)`` for one option set (cached per ``opts``).

        ``progs`` holds the jitted shard_map programs — the cycle shape and
        smoother are baked in at trace time — and ``arrs`` the matching
        per-level device arrays to pass them (:meth:`run_arrays`).
        Single-RHS programs take [local] vectors; the ``*_m`` variants take
        [local, k] multi-RHS blocks: the cycle traces directly on the
        [local, k] operands, so every SpMV is a native SpMM reading each
        operator's nonzeros once for all k columns and exchanging ONE
        fused halo buffer, and norms/dots come back as replicated [k]
        vectors.

        The cache key covers only the knobs the traced program reads —
        host-reference-only knobs (``smoother_parts``; ``block_size`` for
        non-block smoothers) never force a bitwise-identical re-compile.
        """
        key = (opts.cycle, opts.smoother, opts.presweeps, opts.postsweeps,
               opts.omega, opts.cheby_degree, self._smoother_arrs_key(opts))
        if key in self._programs:
            return self._programs[key]
        run_arrs = self.run_arrays(opts)
        dev = self._dev_spec
        rep = jax.sharding.PartitionSpec()
        mesh = self.mesh

        def squeeze(t):
            return jax.tree_util.tree_map(lambda v: v[0], t)

        def smap(f, in_specs, out_specs):
            return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                         out_specs=out_specs, check_vma=False))

        def spmv0(arrs, x):                         # [local(, k)]
            return self.levels[0].A.apply(arrs[0]["A"], x)

        def residual0(arrs, x, b):                  # b − A·x on level 0
            with jax.named_scope("L0.residual"):
                return b - spmv0(arrs, x)

        def resid_norm_body(x, b, arrs):
            x, b, arrs = x[0], b[0], squeeze(arrs)
            return self._pnorm(residual0(arrs, x, b))

        def resid_norm_m_body(x, b, arrs):
            x, b, arrs = x[0], b[0], squeeze(arrs)
            return self._pnorm(residual0(arrs, x, b), axis=0)

        def cycle_body(x, b, arrs):
            x, b, arrs = x[0], b[0], squeeze(arrs)
            x = self._cycle_dev(arrs, b, x, opts)
            return x[None], self._pnorm(residual0(arrs, x, b))

        def cycle_m_body(x, b, arrs):
            x, b, arrs = x[0], b[0], squeeze(arrs)
            x = self._cycle_dev(arrs, b, x, opts)
            return x[None], self._pnorm(residual0(arrs, x, b), axis=0)

        def vcycle_body(b, arrs):
            b, arrs = b[0], squeeze(arrs)
            return self._cycle_dev(arrs, b, None, opts)[None]

        def vcycle_m_body(b, arrs):
            b, arrs = b[0], squeeze(arrs)
            return self._cycle_dev(arrs, b, None, opts)[None]

        def pcg_init_body(x, b, arrs):
            x, b, arrs = x[0], b[0], squeeze(arrs)
            r = residual0(arrs, x, b)               # x0 warm start
            z = self._cycle_dev(arrs, r, None, opts)
            rz = self._pdot(r, z)
            return r[None], z[None], rz, self._pnorm(r)

        def pcg_init_m_body(x, b, arrs):
            x, b, arrs = x[0], b[0], squeeze(arrs)
            r = residual0(arrs, x, b)
            z = self._cycle_dev(arrs, r, None, opts)
            rz = self._pdot(r, z, axis=0)
            return r[None], z[None], rz, self._pnorm(r, axis=0)

        def pcg_step_body(x, r, p, rz, arrs):
            x, r, p = x[0], r[0], p[0]
            arrs = squeeze(arrs)
            with jax.named_scope("L0.Ap"):
                Ap = spmv0(arrs, p)
            pAp = self._pdot(p, Ap)
            with jax.named_scope("pcg.update"):
                alpha = rz / pAp
                x = x + alpha * p
                r = r - alpha * Ap
            rnorm = self._pnorm(r)
            z = self._cycle_dev(arrs, r, None, opts)
            rz_new = self._pdot(r, z)
            with jax.named_scope("pcg.update"):
                p = z + (rz_new / rz) * p
            return x[None], r[None], p[None], rz_new, rnorm

        def pcg_step_m_body(x, r, p, rz, arrs):
            x, r, p = x[0], r[0], p[0]              # [local, k]; rz [k]
            arrs = squeeze(arrs)
            with jax.named_scope("L0.Ap"):
                Ap = spmv0(arrs, p)
            den = self._pdot(p, Ap, axis=0)
            with jax.named_scope("pcg.update"):
                # columns that already converged exactly (rz = pAp = 0,
                # e.g. a zero RHS) must not poison the batch with 0/0 NaNs:
                # guard the divisions so such columns step by exactly zero
                alpha = rz / jnp.where(den == 0, 1.0, den)  # [k], on cols
                x = x + alpha * p
                r = r - alpha * Ap
            rnorm = self._pnorm(r, axis=0)
            z = self._cycle_dev(arrs, r, None, opts)
            rz_new = self._pdot(r, z, axis=0)
            with jax.named_scope("pcg.update"):
                p = z + (rz_new / jnp.where(rz == 0, 1.0, rz)) * p
            return x[None], r[None], p[None], rz_new, rnorm

        progs = {
            "resid_norm": smap(resid_norm_body, (dev, dev, dev), rep),
            "cycle": smap(cycle_body, (dev, dev, dev), (dev, rep)),
            "vcycle": smap(vcycle_body, (dev, dev), dev),
            "pcg_init": smap(pcg_init_body, (dev, dev, dev),
                             (dev, dev, rep, rep)),
            "pcg_step": smap(pcg_step_body, (dev, dev, dev, rep, dev),
                             (dev, dev, dev, rep, rep)),
            "resid_norm_m": smap(resid_norm_m_body, (dev, dev, dev), rep),
            "cycle_m": smap(cycle_m_body, (dev, dev, dev), (dev, rep)),
            "vcycle_m": smap(vcycle_m_body, (dev, dev), dev),
            "pcg_init_m": smap(pcg_init_m_body, (dev, dev, dev),
                               (dev, dev, rep, rep)),
            "pcg_step_m": smap(pcg_step_m_body, (dev, dev, dev, rep, dev),
                               (dev, dev, dev, rep, rep)),
        }
        self._programs[key] = (progs, run_arrs)
        return self._programs[key]

    # ------------------------------------------------- static-analysis hooks
    # Introspection surface consumed by repro.analysis.comm_audit: trace any
    # compiled program / single apply to its ClosedJaxpr, and state the
    # collective structure the selected strategies predict for it.  Tracing
    # is abstract — nothing runs on devices.

    def expected_apply_signature(self, level: int,
                                 op: str = "A") -> tuple[str, ...]:
        """Ordered collectives ONE apply of ``levels[level].<op>`` must
        lower to (the operator's selected halo-exchange strategy; empty on
        an empty-halo level)."""
        return getattr(self.levels[level], op).expected_signature

    def trace_apply(self, level: int, op: str = "A", *,
                    k: int | None = None):
        """ClosedJaxpr of one shard_mapped apply of ``levels[level].<op>``
        (``k`` adds a trailing multi-RHS axis)."""
        dop = getattr(self.levels[level], op)
        arrs = self._arrs[level][op]
        dev = self._dev_spec

        def body(x, a):
            x = x[0]
            a = jax.tree_util.tree_map(lambda v: v[0], a)
            return dop.apply(a, x)[None]

        fn = jax.shard_map(body, mesh=self.mesh, in_specs=(dev, dev),
                           out_specs=dev, check_vma=False)
        D = self.n_pods * self.lanes
        shape = (D, dop.plan.local_n) + (() if k is None else (k,))
        return jax.make_jaxpr(fn)(jnp.zeros(shape, self.dtype), arrs)

    def trace_program(self, name: str, opts=None, k: int = 2):
        """ClosedJaxpr of the compiled fused program ``name`` for ``opts``
        (the exact cached callables :meth:`programs` hands the solvers,
        traced on zero operands of the program's shapes; ``k`` is the
        multi-RHS width of the ``*_m`` variants)."""
        opts = opts or SolveOptions()
        progs, arrs = self.programs(opts)
        D = self.n_pods * self.lanes
        n = self.levels[0].A.plan.local_n
        multi = name.endswith("_m")
        vec = jnp.zeros((D, n, k) if multi else (D, n), self.dtype)
        rz = jnp.zeros((k,) if multi else (), self.dtype)
        base = name[:-2] if multi else name
        args = {"resid_norm": (vec, vec, arrs),
                "cycle": (vec, vec, arrs),
                "vcycle": (vec, arrs),
                "pcg_init": (vec, vec, arrs),
                "pcg_step": (vec, vec, vec, rz, arrs)}[base]
        return jax.make_jaxpr(progs[name])(*args)

    def _cycle_collectives(self, opts) -> Counter:
        """Per-primitive collective counts ONE cycle of ``opts`` predicts:
        the same visits × (sweeps + residual + restrict + interpolate)
        arithmetic as :func:`cycle_comm_stats`, but counting each selected
        strategy's lowered primitives instead of modeled messages."""
        visits = level_visits(len(self.levels), opts.cycle)
        sweep_spmvs = opts.spmvs_per_sweep() * (opts.presweeps
                                                + opts.postsweeps)
        cnt: Counter = Counter()

        def add(sig, times=1):
            for p in sig:
                cnt[p] += times

        for l, dl in enumerate(self.levels):
            if dl.coarse_inv is not None:
                # distributed direct solve: hier_all_gather of the coarse
                # residual (default NAP-3 lowering)
                add(gather_signature("nap3"), visits[l])
            else:
                add(halo_signature(dl.A.plan), (sweep_spmvs + 1) * visits[l])
                add(halo_signature(dl.R.plan), visits[l])
                add(halo_signature(dl.P.plan), visits[l])
        return cnt

    def expected_collectives(self, opts=None,
                             name: str = "cycle") -> dict[str, int]:
        """Per-primitive collective counts the lowered fused program
        ``name`` must contain — cycle structure plus the program's own
        top-level SpMV and all-reduce calls.  The ``*_m`` variants are
        identical: a batched collective is still one equation."""
        opts = opts or SolveOptions()
        base = name[:-2] if name.endswith("_m") else name
        total: Counter = Counter()

        def add(sig, times=1):
            for p in sig:
                total[p] += times

        if base in ("cycle", "vcycle", "pcg_init", "pcg_step"):
            total += self._cycle_collectives(opts)
        if base in ("resid_norm", "cycle", "pcg_init", "pcg_step"):
            add(halo_signature(self.levels[0].A.plan))   # top-level residual
        add(reduce_signature("nap3"),
            {"resid_norm": 1, "cycle": 1, "vcycle": 0,
             "pcg_init": 2, "pcg_step": 3}[base])
        return {p: c for p, c in total.items() if c}


# --------------------------------------------------------------------------
# Solver drivers (host loop = convergence check only)
# --------------------------------------------------------------------------


# defaults of DistHierarchy.build, used to normalize cache keys so kwargs
# dicts that spell a default explicitly hit the same entry
_BUILD_DEFAULTS = dict(params=TPU_V5E, strategy="auto",
                       strategies=SOLVE_STRATEGIES, dtype=jnp.float32,
                       mesh=None)
DIST_CACHE_SIZE = 8


def _freeze_kwargs(kw: dict) -> tuple | None:
    """Hashable cache key for a DistHierarchy.build kwargs dict (normalized
    against the build defaults), or ``None`` when any value is unhashable
    (an explicit mesh, say) — such calls are not cached rather than risking
    a stale hit keyed on a recycled id."""
    items = []
    for k, v in sorted({**_BUILD_DEFAULTS, **kw}.items()):
        try:
            hash(v)
        except TypeError:
            return None
        items.append((k, v))
    return tuple(items)


def _ensure_dist(h, dist, **build_kwargs) -> DistHierarchy:
    """Resolve the legacy ``dist=`` argument to a DistHierarchy.

    A kwargs dict is resolved through the per-hierarchy ``dist_cache`` so
    repeated ``solve(..., backend="dist", dist={...})`` calls reuse ONE
    lowered hierarchy (comm graphs, strategy selection, compiled programs)
    instead of rebuilding it every call.
    """
    if isinstance(h, DistHierarchy):
        return h
    if isinstance(dist, DistHierarchy):
        return dist
    if dist is None:
        raise ValueError(
            "backend='dist' needs dist=: pass a prebuilt DistHierarchy "
            "(reused across calls) or a DistHierarchy.build kwargs dict "
            "with at least n_pods and lanes")
    kw = dict(dist)
    kw.update(build_kwargs)
    key = _freeze_kwargs(kw)
    cache = getattr(h, "dist_cache", None)
    if cache is not None and key is not None and key in cache:
        return cache[key]
    try:
        n_pods, lanes = kw.pop("n_pods"), kw.pop("lanes")
    except KeyError as e:
        raise ValueError(f"dist= kwargs dict must set {e.args[0]!r}") from None
    dh = DistHierarchy.build(h, n_pods, lanes, **kw)
    if cache is not None and key is not None:
        cache[key] = dh
        while len(cache) > DIST_CACHE_SIZE:      # oldest-first eviction
            cache.pop(next(iter(cache)))
    return dh


def _norms(b: np.ndarray):
    """Per-column norms of b as a denominator: [k] for [n, k], scalar else."""
    nb = np.linalg.norm(b, axis=0)
    return np.where(nb == 0, 1.0, nb)


def cycle_comm_stats(dh: DistHierarchy, opts=None) -> dict:
    """Modeled communication of ONE cycle of ``opts``'s shape + smoother.

    Multiplies each level's per-op message/byte counts (the selected
    strategy's :func:`~repro.amg.dist.schedule_comm_stats`) by the number
    of SpMVs a visit costs and by the cycle shape's per-level visit counts
    — the quantity that makes W/F-cycles coarse-level-communication heavy
    and hence where NAP-2/NAP-3 aggregation pays.  ``coarse_*`` totals
    cover levels ≥ 1 (the coarsest direct solve is an all-gather, not a
    halo exchange, and is excluded).
    """
    opts = opts or SolveOptions()
    visits = level_visits(len(dh.levels), opts.cycle)
    sweep_spmvs = opts.spmvs_per_sweep() * (opts.presweeps + opts.postsweeps)
    keys = ("inter_msgs", "inter_bytes", "intra_msgs", "intra_bytes")
    per_level = []
    totals = dict.fromkeys(keys, 0)
    coarse = {"coarse_inter_msgs": 0, "coarse_intra_msgs": 0}
    for l, dl in enumerate(dh.levels):
        row = dict.fromkeys(keys, 0)
        if dl.coarse_inv is None and "spmv_A" in dl.comm_stats:
            n_spmv = sweep_spmvs + 1                  # sweeps + residual
            for k in keys:
                row[k] += n_spmv * dl.comm_stats["spmv_A"][k]
            for op in ("interp", "restrict"):
                if op in dl.comm_stats:
                    for k in keys:
                        row[k] += dl.comm_stats[op][k]
        entry = {"level": l, "visits": visits[l]}
        for k in keys:
            entry[k] = row[k] * visits[l]
            totals[k] += entry[k]
        if l > 0:
            coarse["coarse_inter_msgs"] += entry["inter_msgs"]
            coarse["coarse_intra_msgs"] += entry["intra_msgs"]
        per_level.append(entry)
    return {"cycle": opts.cycle, "smoother": opts.smoother,
            "per_level": per_level, **totals, **coarse}


def dist_vcycle(dh: DistHierarchy, b: np.ndarray, opts=None) -> np.ndarray:
    """One device-resident cycle (``opts.cycle`` shape) from a zero initial
    guess (``b``: [n] or [n, k])."""
    opts = opts or SolveOptions()
    b = np.asarray(b)  # staged by BoundSolver._check_b; keep dtype
    progs, arrs = dh.programs(opts)
    bd = dh.scatter(b)
    prog = progs["vcycle_m" if b.ndim == 2 else "vcycle"]
    return dh.gather(prog(bd, arrs))


def _column_results(X, res, nb, tol):
    """Slice a batched solve's gathered answer ``X`` [n, k] into per-column
    SolveResults.

    Matches the host backend's per-column semantics: each column reports
    the iteration count at which IT first converged (the batch may have
    kept cycling for slower columns) and a residual history truncated
    there, so ``iterations``/``avg_conv_factor`` agree across backends.
    """
    k = X.shape[1]
    cols = []
    for j in range(k):
        hist = [float(r[j]) for r in res]
        nbj = float(nb[j])
        it = next((i for i, r in enumerate(hist) if r / nbj < tol), None)
        if it is None:
            cols.append(SolveResult(X[:, j], hist, len(hist) - 1, False))
        else:
            cols.append(SolveResult(X[:, j], hist[: it + 1], it, True))
    return MultiSolveResult(X, cols)


def dist_solve(dh: DistHierarchy, b: np.ndarray, tol: float = 1e-8,
               maxiter: int = 100, opts=None, x0: np.ndarray | None = None):
    """Stationary AMG iteration x ← x + cycle(b − Ax), fused on device.

    ``b`` may be ``[n]`` or ``[n, k]``; the multi-RHS form batches all k
    systems through one device trace and iterates until every column
    converges.
    """
    opts = opts or SolveOptions()
    b = np.asarray(b)  # staged by BoundSolver._check_b; keep dtype
    multi = b.ndim == 2
    progs, arrs = dh.programs(opts)
    bd = dh.scatter(b)
    x = dh.scatter(np.zeros_like(b) if x0 is None else np.asarray(x0))
    if multi:
        nb = _norms(b)
        res = [np.asarray(progs["resid_norm_m"](x, bd, arrs),
                          dtype=np.float64)]
        for _ in range(maxiter):
            if (res[-1] / nb < tol).all():
                break
            x, rn = progs["cycle_m"](x, bd, arrs)
            res.append(np.asarray(rn, dtype=np.float64))
        return _column_results(dh.gather(x), res, nb, tol)
    nb = float(np.linalg.norm(b)) or 1.0
    res = [float(progs["resid_norm"](x, bd, arrs))]
    for it in range(maxiter):
        if res[-1] / nb < tol:
            return SolveResult(dh.gather(x), res, it, True)
        x, rn = progs["cycle"](x, bd, arrs)
        res.append(float(rn))
    return SolveResult(dh.gather(x), res, maxiter, res[-1] / nb < tol)


def dist_pcg(dh: DistHierarchy, b: np.ndarray, tol: float = 1e-8,
             maxiter: int = 200, opts=None, x0: np.ndarray | None = None):
    """AMG-preconditioned CG, preconditioner + operator fully on device.

    Supports ``x0=`` warm starts and multi-RHS ``b`` of shape ``[n, k]``.
    The call is one ``amg.pcg`` span holding the host's staging
    (``amg.pcg.scatter`` / ``amg.pcg.gather``), each program's dispatch
    (``amg.pcg.init`` / ``amg.pcg.step``) and each wait for the residual
    norm the convergence check reads (``amg.pcg.sync``).
    """
    opts = opts or SolveOptions()
    b = np.asarray(b)  # staged by BoundSolver._check_b; keep dtype
    multi = b.ndim == 2
    with span("amg.pcg", n=b.shape[0], columns=b.shape[1] if multi else 1,
              maxiter=maxiter):
        progs, arrs = dh.programs(opts)
        x0 = np.zeros_like(b) if x0 is None else np.asarray(x0)
        with span("amg.pcg.scatter", bytes=b.nbytes):
            bd = dh.scatter(b)
        with span("amg.pcg.scatter", bytes=x0.nbytes):
            x = dh.scatter(x0)
        suffix = "_m" if multi else ""
        with span("amg.pcg.init"):
            r, z, rz, rnorm = progs["pcg_init" + suffix](x, bd, arrs)
        p = z
        nb = _norms(b) if multi else (float(np.linalg.norm(b)) or 1.0)
        with span("amg.pcg.sync"):
            res = [np.asarray(rnorm, dtype=np.float64) if multi
                   else float(rnorm)]
        it = 0
        while it < maxiter and not np.all(res[-1] / nb < tol):
            with span("amg.pcg.step"):
                x, r, p, rz, rnorm = progs["pcg_step" + suffix](x, r, p, rz,
                                                                arrs)
            with span("amg.pcg.sync"):
                res.append(np.asarray(rnorm, dtype=np.float64) if multi
                           else float(rnorm))
            it += 1
        with span("amg.pcg.gather", bytes=x.nbytes):
            X = dh.gather(x)
    if multi:
        return _column_results(X, res, nb, tol)
    return SolveResult(X, res, it, bool(res[-1] / nb < tol))
