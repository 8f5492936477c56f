"""Device-side distributed SpMV: the paper's solve-phase hot loop on a
hierarchical TPU mesh.

Setup (host, once per level and operator — like an MPI communicator build):
  * row-partition the operator over the (pods × lanes) device grid,
  * convert each rank's rows to padded ELL with columns remapped to
    [local | halo] positions,
  * build a :class:`~repro.core.nap_collectives.HaloPlan` for the selected
    strategy (standard / nap2 / nap3).

Operators may be **rectangular**: ``y = M·x`` with the rows of ``M`` (and
``y``) following ``row_part`` while ``x`` follows ``col_part``.  This is what
lets restriction (R: coarse×fine) and interpolation (P: fine×coarse) run as
distributed SpMVs with their own communication graphs and halo plans instead
of host matvecs — each level of the AMG hierarchy gets one
:class:`DistOperator` per {A, P, R}, each with its own model-selected
strategy (see :mod:`repro.amg.dist_solve`).

Execute (device, every smoother sweep / residual / restrict / interpolate):
  shard_map body = halo_exchange → local product
  (:func:`repro.kernels.spmv.spmv.ell_apply`, or
  :func:`repro.kernels.spmv.bcsr.bcsr_apply` on a BCSR-lowered level, or
  :func:`repro.kernels.spmv.dia.dia_apply` for a DIA-lowered on-process
  part) → the off-process product over the rows that read the halo
  (:func:`remote_apply`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core.comm_graph import CommGraph
from ..core.nap_collectives import (HaloPlan, build_halo_plan, halo_exchange,
                                    halo_signature)
from ..core.topology import Partition, Topology
from ..kernels.spmv.bcsr import bcsr_apply
from ..kernels.spmv.dia import LANES, dia_apply
from ..kernels.spmv.spmv import ell_apply
from .csr import CSR
from .dist import rect_vector_graph


def _ell_block(M: CSR, row_part: Partition, col_part: Partition, d: int,
               need_sorted: np.ndarray, rows_local: int, x_local: int, K: int):
    """One device's ELL block with columns remapped to [local | halo]."""
    rlo, rhi = row_part.local_range(d)
    clo, chi = col_part.local_range(d)
    sub = M.submatrix_rows(rlo, rhi)
    cols = np.full((rows_local, K), -1, dtype=np.int32)
    vals = np.zeros((rows_local, K), dtype=np.float64)
    if sub.nnz:
        lens = np.diff(sub.indptr)
        rows = np.repeat(np.arange(sub.nrows, dtype=np.int64), lens)
        k = np.arange(sub.nnz, dtype=np.int64) - np.repeat(sub.indptr[:-1], lens)
        c = sub.indices
        local = (c >= clo) & (c < chi)
        halo_pos = np.searchsorted(need_sorted, c)
        pos = np.where(local, c - clo, x_local + halo_pos).astype(np.int32)
        cols[rows, k] = pos
        vals[rows, k] = sub.data
    return cols, vals


def _split_ell_stacked(cols: np.ndarray, vals: np.ndarray, x_local: int):
    """Split fused [D, rows, K] ELL arrays into the on-process part (columns
    < ``x_local``, kept as local ids) and the off-process part (halo columns,
    rebased to index the halo buffer directly).

    Within each row the relative nonzero order is preserved, so
    ``A_on·x + A_off·halo`` partitions the fused contraction term-for-term —
    the property the split-parity suite asserts exactly.
    """
    D, R, K = cols.shape

    def pack(mask, offset):
        m2 = mask.reshape(D * R, K)
        width = int(m2.sum(axis=1).max(initial=0)) or 1
        oc = np.full((D * R, width), -1, dtype=np.int32)
        ov = np.zeros((D * R, width), dtype=vals.dtype)
        rows, _ = np.nonzero(m2)
        if rows.size:
            counts = m2.sum(axis=1)
            starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
            slot = np.arange(rows.size) - np.repeat(starts, counts)
            oc[rows, slot] = cols.reshape(D * R, K)[m2] - offset
            ov[rows, slot] = vals.reshape(D * R, K)[m2]
        return oc.reshape(D, R, width), ov.reshape(D, R, width)

    on = pack((cols >= 0) & (cols < x_local), 0)
    off = pack(cols >= x_local, x_local)
    return on, off


def _remote_rows(off_cols: np.ndarray) -> np.ndarray | None:
    """The sorted ``[D, R_off]`` list of each device's local rows that hold
    a halo entry, ``R_off`` the largest count over the devices; ``None``
    where some device has a halo entry in every row, so a row list saves
    nothing, or where no row has one, so the apply never reaches the
    off-part.

    A device with fewer such rows is padded with its first rows that hold
    none: their off-part row is empty, so they add zero, and the list stays
    sorted and free of repeats.
    """
    has = (off_cols >= 0).any(axis=2)
    R_off = int(has.sum(axis=1).max(initial=0))
    if R_off in (0, has.shape[1]):
        return None
    first = np.argsort(~has, axis=1, kind="stable")[:, :R_off]
    return np.sort(first, axis=1).astype(np.int32)


def remote_apply(y: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
                 halo: jnp.ndarray, rows: jnp.ndarray | None = None):
    """``y + A_off·halo``.  With a row list ``rows`` (see
    :func:`_remote_rows`), ``cols``/``vals`` hold only those rows and the
    product is added into them with one scatter; without, they hold every
    row.  Either way each row sums the same terms in the same order."""
    if rows is None:
        return y + ell_apply(cols, vals, halo)
    return y.at[rows].add(ell_apply(cols, vals, halo),
                          indices_are_sorted=True, unique_indices=True)


@dataclasses.dataclass
class DistOperator:
    """Host-side container for one distributed (possibly rectangular) operator.

    Device-stacked arrays carry a leading ``n_devices`` dim and are fed to the
    fused shard_map program sharded over the (pod, lane) device axis; the
    :class:`HaloPlan` and partitions are static setup-time metadata.
    """

    strategy: str
    plan: HaloPlan               # halo plan in x-space (col_part layout)
    row_part: Partition          # layout of y (output)
    col_part: Partition          # layout of x (input)
    rows_local: int              # padded local row count per device
    # host only: the fused block the on/off split below is derived from
    # (at build and on every value refresh); never placed on the device
    ell_cols: np.ndarray         # [D, rows_local, K] int32 into [local|halo], -1 pad
    ell_vals: np.ndarray         # [D, rows_local, K]
    send_idx: np.ndarray         # per-device slices of the plan arrays
    recv_sel: np.ndarray
    pool_sel: np.ndarray         # zeros placeholder when plan.pool_sel is None
    # on/off split of the same block: A_on holds the halo-free columns (local
    # ids), A_off the halo columns rebased to halo-buffer ids
    on_cols: np.ndarray | None = None    # [D, rows_local, K_on] int32, -1 pad
    on_vals: np.ndarray | None = None
    off_cols: np.ndarray | None = None   # [D, rows_local, K_off] into halo
    off_vals: np.ndarray | None = None
    # the off-part compacted to the rows that hold a halo entry (see
    # _remote_rows); None where that saves nothing, and the full-row
    # off-part runs instead
    off_rows: np.ndarray | None = None   # [D, R_off] int32, sorted
    off_ccols: np.ndarray | None = None  # [D, R_off, K_off]
    off_cvals: np.ndarray | None = None
    # optional BCSR lowering of the on-part (see lower_bcsr): dense bs×bs
    # blocks contracted per block slot instead of one gather per nonzero
    bcsr_on_bcols: np.ndarray | None = None  # [D, mb, Kb] int32, -1 pad
    bcsr_on_bvals: np.ndarray | None = None  # [D, mb, Kb, bs, bs]
    block_size: int = 0                    # 0 = ELL layout
    # optional DIA lowering of the on-part (see lower_dia): one value row
    # per offset col − row, read as shifted slices of x
    dia_offsets: tuple[int, ...] | None = None
    dia_vals: np.ndarray | None = None     # [D, n_diag, nb, 128]

    @property
    def n_devices(self) -> int:
        return self.plan.n_devices

    @property
    def halo_empty(self) -> bool:
        """True when the plan moves zero entries (halo_len is floored to 1
        for static shapes, so emptiness must be read from total_halo)."""
        return self.plan.total_halo == 0

    @property
    def local_kernel(self) -> str:
        """Layout label for reporting: 'bcsr' or 'dia' once lowered, else
        'ell'."""
        if self.bcsr_on_bcols is not None:
            return "bcsr"
        return "dia" if self.dia_vals is not None else "ell"

    @property
    def expected_signature(self) -> tuple[str, ...]:
        """Ordered collective primitives ONE apply of this operator must
        lower to — the selected strategy's halo signature, empty when the
        halo is (the comm auditor's per-operator contract)."""
        return halo_signature(self.plan)

    @property
    def remote_rows(self) -> int:
        """Rows the off-process product runs over on each device: every
        row where the off-part is not compacted."""
        return self.rows_local if self.off_rows is None \
            else self.off_rows.shape[1]

    def compact_remote(self, rows: np.ndarray | None) -> None:
        """Gather the off-part's rows ``rows`` (``None``: keep every row)."""
        self.off_rows = rows
        if rows is None:
            self.off_ccols = self.off_cvals = None
            return
        self.off_ccols = np.take_along_axis(self.off_cols, rows[..., None], 1)
        self.off_cvals = np.take_along_axis(self.off_vals, rows[..., None], 1)

    def onoff_nnz(self) -> dict[str, int]:
        """Total and per-device-max nnz of the on/off split (for the
        overlap-aware cost model and reporting)."""
        on = (self.on_cols >= 0).sum(axis=(1, 2))
        off = (self.off_cols >= 0).sum(axis=(1, 2))
        return {"on_nnz": int(on.sum()), "off_nnz": int(off.sum()),
                "max_on_nnz": int(on.max(initial=0)),
                "max_off_nnz": int(off.max(initial=0))}

    def device_arrays(self) -> dict[str, np.ndarray]:
        """The sharded inputs the shard_map body needs for one matvec."""
        arrs = {"send": self.send_idx, "recv": self.recv_sel,
                "psel": self.pool_sel,
                "off_cols": self.off_cols, "off_vals": self.off_vals}
        if self.off_rows is not None:
            arrs.update(off_rows=self.off_rows, off_cols=self.off_ccols,
                        off_vals=self.off_cvals)
        if self.dia_vals is not None:
            arrs["dia"] = self.dia_vals
        elif self.bcsr_on_bcols is not None:
            arrs.update(on_bcols=self.bcsr_on_bcols,
                        on_bvals=self.bcsr_on_bvals)
        else:
            arrs.update(on_cols=self.on_cols, on_vals=self.on_vals)
        return arrs

    def lower_bcsr(self, block_size: int) -> None:
        """Lower this operator's on-part to block-ELL BCSR.

        Each device's (rows_local × local) halo-free block is re-tiled into
        dense ``bs×bs`` blocks; block-row padding never mixes devices
        because each device is lowered independently.  Once lowered,
        :meth:`apply` runs ``A_on·x`` as the block contraction
        (:func:`~repro.kernels.spmv.bcsr.bcsr_apply`) instead of the ELL
        gather.  The off-part stays ELL: its rows are halo-width gathers
        that would shred into mostly-empty bs×bs blocks.
        """
        from .csr import CSR, csr_to_bcsr
        D = self.n_devices
        width = self.plan.local_n
        per = []
        for d in range(D):
            cols = self.on_cols[d]
            keep = cols >= 0
            r = np.broadcast_to(
                np.arange(self.rows_local, dtype=np.int64)[:, None],
                cols.shape)[keep]
            per.append(csr_to_bcsr(
                CSR.from_coo(r, cols[keep], self.on_vals[d][keep],
                             (self.rows_local, width)), block_size))
        mb = per[0].bcols.shape[0] if per else 0
        Kb = max((b.bcols.shape[1] for b in per), default=0)
        bcols = np.full((D, mb, Kb), -1, dtype=np.int32)
        bvals = np.zeros((D, mb, Kb, block_size, block_size),
                         dtype=self.on_vals.dtype)
        for d, b in enumerate(per):
            kb = b.bcols.shape[1]
            bcols[d, :, :kb] = b.bcols
            bvals[d, :, :kb] = b.bvals
        self.bcsr_on_bcols, self.bcsr_on_bvals = bcols, bvals
        self.block_size = int(block_size)

    def lower_dia(self, offsets: tuple[int, ...]) -> None:
        """Lower the on-part to DIA on ``offsets`` (ascending, covering
        every ``on_cols − row`` of every device; see
        :func:`~repro.kernels.spmv.ops.select_dia`).

        Once lowered, the halo-free product ``A_on·x`` runs as
        :func:`~repro.kernels.spmv.dia.dia_apply`; the off-part stays ELL.
        """
        D, R, _ = self.on_cols.shape
        nb = -(-R // LANES)
        slot = np.full(offsets[-1] - offsets[0] + 1, -1, dtype=np.int64)
        slot[np.asarray(offsets) - offsets[0]] = np.arange(len(offsets))
        rows = np.arange(R, dtype=self.on_cols.dtype)[:, None]
        vals = np.zeros((D, len(offsets), nb * LANES),
                        dtype=self.on_vals.dtype)
        for d in range(D):
            keep = self.on_cols[d] >= 0
            diag = slot[(self.on_cols[d] - rows)[keep] - offsets[0]]
            r = np.broadcast_to(rows, keep.shape)[keep]
            # bincount sums a column stored twice in one row, as ELL does
            vals[d] = np.bincount(
                diag * (nb * LANES) + r, weights=self.on_vals[d][keep],
                minlength=len(offsets) * nb * LANES).reshape(
                    len(offsets), nb * LANES)
        self.dia_offsets = tuple(int(o) for o in offsets)
        self.dia_vals = vals.reshape(D, len(offsets), nb, LANES)

    def refresh_values(self, block_of) -> None:
        """Value-only re-lowering onto the frozen layouts.

        ``block_of(d)`` returns the CSR device ``d`` reads its rows from —
        same contract as the build — whose sparsity pattern must match the
        one this operator was lowered from.  The ELL fill order is a pure
        function of ``indptr``/``indices`` (see :func:`_ell_block`), so with
        a frozen pattern the column maps, halo plan and on/off split
        layouts are all reproduced exactly; only the value planes change.
        BCSR lowerings are re-tiled at the same ``block_size``, DIA
        lowerings re-filled on the same offsets, the compacted off-part
        re-gathered on the same row list.
        """
        vals = np.zeros(self.ell_cols.shape, dtype=np.float64)
        for d in range(self.n_devices):
            rlo, rhi = self.row_part.local_range(d)
            sub = block_of(d).submatrix_rows(rlo, rhi)
            if sub.nnz:
                lens = np.diff(sub.indptr)
                rows = np.repeat(np.arange(sub.nrows, dtype=np.int64), lens)
                k = np.arange(sub.nnz, dtype=np.int64) \
                    - np.repeat(sub.indptr[:-1], lens)
                vals[d][rows, k] = sub.data
        self.ell_vals = vals.astype(self.ell_vals.dtype)
        (on_cols, on_vals), (off_cols, off_vals) = _split_ell_stacked(
            self.ell_cols, self.ell_vals, self.plan.local_n)
        # the split is deterministic given cols: layouts come back identical
        self.on_cols, self.on_vals = on_cols, on_vals
        self.off_cols, self.off_vals = off_cols, off_vals
        self.compact_remote(self.off_rows)
        if self.block_size:
            self.lower_bcsr(self.block_size)
        if self.dia_offsets is not None:
            self.lower_dia(self.dia_offsets)

    def _on_product(self, arrs, x_loc):
        """``A_on · x`` — the halo-free product that overlaps the exchange."""
        if "on_bcols" in arrs:
            y = bcsr_apply(arrs["on_bcols"], arrs["on_bvals"], x_loc)
            return y[: self.rows_local]
        if "dia" in arrs:
            y = dia_apply(self.dia_offsets, arrs["dia"], x_loc)
            return y[: self.rows_local]
        return ell_apply(arrs["on_cols"], arrs["on_vals"], x_loc)

    def apply(self, arrs: dict[str, jnp.ndarray],
              x_loc: jnp.ndarray) -> jnp.ndarray:
        """Inside shard_map: halo exchange + local SpMV/SpMM for this device.

        ``arrs`` holds this device's slices of :meth:`device_arrays` (leading
        device dim already squeezed).  ``x_loc`` may be ``[local]`` (one RHS)
        or ``[local, k]`` (multi-RHS): the halo is exchanged once with the
        RHS axis riding along.

        The exchange is traced *before* the independent ``y_on = A_on·x``
        product (DIA, BCSR or ELL, as lowered), so XLA's async collectives
        can hide the NAP message latency behind the on-process SpMV; the
        ``A_off·halo`` correction lands after.  Levels whose plan moves
        zero entries emit no collective at all.

        The ops carry the scope ``halo`` (the exchange with its packing),
        ``local`` (``A_on·x``) or ``remote`` (``A_off·halo``, over the rows
        that read the halo where the off-part was compacted).
        """
        if self.halo_empty:
            with jax.named_scope("local"):
                return self._on_product(arrs, x_loc)
        psel = None if self.plan.pool_sel is None else arrs["psel"]
        # `halo` is not consumed until the off-process correction, so the
        # collective and the on-process product are dataflow-independent
        # and free to overlap
        with jax.named_scope("halo"):
            halo = halo_exchange(x_loc, self.plan, arrs["send"],
                                 arrs["recv"], psel)
        with jax.named_scope("local"):
            y = self._on_product(arrs, x_loc)
        with jax.named_scope("remote"):
            return remote_apply(y, arrs["off_cols"], arrs["off_vals"],
                                halo, arrs.get("off_rows"))

    # ------------------------------------------------------- host-side layout
    def scatter_x(self, x: np.ndarray, dtype=None) -> np.ndarray:
        """Global x (col_part layout) -> [D, x_local(, k)] device layout.

        ``x`` may be ``[n]`` or ``[n, k]`` (multi-RHS block); the trailing
        RHS axis is carried through unsharded.
        """
        x = np.asarray(x)
        if x.ndim not in (1, 2) or x.shape[0] != self.col_part.n:
            raise ValueError(f"expected x of shape ({self.col_part.n},) or "
                             f"({self.col_part.n}, k), got {x.shape}")
        D = self.n_devices
        dtype = dtype or self.ell_vals.dtype
        out = np.zeros((D, self.plan.local_n) + x.shape[1:], dtype=dtype)
        for d in range(D):
            lo, hi = self.col_part.local_range(d)
            out[d, : hi - lo] = x[lo:hi]
        return out

    def gather_y(self, y_dev: np.ndarray) -> np.ndarray:
        """[D, rows_local(, k)] device layout -> global y (row_part layout)."""
        y_dev = np.asarray(y_dev)
        out = np.zeros((self.row_part.n,) + y_dev.shape[2:], dtype=y_dev.dtype)
        for d in range(self.n_devices):
            lo, hi = self.row_part.local_range(d)
            out[lo:hi] = y_dev[d, : hi - lo]
        return out


def local_square_block(M, part: Partition, d: int) -> CSR:
    """Device d's diagonal square block of ``M`` (rows AND columns in
    ``part.local_range(d)``, columns shifted to local 0-based ids).

    This is the sub-operator the block smoothers factor locally — the
    block-Jacobi diagonal-block inverses and the hybrid-GS (D+L)⁻¹ factor
    are lowered from it alongside the ELL blocks, while couplings outside
    it stay in the halo'd residual.  ``M`` may be a global CSR or a
    born-partitioned BlockMatrix (both expose ``submatrix_rows``).
    """
    lo, hi = part.local_range(d)
    sub = M.submatrix_rows(lo, hi)
    r, c = sub.rows_expanded(), sub.indices
    keep = (c >= lo) & (c < hi)
    return CSR.from_coo(r[keep], c[keep] - lo, sub.data[keep],
                        (hi - lo, hi - lo))


def _assemble_operator(block_of, K: int, n_pods: int, lanes: int,
                       strategy: str, row_part: Partition,
                       col_part: Partition, graph: CommGraph,
                       dtype) -> DistOperator:
    """Shared tail: halo plan + per-device ELL lowering.

    ``block_of(d)`` returns the CSR each device reads its rows from — the
    whole matrix on the from-global path, device d's own row block on the
    from-blocks path.  ``K`` is the global max row length.
    """
    D = n_pods * lanes
    plan = build_halo_plan(graph, n_pods, lanes, strategy)
    need_sorted = [np.sort(graph.need[d]) for d in range(D)]
    rows_local = row_part.max_local_size
    x_local = plan.local_n
    cols = np.zeros((D, rows_local, K), dtype=np.int32)
    vals = np.zeros((D, rows_local, K), dtype=np.float64)
    for d in range(D):
        cols[d], vals[d] = _ell_block(block_of(d), row_part, col_part, d,
                                      need_sorted[d], rows_local, x_local, K)
    psel = plan.pool_sel if plan.pool_sel is not None else np.zeros(
        (D, 1), dtype=np.int32)
    vals = vals.astype(dtype)
    (on_cols, on_vals), (off_cols, off_vals) = _split_ell_stacked(
        cols, vals, x_local)
    op = DistOperator(strategy=strategy, plan=plan, row_part=row_part,
                      col_part=col_part, rows_local=rows_local,
                      ell_cols=cols, ell_vals=vals,
                      send_idx=plan.send_idx, recv_sel=plan.recv_sel,
                      pool_sel=psel, on_cols=on_cols, on_vals=on_vals,
                      off_cols=off_cols, off_vals=off_vals)
    op.compact_remote(_remote_rows(off_cols))
    return op


def build_dist_operator(M: CSR, n_pods: int, lanes: int, strategy: str,
                        row_part: Partition | None = None,
                        col_part: Partition | None = None,
                        graph: CommGraph | None = None,
                        dtype=jnp.float32) -> DistOperator:
    """Build the device form of ``M`` (square or rectangular) for one strategy.

    ``graph`` may be passed in when the caller already built/selected on it
    (the per-level selection path) — it must be ``rect_vector_graph(M, ...)``.
    """
    topo = Topology(n_nodes=n_pods, ppn=lanes)
    row_part = row_part or Partition.balanced(M.nrows, topo)
    col_part = col_part or Partition.balanced(M.ncols, topo)
    if graph is None:
        graph = rect_vector_graph(M, row_part, col_part)
    K = int(np.diff(M.indptr).max(initial=1)) or 1
    return _assemble_operator(lambda d: M, K, n_pods, lanes, strategy,
                              row_part, col_part, graph, dtype)


def build_dist_operator_from_blocks(blocks: list[CSR], n_pods: int,
                                    lanes: int, strategy: str, *,
                                    row_part: Partition,
                                    col_part: Partition,
                                    graph: CommGraph | None = None,
                                    dtype=jnp.float32) -> DistOperator:
    """Device form of an operator that exists only as per-device row blocks.

    ``blocks[d]`` is a *global-shape* CSR holding exactly device d's rows
    (rows outside ``row_part.local_range(d)`` empty, global column ids) —
    the :mod:`repro.amg.dist_setup` representation, where each level is born
    partitioned and no global CSR is ever assembled.
    """
    D = n_pods * lanes
    assert len(blocks) == D, (len(blocks), D)
    if graph is None:
        offp = []
        for p in range(D):
            rlo, rhi = row_part.local_range(p)
            clo, chi = col_part.local_range(p)
            offp.append(blocks[p].offproc_columns(clo, chi, rlo, rhi))
        graph = CommGraph.from_offproc_columns(col_part, offp)
    K = max(int(np.diff(b.indptr).max(initial=0)) for b in blocks) or 1
    return _assemble_operator(lambda d: blocks[d], K, n_pods, lanes, strategy,
                              row_part, col_part, graph, dtype)


# --------------------------------------------------------------------------
# Stand-alone square SpMV (kept for benchmarks/tests of a single operator)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class DistSpMV:
    """Host-side container: device arrays + jitted distributed matvec."""

    plan: HaloPlan
    part: Partition
    mesh: jax.sharding.Mesh
    op: DistOperator
    fn: callable = None      # jitted shard_map spmv

    @property
    def ell_cols(self) -> np.ndarray:
        return self.op.ell_cols

    @property
    def ell_vals(self) -> np.ndarray:
        return self.op.ell_vals

    def scatter_x(self, x: np.ndarray) -> np.ndarray:
        return self.op.scatter_x(x)

    def gather_y(self, y_dev: np.ndarray) -> np.ndarray:
        return self.op.gather_y(y_dev)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        return self.gather_y(self.fn(self.scatter_x(x)))


def build_dist_spmv(A: CSR, n_pods: int, lanes: int, strategy: str,
                    mesh: jax.sharding.Mesh | None = None,
                    dtype=jnp.float32) -> DistSpMV:
    op = build_dist_operator(A, n_pods, lanes, strategy, dtype=dtype)
    if mesh is None:
        mesh = jax.make_mesh((n_pods, lanes), ("pod", "lane"))

    P = jax.sharding.PartitionSpec
    dev_spec = P(("pod", "lane"))
    arrs = op.device_arrays()

    def body(x_loc, a):
        # squeeze the per-device leading dim added by shard_map
        x_loc = x_loc[0]
        a = jax.tree.map(lambda v: v[0], a)
        return op.apply(a, x_loc)[None]

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(dev_spec, dev_spec),
                      out_specs=dev_spec, check_vma=False))

    def matvec_dev(x_dev):
        return fn(jnp.asarray(x_dev, dtype=dtype), arrs)

    return DistSpMV(plan=op.plan, part=op.row_part, mesh=mesh, op=op,
                    fn=matvec_dev)
