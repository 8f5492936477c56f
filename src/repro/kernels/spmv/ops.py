"""Per-level local-product layout selection.

:func:`select_local_kernel` / :func:`select_dist_kernel` are the layout
heuristic the distributed solve phase uses to pick, per level, between
the ELL gather product (:func:`repro.kernels.spmv.spmv.ell_apply`) and the
block-ELL product (:func:`repro.kernels.spmv.bcsr.bcsr_apply`).  Where
they keep ELL, :func:`select_dia` moves a stencil-structured on-process
part to the diagonal product (:func:`repro.kernels.spmv.dia.dia_apply`):
in the AMG hierarchies of grid problems, the fine level's ``A``; the
Galerkin levels, ``P`` and ``R`` stay on ELL.
"""
from __future__ import annotations

import numpy as np

from .bcsr import BLOCK_SIZES


# --------------------------------------------------------------------------
# Per-level layout selection: ELL (VPU gather) vs BCSR (MXU block contract)
# --------------------------------------------------------------------------

# How many stored-value touches a BCSR lane is worth relative to an ELL
# gather lane: dense bs×bs contractions run on the MXU at matmul rate while
# ELL pays a scalar gather per nonzero, so BCSR can afford this factor of
# explicit-zero fill before it loses.  4 is deliberately conservative for
# the v5e (the MXU:VPU FLOP ratio is far higher, but BCSR still streams the
# zero-filled blocks from HBM — bandwidth, not FLOPs, bounds sparse work).
MXU_ADVANTAGE = 4.0


def _bcsr_stats(cols: np.ndarray, bs: int) -> tuple[int, int]:
    """(n_blocks, Kb) of blocking an ELL block's coordinates at bs."""
    n, _ = cols.shape
    r = np.repeat(np.arange(n, dtype=np.int64), cols.shape[1])
    c = np.asarray(cols, dtype=np.int64).reshape(-1)
    keep = c >= 0
    r, c = r[keep], c[keep]
    if r.size == 0:
        return 0, 0
    keys = np.unique((r // bs) << 32 | (c // bs))
    brows = keys >> 32
    kb = int(np.bincount(brows).max(initial=0))
    return int(keys.size), kb


def select_local_kernel(cols: np.ndarray,
                        block_sizes: tuple[int, ...] = BLOCK_SIZES,
                        mxu_advantage: float = MXU_ADVANTAGE) -> dict:
    """Choose the local-SpMV layout for one ELL block: ``cols`` [n, K].

    Compares the MXU-adjusted stored-value volume of each candidate BCSR
    blocking (``n_blocks·bs² / mxu_advantage`` — explicit-zero fill made
    cheaper by the dense-math rate) against the ELL volume ``n·K``
    (padding waste included).  Returns a dict::

        {"kernel": "ell" | "bcsr", "block_size": 0 | bs,
         "ell_cost": float, "bcsr_cost": float,
         "ell_fill": nnz / (n·K), "bcsr_fill": nnz / (n_blocks·bs²)}

    so callers can log the decision, not just apply it.
    """
    cols = np.asarray(cols)
    n, K = cols.shape
    nnz = int((cols >= 0).sum())
    ell_cost = float(n * max(K, 1))
    best = {"kernel": "ell", "block_size": 0, "ell_cost": ell_cost,
            "bcsr_cost": float("inf"),
            "ell_fill": nnz / ell_cost if ell_cost else 0.0, "bcsr_fill": 0.0}
    if nnz == 0:
        return best
    for bs in block_sizes:
        n_blocks, _ = _bcsr_stats(cols, bs)
        stored = n_blocks * bs * bs
        cost = stored / mxu_advantage
        if cost < best["bcsr_cost"]:
            best["bcsr_cost"] = cost
            best["bcsr_fill"] = nnz / stored if stored else 0.0
            best_bs = bs
    if best["bcsr_cost"] < best["ell_cost"]:
        best["kernel"] = "bcsr"
        best["block_size"] = best_bs
    return best


def select_dist_kernel(cols_stack: np.ndarray,
                       block_sizes: tuple[int, ...] = BLOCK_SIZES,
                       mxu_advantage: float = MXU_ADVANTAGE) -> dict:
    """One layout decision for a device-stacked operator: ``cols_stack``
    [D, n, K].  Costs are summed across devices (each device's block is
    lowered independently, so block rows never straddle devices) and a
    single (kernel, block_size) is returned in the same dict shape as
    :func:`select_local_kernel`.
    """
    cols_stack = np.asarray(cols_stack)
    D, n, K = cols_stack.shape
    nnz = int((cols_stack >= 0).sum())
    ell_cost = float(D * n * max(K, 1))
    best = {"kernel": "ell", "block_size": 0, "ell_cost": ell_cost,
            "bcsr_cost": float("inf"),
            "ell_fill": nnz / ell_cost if ell_cost else 0.0, "bcsr_fill": 0.0}
    if nnz == 0:
        return best
    best_bs = 0
    for bs in block_sizes:
        stored = sum(_bcsr_stats(cols_stack[d], bs)[0]
                     for d in range(D)) * bs * bs
        cost = stored / mxu_advantage
        if cost < best["bcsr_cost"]:
            best["bcsr_cost"] = cost
            best["bcsr_fill"] = nnz / stored if stored else 0.0
            best_bs = bs
    if best["bcsr_cost"] < best["ell_cost"]:
        best["kernel"] = "bcsr"
        best["block_size"] = best_bs
    return best


def select_dia(cols_stack: np.ndarray) -> tuple[int, ...] | None:
    """The offsets to lower a device-stacked ELL block ``cols_stack``
    [D, n, K] (column ids local to each device) to DIA with, or None.

    The offsets are the distinct ``col − row`` of the valid entries over
    every device, ascending, so one offset set serves every device.  DIA
    is taken when they number no more than the ELL width K: it then stores
    no more values than ELL does and reads no column ids.
    """
    cols_stack = np.asarray(cols_stack)
    keep = cols_stack >= 0
    if not keep.any():
        return None
    n = cols_stack.shape[1]
    diff = (cols_stack - np.arange(n, dtype=cols_stack.dtype)[:, None])[keep]
    lo = int(diff.min())
    offsets = np.flatnonzero(np.bincount(diff - lo)) + lo
    if offsets.size > cols_stack.shape[2]:
        return None
    return tuple(int(o) for o in offsets)


__all__ = ["select_local_kernel", "select_dist_kernel", "select_dia",
           "MXU_ADVANTAGE"]
