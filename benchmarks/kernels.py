"""Local SpMV/SpMM kernel benchmark against a *measured* ERT-style roofline.

Instead of quoting documented v5e peaks, :func:`repro.launch.roofline.ert_sweep`
measures what this backend actually achieves — streaming bandwidth, random-
gather bandwidth (the ELL kernels' access pattern) and dense FLOP rate —
over several working-set sizes and FLOP intensities.  Each local kernel row
then reports its achieved bytes/s as ``pct_peak`` of the relevant measured
ceiling, plus a ``parity`` field (max relative error vs the host CSR
matvec) the CI gate vets.

Bytes are counted with the *minimal-traffic* model — the sparse operator
read once per apply (cols + vals), one gathered source element per stored
nonzero per RHS, one result write — so the vmapped multi-RHS row, which
really re-reads the operator k times, shows honestly lower ``pct_peak``
than the native SpMM reading it once.

Emits the ``name,us_per_call,derived`` rows used by :mod:`benchmarks.run`,
and — when run standalone — a ``BENCH_kernels.json`` baseline:

    PYTHONPATH=src python -m benchmarks.kernels [--smoke] [--out PATH]
"""
from __future__ import annotations

import json
import os
import time

K_RHS = 8          # multi-RHS batch width the SpMM rows use


def _csr_to_ell(A):
    import numpy as np
    K = int(np.diff(A.indptr).max(initial=1)) or 1
    cols = np.full((A.nrows, K), -1, dtype=np.int32)
    vals = np.zeros((A.nrows, K))
    if A.nnz:
        lens = np.diff(A.indptr)
        r = A.rows_expanded()
        slot = np.arange(A.nnz, dtype=np.int64) - np.repeat(A.indptr[:-1],
                                                            lens)
        cols[r, slot] = A.indices
        vals[r, slot] = A.data
    return cols, vals


def _time_loop(fn, args, reps: int) -> float:
    """Best-of-``reps`` seconds per call (one warm-up call absorbs jit)."""
    import jax
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def rows(smoke: bool | None = None):
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.amg.csr import csr_to_bcsr
    from repro.amg.problems import laplace_3d
    from repro.kernels.spmv.bcsr import bcsr_apply
    from repro.kernels.spmv.ops import select_local_kernel
    from repro.kernels.spmv.ref import ell_spmm_ref, ell_spmv_ref
    from repro.launch.roofline import ert_sweep

    reps = 3 if smoke else 5
    peaks = ert_sweep(smoke=smoke, reps=reps)
    out = []
    t_stream = min(p["seconds"] for p in peaks["points"]
                   if p["kernel"] == "stream")
    t_gather = min(p["seconds"] for p in peaks["points"]
                   if p["kernel"] == "gather")
    common = f"backend={peaks['backend']};smoke={int(peaks['smoke'])}"
    out.append(("ert_stream", t_stream * 1e6,
                f"{common};bw={peaks['stream_bw']:.4g};"
                f"flops_peak={peaks['flops']:.4g};"
                f"documented_bw={peaks['documented_hbm_bw']:.4g}"))
    out.append(("ert_gather", t_gather * 1e6,
                f"{common};bw={peaks['gather_bw']:.4g}"))

    n = 8 if smoke else 14
    A = laplace_3d(n)
    cols_np, vals_np = _csr_to_ell(A)
    nrows, K = cols_np.shape
    rng = np.random.default_rng(0)
    X_np = rng.standard_normal((A.ncols, K_RHS))
    cols = jnp.asarray(cols_np)
    vals = jnp.asarray(vals_np, dtype=jnp.float32)
    x = jnp.asarray(X_np[:, 0], dtype=jnp.float32)
    X = jnp.asarray(X_np, dtype=jnp.float32)
    dsize = x.dtype.itemsize
    # host CSR oracles in fp64 — the parity denominators
    y_ref = A.matvec(np.asarray(x, dtype=np.float64))
    Y_ref = np.stack([A.matvec(np.asarray(X[:, j], dtype=np.float64))
                      for j in range(K_RHS)], axis=1)

    def parity(got, ref):
        got = np.asarray(got, dtype=np.float64)
        denom = np.abs(ref).max() or 1.0
        return np.abs(got - ref).max() / denom

    # minimal-traffic byte models (operator read ONCE per apply)
    a_bytes = nrows * K * (4 + dsize)                    # cols + vals
    spmv_bytes = a_bytes + nrows * K * dsize + nrows * dsize
    spmm_bytes = (a_bytes + nrows * K * K_RHS * dsize
                  + nrows * K_RHS * dsize)

    def kern_row(name, fn, args, byts, ref, extra=""):
        s = _time_loop(fn, args, reps)
        bw = byts / s
        pct = 100.0 * bw / peaks["gather_bw"]
        got = fn(*args)
        return (name, s * 1e6,
                f"impl=jnp_inline;n={nrows};K={K};bytes={byts:.4g};"
                f"achieved_bw={bw:.4g};pct_peak={pct:.2f};"
                f"parity={parity(got, ref):.3e}{extra}")

    out.append(kern_row("kern_ell_spmv", jax.jit(ell_spmv_ref),
                        (cols, vals, x), spmv_bytes, y_ref))
    out.append(kern_row(f"kern_ell_spmm_k{K_RHS}", jax.jit(ell_spmm_ref),
                        (cols, vals, X), spmm_bytes, Y_ref,
                        extra=f";k={K_RHS}"))
    vmapped = jax.jit(jax.vmap(ell_spmv_ref, in_axes=(None, None, 1),
                               out_axes=1))
    out.append(kern_row(f"kern_ell_vmap_k{K_RHS}", vmapped,
                        (cols, vals, X), spmm_bytes, Y_ref,
                        extra=f";k={K_RHS}"))
    sel = select_local_kernel(cols_np)
    bs = sel["block_size"] or 8
    B = csr_to_bcsr(A, bs)
    bcols = jnp.asarray(B.bcols)
    bvals = jnp.asarray(B.bvals, dtype=jnp.float32)
    bcsr_fn = jax.jit(
        lambda bc, bv, xx: bcsr_apply(bc, bv, xx)[: nrows])
    mb, Kb = B.bcols.shape
    bcsr_bytes = (mb * Kb * 4 + mb * Kb * bs * bs * dsize
                  + mb * Kb * bs * K_RHS * dsize + mb * bs * K_RHS * dsize)
    out.append(kern_row(f"kern_bcsr_spmm_k{K_RHS}", bcsr_fn,
                        (bcols, bvals, X), bcsr_bytes, Y_ref,
                        extra=(f";k={K_RHS};bs={bs};"
                               f"heuristic={sel['kernel']};"
                               f"bcsr_fill={sel['bcsr_fill']:.3f}")))
    return out


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--out", default="BENCH_kernels.json")
    args = parser.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    data = rows(smoke=args.smoke)
    print("name,us_per_call,derived")
    for name, us, derived in data:
        print(f"{name},{us:.2f},{derived}")
    with open(args.out, "w") as f:
        json.dump({"benchmark": "kernels",
                   "rows": [{"name": n, "us_per_call": u, "derived": d}
                            for n, u, d in data]}, f, indent=2)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
