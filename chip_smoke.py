"""Drive the AMG session and service path once on a TPU, at HPCG size.

    python chip_smoke.py            # one chip
    python chip_smoke.py --four     # the 2x2 mesh phase only (four chips)

With no arguments, on one chip, in one process and in order:

* device  — fail unless JAX's first device is a TPU;
* problem — HPCG's 27-point Poisson on its reference 104³ local grid
  (1,124,864 rows), right-hand sides drawn from ``--seed``;
* session — ``AMGSolver(AMGConfig(backend="dist", ...)).setup(A)`` and its
  lowering, with the rows, ELL width, halo strategy and local product of
  every level;
* solves  — one PCG with k=1 and one multi-RHS PCG with k=8, each warmed
  up, timed, and checked by the float64 true residual on the host;
* reference — float64 ``host_pcg`` on the session's own host hierarchy,
  for the k=1 right-hand side and the k=8 columns that took the fewest
  and the most device iterations;
* served  — an AMGWire server and client in this process: register the
  largest ``laplace_3d`` whose register frame fits the frame cap, three
  solves and one ``update(delta=...)``, every answer checked.

``--four`` runs only the 2x2 mesh phase: the same 104³ problem under
``strategy="auto"``, forced ``standard``/``nap2``/``nap3`` and
``setup_backend="dist"``, each held to the host float64 reference, with
the level arrays checked to span all four devices.

Every phase prints its own lines.  The last line of a passing run is one
JSON object naming the device; any failure exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N = 104            # HPCG's reference local grid per chip
K_RHS = 8          # multi-RHS width of the coalesced serving path
TOL = 1e-6         # solver tolerance of the float32 device sessions
# The device PCG stops on its float32 recurrence residual, which is
# updated (r -= alpha·A·p), never recomputed; in float32 it drifts from
# the true residual b - A·x by a few float32 ulps of ‖b‖ per iteration.
# The float64 true residual of a converged answer is held to 10× the
# solver tolerance.
RES_TOL = 10 * TOL
# float32 rounding moves the recurrence residual across the stopping
# threshold an iteration or two earlier or later than float64 does.
ITER_MARGIN = 2
WIRE_TIMEOUT = 900.0   # the first served solve sets up and compiles


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_residuals(A, X, B) -> np.ndarray:
    """Per-column ‖b − A·x‖/‖b‖ in float64 on the host."""
    X = np.asarray(X, dtype=np.float64).reshape(A.nrows, -1)
    B = np.asarray(B, dtype=np.float64).reshape(A.nrows, -1)
    R = B - np.stack([A.matvec(X[:, j]) for j in range(X.shape[1])], axis=1)
    return np.linalg.norm(R, axis=0) / np.linalg.norm(B, axis=0)


def check(ok: bool, phase: str, msg: str) -> None:
    if not ok:
        say(phase, "FAIL: " + msg)
        raise SystemExit(1)


def device_phase(want: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    say("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)}")
    check(d.platform == "tpu", "device", "no TPU attached")
    check(len(devs) >= want, "device", f"{want} chips needed")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def product_of(dl) -> str:
    """Which local product a level's A runs on the device."""
    if dl.coarse_inv is not None:
        return "dense-coarse-solve"
    if dl.A.block_size:
        return f"xla-bcsr-gather(bs={dl.A.block_size})"
    return "xla-ell-gather"


def session_phase(A, cfg):
    from repro.amg.api import AMGSolver

    t0 = time.perf_counter()
    bound = AMGSolver(cfg).setup(A)
    t1 = time.perf_counter()
    dh = bound.dist_hierarchy
    t2 = time.perf_counter()
    say("session", f"setup {t1 - t0:.3f}s, lowering {t2 - t1:.3f}s, "
                   f"{len(dh.levels)} levels on a {cfg.n_pods}x{cfg.lanes} "
                   f"mesh")
    for l, dl in enumerate(dh.levels):
        say("session", f"L{l} rows={dl.A.row_part.n} "
                       f"ell_K={dl.A.ell_cols.shape[-1]} "
                       f"strategy={dl.strategies.get('spmv_A', '-')} "
                       f"product={product_of(dl)}")
    return bound


def timed_pcg(bound, rhs):
    """Warm-up solve (compiles this shape), then the timed solve.  The
    answer comes back as a host array, so the clock stops only after the
    device has finished."""
    bound.pcg(rhs)
    t0 = time.perf_counter()
    res = bound.pcg(rhs)
    return res, time.perf_counter() - t0


def solve_phase(phase: str, label: str, A, bound, rhs) -> list[int]:
    """One warmed-up, timed device PCG; every column converged with its
    float64 true residual within RES_TOL.  Returns per-column iterations."""
    res, dt = timed_pcg(bound, rhs)
    cols = getattr(res, "columns", [res])
    iters = [c.iterations for c in cols]
    rel = rel_residuals(A, res.x, rhs)
    say(phase, f"pcg {label}: {dt:.3f}s, iterations {iters}, "
               f"true rel residual max {rel.max():.3e}")
    check(all(c.converged for c in cols), phase,
          f"pcg {label} did not converge")
    check(bool((rel <= RES_TOL).all()), phase,
          f"pcg {label} true residual {rel.max():.3e} > {RES_TOL:.0e}")
    return iters


def reference_phase(hierarchy, opts, rhs_cols) -> list[int]:
    """float64 host PCG on the session's own hierarchy, one column at a
    time to the same tolerance.  Returns its iterations per column."""
    from repro.amg.solve import host_pcg

    t0 = time.perf_counter()
    iters = []
    for rhs in rhs_cols:
        res = host_pcg(hierarchy, np.asarray(rhs, np.float64), tol=TOL,
                       opts=opts)
        check(res.converged, "reference", "host PCG did not converge")
        iters.append(res.iterations)
    say("reference", f"host float64 pcg iterations {iters} "
                     f"({time.perf_counter() - t0:.1f}s)")
    return iters


def check_margin(phase: str, dev_iters, host_iters) -> None:
    off = max(abs(i - r) for i, r in zip(dev_iters, host_iters))
    say(phase, f"device iterations {dev_iters} vs host {host_iters}")
    check(off <= ITER_MARGIN, phase,
          f"iterations differ by {off} > {ITER_MARGIN}")


def wire_size() -> int:
    """Largest laplace_3d grid whose register frame fits the frame cap."""
    from repro.amg.api import csr_to_wire
    from repro.amg.problems import laplace_3d
    from repro.serve.wire import FrameTooLarge, encode_frame

    # at 16 bytes per nonzero (int64 column, float64 value), base64'd, the
    # 64 MiB cap falls just under a 50³ grid
    for n in range(52, 8, -1):
        frame = {"schema": 2, "kind": "register", "seq": 0,
                 "tenant": "smoke", "payload": csr_to_wire(laplace_3d(n))}
        try:
            encode_frame(frame)
        except FrameTooLarge:
            continue
        return n
    raise SystemExit("no laplace_3d fits the frame cap")


def served_phase(cfg, seed: int) -> None:
    from repro.amg.api import (csr_to_wire, solve_request_to_wire,
                               update_request_to_wire)
    from repro.amg.problems import laplace_3d
    from repro.serve import AMGWireClient, ServerThread, TenantSpec

    n = wire_size()
    A = laplace_3d(n)
    rng = np.random.default_rng(seed + 1)
    say("served", f"laplace_3d({n}): {A.nrows} rows, {A.nnz} nnz")
    spec = TenantSpec(config=cfg, max_rhs=K_RHS)
    with ServerThread({"smoke": spec}) as srv, \
            AMGWireClient.connect(srv.host, srv.port) as c:
        mid = c.register("smoke", csr_to_wire(A),
                         timeout=WIRE_TIMEOUT)["matrix"]

        def solve(i):
            b = rng.standard_normal(A.nrows)
            t0 = time.perf_counter()
            x, diag = c.solve("smoke", solve_request_to_wire(
                mid, b, method="pcg"), timeout=WIRE_TIMEOUT)
            rel = float(rel_residuals(A, x, b)[0])
            say("served", f"solve {i}: {time.perf_counter() - t0:.3f}s, "
                          f"iterations {diag.get('iterations')}, true rel "
                          f"residual {rel:.3e}")
            check(bool(diag.get("converged")) and rel <= RES_TOL, "served",
                  f"solve {i}: {diag}, residual {rel:.3e}")

        solve(0)
        solve(1)
        # a small symmetric ΔA on the frozen pattern keeps A SPD for PCG
        delta = 1e-3 * np.abs(A.data) * rng.standard_normal(A.nnz)
        delta = 0.5 * (delta + type(A)(A.shape, A.indptr, A.indices,
                                       delta).T.data)
        frame = c.update("smoke", update_request_to_wire(mid, delta=delta),
                         timeout=WIRE_TIMEOUT)
        say("served", f"update: {frame['action']} ({frame['reason']})")
        A = type(A)(A.shape, A.indptr, A.indices, A.data + delta)
        solve(2)


def four_phase(A, b) -> None:
    import jax

    from repro.amg.api import AMGConfig

    base = AMGConfig(backend="dist", n_pods=2, lanes=2, dtype="float32",
                     tol=TOL)
    cases = [("auto", base)]
    cases += [(s, base.replace(strategy=s))
              for s in ("standard", "nap2", "nap3")]
    cases += [("dist-setup", base.replace(setup_backend="dist"))]
    ref = None
    for label, cfg in cases:
        bound = session_phase(A, cfg)
        if ref is None:
            # one float64 reference for every case: the first case's host
            # hierarchy (the partitioned setup coarsens the same operator
            # to within a few rows per level)
            ref = reference_phase(bound.hierarchy, bound.opts, [b])
        dh = bound.dist_hierarchy
        spans = {len(leaf.sharding.device_set)
                 for leaf in jax.tree.leaves(dh._arrs)}
        check(spans == {4}, "four", f"{label}: level arrays span {spans}")
        check_margin("four", solve_phase("four", label, A, bound, b), ref)
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say("four", f"{d}: bytes_in_use={stats.get('bytes_in_use')}")
        check(stats.get("bytes_in_use", 0) > 0, "four",
              f"{d} reports no memory in use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four", action="store_true",
                    help="run only the 2x2 mesh phase on four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    say("cache", f"compilation cache in {enable_compile_cache()}")
    device = device_phase(4 if args.four else 1)

    from repro.amg.api import AMGConfig
    from repro.amg.problems import laplace_3d

    A = laplace_3d(N)
    rng = np.random.default_rng(args.seed)
    b = rng.standard_normal(A.nrows)
    say("problem", f"laplace_3d({N}): {A.nrows} rows, {A.nnz} nnz, "
                   f"seed {args.seed}")
    if args.four:
        four_phase(A, b)
    else:
        B = rng.standard_normal((A.nrows, K_RHS))
        cfg = AMGConfig(backend="dist", n_pods=1, lanes=1, dtype="float32",
                        tol=TOL)
        bound = session_phase(A, cfg)
        it1 = solve_phase("solves", "k=1", A, bound, b)
        it8 = solve_phase("solves", f"k={K_RHS}", A, bound, B)
        # host PCG costs seconds per iteration at this size: of the k=8
        # block, the columns with the fewest and the most device iterations
        cols = sorted({int(np.argmin(it8)), int(np.argmax(it8))})
        ref = reference_phase(bound.hierarchy, bound.opts,
                              [b] + [B[:, j] for j in cols])
        check_margin("reference", it1 + [it8[j] for j in cols], ref)
        served_phase(cfg, args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
