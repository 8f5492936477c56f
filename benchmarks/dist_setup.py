"""Setup-phase benchmark: the partitioned node-aware Galerkin products
(paper Figs. 14/15's setup-phase claim, executed rather than simulated).

For ≥3 problem sizes: host ``hierarchy.setup`` vs partitioned
``dist_setup_partitioned`` wall time, plus one row per (level, SpGEMM op)
with the model-selected strategy, its modeled microseconds per strategy,
and the *measured* exchange (inter/intra messages, bytes, seconds) — the
modeled-vs-measured comparison the selection relies on.

Emits the ``name,us_per_call,derived`` rows used by :mod:`benchmarks.run`,
and — when run standalone — a ``BENCH_dist_setup.json`` record file:

    PYTHONPATH=src python -m benchmarks.dist_setup [--smoke] [--out PATH]

``--smoke`` (or ``REPRO_BENCH_SMOKE=1``) shrinks the sizes so the whole
benchmark runs in seconds.  The partitioned setup loop is numpy-only (it
models the mesh with a Topology), so no multi-device XLA platform is
needed — this runs anywhere the tier-1 tests run.
"""
from __future__ import annotations

import json
import os
import time

MESH = (2, 4)


def rows(smoke: bool | None = None):
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import numpy as np  # noqa: F401

    from repro.amg import setup
    from repro.amg.dist_setup import dist_setup_partitioned
    from repro.amg.problems import laplace_3d
    from repro.core import BLUE_WATERS

    sizes = (6, 8, 10) if smoke else (12, 16, 20)
    n_pods, lanes = MESH
    out = []
    for n in sizes:
        A = laplace_3d(n)
        t0 = time.perf_counter()
        h = setup(A, solver="rs")
        host_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        plv, recs = dist_setup_partitioned(A, n_pods, lanes,
                                           params=BLUE_WATERS)
        dist_dt = time.perf_counter() - t0
        assert len(plv) == h.n_levels, "partitioned setup level mismatch"
        out.append((f"host_setup_n{A.nrows}", host_dt * 1e6,
                    f"levels={h.n_levels};nnz={A.nnz}"))
        out.append((f"dist_setup_n{A.nrows}", dist_dt * 1e6,
                    f"mesh={n_pods}x{lanes};levels={len(plv)};"
                    f"dist_vs_host={dist_dt / max(host_dt, 1e-12):.2f}x"))
        # per-level modeled-vs-measured strategy rows (the paper's setup
        # phase = the two Galerkin SpGEMM row exchanges per level)
        for r in recs:
            modeled = ";".join(f"{s}={t * 1e6:.1f}" for s, t in
                               sorted(r.modeled.items()))
            out.append((
                f"dist_setup_n{A.nrows}_L{r.level}_{r.op}",
                r.seconds * 1e6,
                f"strategy={r.strategy};modeled_us={modeled};"
                f"inter_msgs={r.inter_msgs};inter_bytes={r.inter_bytes:.0f};"
                f"intra_msgs={r.intra_msgs};halo_rows={r.n_halo_rows}"))
    return out


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--out", default="BENCH_dist_setup.json")
    args = parser.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    data = rows(smoke=args.smoke)
    print("name,us_per_call,derived")
    for name, us, derived in data:
        print(f"{name},{us:.2f},{derived}")
    with open(args.out, "w") as f:
        json.dump({"benchmark": "dist_setup",
                   "rows": [{"name": n, "us_per_call": u, "derived": d}
                            for n, u, d in data]}, f, indent=2)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
