"""Run the harness on CPU devices at a small grid, with a fault planted.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench/run_small.py --cell small-2x2 --fault exchange

For tests that need several devices, which JAX fixes when it starts, so
they run in a process of their own.  Faults:

* ``none`` — the program as it is;
* ``exchange`` — every halo exchange between devices returns zeros, as if
  the exchange were left out;
* ``unchanged`` — every PCG step returns its state unchanged;
* ``altered`` — one entry of every answer is changed where it is produced.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "bench"),
                str(HERE.parents[1] / "src")]

import run  # noqa: E402
import smallroot  # noqa: E402

FAULTS = ("none", "exchange", "unchanged", "altered")


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from repro.amg import dist_solve, dist_spmv

    if fault == "exchange":
        real = dist_spmv.halo_exchange

        def no_exchange(*args, **kw):
            return jnp.zeros_like(real(*args, **kw))

        dist_spmv.halo_exchange = no_exchange
    elif fault == "unchanged":
        real_programs = dist_solve.DistHierarchy.programs

        def programs(self, opts):
            progs, arrs = real_programs(self, opts)
            progs = dict(progs)
            progs["pcg_step"] = lambda x, r, p, rz, a: (
                x, r, p, rz, jnp.sqrt(jnp.sum(r * r)))
            return progs, arrs

        dist_solve.DistHierarchy.programs = programs
    elif fault == "altered":
        real_pcg = dist_solve.dist_pcg

        def altered(*args, **kw):
            res = real_pcg(*args, **kw)
            res.x[len(res.x) // 2] += 1.0
            return res

        dist_solve.dist_pcg = altered


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", default="small")
    ap.add_argument("--fault", choices=FAULTS, default="none")
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--seconds", default="0.2")
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args(argv)
    root = smallroot.make_root(pathlib.Path(tempfile.mkdtemp()), n=args.n,
                               dtype=args.dtype)
    plant(args.fault)
    with smallroot.on_cpu(run):
        return run.main(["--workload", f"{args.cell}.pcg1", "--seed",
                         "4294967311", "--seconds", args.seconds,
                         "--trace", "0"], root=root)


if __name__ == "__main__":
    sys.exit(main())
