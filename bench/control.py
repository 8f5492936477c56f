"""Readings the answer check's limit is set from, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds <s1,s2,...>
        --control-seeds <c1,c2,c3> [--requests <n>] [--control-requests <n>]

In one process, on the cell's chips: build the cell's session as a run
does, then solve the first ``--requests`` right-hand sides of every seed
(what a window of that seed solves first) and read each answer's float64
true residual on the benchmark's own matrix.  Then the control: the same
session lowered in the precision below the configuration's (bfloat16 for
float32), the program's own lower-precision path, on the control seeds,
read the same way.  The limit, the configuration's tolerance, has to sit
above the largest program reading and below the smallest control reading
(PERF.md gives both).

Each request is the call a run makes (``bench/traffic.py``).  The
benchmark's own runs never run this.  The last line of standard output is
one JSON object with every reading.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

LOWER = {"float64": "float32", "float32": "bfloat16"}


def readings(cell, A_ref, bound, seeds, requests) -> list:
    solve = run.cell_solver(cell, bound)
    run.warm_up(cell, solve, A_ref.nrows, seeds[0])
    out = []
    for seed in seeds:
        for i in range(requests):
            b = traffic.draw_rhs(cell.traffic, A_ref.nrows, seed, i)
            t = time.perf_counter()
            res = solve(b)
            secs = time.perf_counter() - t
            rel = reference.rel_residual(A_ref, res.x, b)
            out.append({"seed": seed, "request": i, "seconds": secs,
                        "iterations": run.iterations_of(res),
                        "converged": bool(getattr(res, "converged", True)),
                        "rel_residual": rel})
            run.say(f"seed {seed} request {i}: {secs:.3f}s, "
                    f"{out[-1]['iterations']} iterations, rel {rel:.4e}")
    return out


def main(argv=None, root: pathlib.Path = spec.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the program's readings")
    ap.add_argument("--control-seeds", required=True,
                    help="comma-separated seeds of the control's readings")
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--control-requests", type=int, default=None,
                    help="requests per control seed (default --requests)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = [int(s) for s in args.control_seeds.split(",")]
    try:
        cell, system, devices, _ = run.open_cell(args.workload, root)
    except (run.Refused, spec.SpecError) as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 3
    timed = lambda name: contextlib.nullcontext()  # noqa: E731
    A_ref, bound = run.build_session(cell, system, timed)
    program = readings(cell, A_ref, bound, seeds, args.requests)
    lower = LOWER[bound.config.dtype]
    del bound
    A_ref, bound = run.build_session(cell, system, timed, dtype=lower)
    control = readings(cell, A_ref, bound, control_seeds,
                       args.control_requests or args.requests)
    lo = max(r["rel_residual"] for r in program)
    hi = min(r["rel_residual"] for r in control)
    run.say(f"program: largest rel_residual {lo!r} over {len(program)} "
            f"answers; control ({lower}): smallest {hi!r} over "
            f"{len(control)}")
    print(json.dumps({"workload": cell.name, "device": devices[0].device_kind,
                      "program_max": lo, "control_min": hi,
                      "control_dtype": lower, "program": program,
                      "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
