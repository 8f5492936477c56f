"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python3 tests/bench/record_trace.py --cell small --n 16 --out <dir>
    python3 tests/bench/record_trace.py --cell small-2x2 --n 16 --out <dir>

On a TPU machine: the harness's set-up for a real cell's configuration at
an ``n``³ grid (``smallroot.py``), a warm-up, then two requests of the
traffic mix under the profiler with the harness's own spans.  Writes to
``--out``:

* ``<cell>.xplane.pb`` — the trace, for the test to read;
* ``<cell>.expected.json`` — what ``bench/devicetrace.py`` and the trace's
  per-layer metrics made of it on the chip, with the iterations, least
  work and peaks they read, which the test must reproduce;
* ``<cell>.structure.txt`` — every plane and line of the trace with its
  busiest event names and a few events' stats, to read by hand.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile
from collections import Counter

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "bench")]

import leastbytes  # noqa: E402
import run  # noqa: E402
import smallroot  # noqa: E402
import spec  # noqa: E402
import devicetrace  # noqa: E402
import traffic  # noqa: E402


def structure(path: pathlib.Path) -> str:
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            dur = Counter()
            for ev in evs:
                dur[ev.name] += ev.duration_ns
            lines.append(f"  LINE {line.name!r}: {len(evs)} events")
            for name, ns in dur.most_common(15):
                lines.append(f"    {ns / 1e6:12.3f} ms  {name}")
            for ev in evs[:3]:
                stats = [(k, str(v)[:80]) for k, v in ev.stats][:12]
                lines.append(f"    e.g. {ev.name} @{ev.start_ns} "
                             f"+{ev.duration_ns}ns {stats}")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", default="small")
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    root = smallroot.make_root(pathlib.Path(tempfile.mkdtemp()), n=args.n,
                               peaks_kind=jax.devices()[0].device_kind)
    cell, system, _, _ = run.open_cell(f"{args.cell}.pcg1", root)
    A_ref, bound = run.build_session(
        cell, system, lambda name: jax.profiler.TraceAnnotation(name))
    solve = run.cell_solver(cell, bound)
    run.warm_up(cell, solve, A_ref.nrows, 1)
    with run.profiled(True) as log_dir:
        with jax.profiler.TraceAnnotation(devicetrace.WINDOW_SPAN):
            # two one-request windows: two solves, seeds 2 and 3
            windows = [traffic.closed_loop(
                solve, cell.traffic, A_ref.nrows, seed, 1e-9,
                span=jax.profiler.TraceAnnotation) for seed in (2, 3)]
    src = devicetrace.find_xplane(log_dir)
    dest = out / f"{args.cell}.xplane.pb"
    shutil.copyfile(src, dest)
    shutil.rmtree(log_dir, ignore_errors=True)
    (out / f"{args.cell}.structure.txt").write_text(structure(dest))
    summary = devicetrace.summarize(devicetrace.read_xplane(dest))
    iterations = [run.iterations_of(w.requests[0].result) for w in windows]
    work = leastbytes.iteration_work(bound.hierarchy.levels,
                                     cell.config["session"]["opts"],
                                     bound.config.dtype)
    peaks = spec.peaks_for(jax.devices()[0].device_kind)
    record = run.Run(cell, 0.0, {}, windows[0], iterations, work, peaks,
                     summary)
    expected = {
        "iterations": iterations,
        "work": {"bytes": work.bytes, "flops": work.flops},
        "peaks": peaks,
        "metrics": {m["name"]: spec.metric_reader(cell, m["name"])(record)
                    for m in cell.per_layer
                    if m["source"] == "device_trace"},
        "window_ns": summary.window_ns,
        "chips_of_cell": cell.chips,
        "chips": [{"busy_ns": c.busy_ns,
                   "busy_in_solves_ns": c.busy_in_solves_ns,
                   "collective_ns": c.collective_ns,
                   "ops": sum(1 for _ in c.op_ns), "gaps": len(c.gaps)}
                  for c in summary.chips],
        "breakdown": devicetrace.breakdown(summary)}
    (out / f"{args.cell}.expected.json").write_text(
        json.dumps(expected, indent=1) + "\n")
    print(json.dumps(expected))
    return 0


if __name__ == "__main__":
    sys.exit(main())
