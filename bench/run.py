"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration, its traffic mix
and its metrics are found by name (see ``bench/spec.py``).  In order:

1. set-up — refuse unless JAX's first device is a TPU and there are as
   many as the cell's chips and the chip is in ``bench/peaks.json``; make
   the matrix with the benchmark's own generator; build the solver session
   (``AMGSolver.setup``) and its lowering; warm up the cell's one shape
   with a one-iteration solve, which compiles (or loads from the
   persistent cache) every program a full solve runs;
2. the window — the traffic mix's closed loop for ``--seconds``
   (``bench/traffic.py`` states when the window ends), profiled when
   ``--trace 1``;
3. the check — every answer the window returned, held by its float64
   true residual on the benchmark's own matrix (``bench/reference.py``)
   to the configuration's tolerance (the session's ``tol``);
4. the result — earlier lines name the window's requests and the
   compilations that ran inside it (there should be none); the last lines
   of standard error give each number compared beside its limit; the last
   line of standard output is one JSON object: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` when
   traced, and ``checks`` last.

Set-up is counted from the start of this process to the window's start.
The persistent compilation cache lives in ``.jax_cache/`` at the root of
the checkout, or where ``JAX_COMPILATION_CACHE_DIR`` points.  No device,
too few chips, an unknown chip or a missing program: a non-zero exit and
no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import devicetrace  # noqa: E402
import leastbytes  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class Refused(RuntimeError):
    """The machine or the checkout cannot run this cell."""


@dataclasses.dataclass
class Run:
    """What one run measured, for the metric readers (``bench/metrics``)."""

    cell: spec.Cell
    setup_s: float
    spans: dict[str, float]          # host seconds of each set-up step
    window: traffic.Window
    iterations: list[int]            # PCG iterations of each request
    work: leastbytes.Work            # least work of one PCG iteration
    peaks: dict
    trace: devicetrace.Summary | None = None


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def check_device(chips: int):
    """The machine's devices, or :class:`Refused` unless they are TPUs and
    at least ``chips`` of them.  Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key), or where
    ``JAX_COMPILATION_CACHE_DIR`` points.  Every program is cached, however
    short its compile, so a second run compiles nothing."""
    import os

    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def import_system(root: pathlib.Path):
    """The system under test, from ``src/`` of the checkout."""
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        from repro.amg.api import AMGConfig, AMGSolver
        from repro.amg.csr import CSR
    except ImportError as e:
        raise Refused(f"the system under test is missing: {e}") from None
    return AMGConfig, AMGSolver, CSR


def iterations_of(result) -> int:
    cols = getattr(result, "columns", None)
    return max(c.iterations for c in cols) if cols else result.iterations


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def check_answers(A_ref, window: traffic.Window, limit: float):
    """Worst float64 true residual over the window's answers, and how many
    answers exceed ``limit``.  An answer of the wrong shape or with a
    non-finite entry fails and reads as the largest float, so the result
    line stays plain JSON."""
    worst, failed = 0.0, 0
    for req in window.requests:
        x = getattr(req.result, "x", None)
        try:
            if np.shape(x) != req.b.shape:
                raise ValueError(f"answer of shape {np.shape(x)}")
            rel = reference.rel_residual(A_ref, x, req.b)
        except (TypeError, ValueError, IndexError):
            rel = math.inf
        if not math.isfinite(rel):
            rel = sys.float_info.max
        if rel > limit:
            failed += 1
        worst = max(worst, rel)
    return worst, failed


@contextlib.contextmanager
def profiled(enabled: bool):
    """Profile the block when ``enabled``; yields the trace's directory, a
    new one under ``TMPDIR`` that the caller reads and removes, or
    ``None``."""
    if not enabled:
        yield None
        return
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class System:
    """The system under test as the harness drives it."""

    AMGConfig: type
    AMGSolver: type
    CSR: type


def open_cell(name: str, root: pathlib.Path):
    """The cell, the system under test, the chips and their peaks; raises
    :class:`Refused` or :class:`spec.SpecError` before any work."""
    cell = spec.load_cell(name, root)
    traffic.check_mix(cell.traffic)
    if int(cell.config.get("fixed_iterations", 0)) < 1:
        raise spec.SpecError(f"{cell.config['name']}: fixed_iterations "
                             f"must be given and >= 1")
    system = System(*import_system(root))
    say(f"compilation cache in {enable_compile_cache(root)}")
    devices = check_device(cell.chips)
    peaks = spec.peaks_for(devices[0].device_kind, root)
    say(f"device {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}")
    return cell, system, devices, peaks


def build_session(cell: spec.Cell, system: System, timed, dtype=None):
    """The benchmark's matrix and the solver session bound to a copy of
    it, lowered onto the mesh.  ``dtype`` overrides the configuration's
    precision (the control runs the program's lower-precision path)."""
    conf = cell.config
    gen = spec.problem_module(cell)
    with timed("generate_s"):
        A_ref = gen.build(**{k: v for k, v in conf["problem"].items()
                             if k != "generator"})
        A = system.CSR(A_ref.shape, A_ref.indptr.copy(),
                       A_ref.indices.copy(), A_ref.data.copy())
    session = system.AMGConfig.from_dict(conf["session"])
    if dtype is not None:
        session = session.replace(dtype=dtype)
    say(f"{conf['name']}: {A_ref.nrows} rows, {A_ref.nnz} nnz, "
        f"{session.n_pods}x{session.lanes} mesh, {session.dtype}, "
        f"tol {session.tol}")
    with timed("host_setup_s"):
        bound = system.AMGSolver(session).setup(A)
    with timed("lowering_s"):
        dh = bound.dist_hierarchy
    say(f"{len(dh.levels)} levels, rows "
        f"{[lv.A.nrows for lv in bound.hierarchy.levels]}")
    return A_ref, bound


def cell_solver(cell: spec.Cell, bound):
    """The call each request of the cell makes: the mix's method for the
    configuration's fixed iteration count."""
    return traffic.request_solver(cell.traffic, bound,
                                  cell.config["fixed_iterations"])


def warm_up(cell: spec.Cell, solve, n: int, seed: int) -> None:
    """Compile, or load from the cache, every program the window runs: a
    two-iteration solve runs ``pcg_init`` and ``pcg_step`` on the
    arguments both of a first and of a later step."""
    solve(traffic.draw_rhs(cell.traffic, n, seed, 0, stream=traffic.WARMUP),
          maxiter=2)


def measure(args, root: pathlib.Path) -> dict:
    cell, system, devices, peaks = open_cell(args.workload, root)

    import jax

    compiles: list[str] = []

    def on_event(event, secs, **kw):
        if event in COMPILE_EVENTS:
            compiles.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _measure(args, cell, system, devices, peaks, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _measure(args, cell, system, devices, peaks, compiles) -> dict:
    import jax

    spans: dict[str, float] = {}

    @contextlib.contextmanager
    def timed(name):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        spans[name] = time.perf_counter() - t

    conf = cell.config
    A_ref, bound = build_session(cell, system, timed)
    n = A_ref.nrows
    solve = cell_solver(cell, bound)
    with timed("warmup_s"):
        warm_up(cell, solve, n, args.seed)
    setup_s = time.perf_counter() - T_PROCESS
    say("set-up " + ", ".join(f"{k} {v:.3f}" for k, v in spans.items())
        + f", setup_s {setup_s:.3f}")

    before = len(compiles)
    summary = None
    with profiled(args.trace) as log_dir:
        with jax.profiler.TraceAnnotation(devicetrace.WINDOW_SPAN):
            window = traffic.closed_loop(
                solve, cell.traffic, n, args.seed, args.seconds,
                span=jax.profiler.TraceAnnotation)
    in_window = compiles[before:]
    say(f"compilations inside the window: {len(in_window)} {in_window}")
    mem = memory_peak(devices[:cell.chips])
    if log_dir is not None:
        try:
            summary = devicetrace.summarize(
                devicetrace.read_xplane(devicetrace.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    iters = [iterations_of(r.result) for r in window.requests]
    for r, it in list(zip(window.requests, iters))[:20]:
        print(f"[bench] request {r.index}: start {r.start_s:.3f}s, "
              f"{r.seconds:.3f}s, {it} iterations", file=sys.stderr)
    say(f"window {window.seconds:.3f}s: {len(window.requests)} requests "
        f"started inside {args.seconds}s")
    work = leastbytes.iteration_work(bound.hierarchy.levels,
                                     conf["session"].get("opts", {}),
                                     bound.config.dtype)
    say(f"least work of a PCG iteration: {work.bytes:.0f} bytes, "
        f"{work.flops:.0f} operations")
    del bound, solve

    limit = float(conf["session"]["tol"])     # the stated tolerance
    worst, failed = check_answers(A_ref, window, limit)
    run = Run(cell, setup_s, spans, window, iters, work, peaks, summary)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(cell, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": failed == 0,
           "attempted": len(window.requests), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean("busy_ns") / 1e9
        device["window_s"] = summary.window_ns / 1e9
        out["breakdown"] = devicetrace.breakdown(summary)
    out["checks"] = {"rel_residual_max": {"value": worst, "limit": limit}}
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: pathlib.Path = spec.ROOT) -> int:
    args = parse_args(argv)
    try:
        out = measure(args, root)
    except (Refused, spec.SpecError) as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
