"""The per-layer metrics that read the program's own spans
(``repro.amg.spans``), on a small run of the harness's pieces on the CPU:
what each reads, that the warm-up is left out, and that each reads
nothing from a program that recorded nothing or has no spans at all."""
import contextlib
import sys
import time

import pytest

import run
import smallroot
import spec
import traffic

METRICS = ("setup_coarsen_s", "setup_galerkin_s", "lower_plan_s",
           "lower_factors_s", "lower_place_s", "iter_dispatch_ms",
           "solve_staging_ms")
WARMUP_STEPS = 2            # run.warm_up: a two-iteration solve


def _reader(name):
    return spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py",
                            "bench_metric_" + name).read


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A set-up, warm-up and 0.2 s window of ``small.pcg1`` as the harness
    runs them, with the spans the program recorded along the way."""
    from repro.amg import spans
    from repro.amg.api import clear_sessions

    clear_sessions()
    spans.clear()
    root = smallroot.make_root(tmp_path_factory.mktemp("root"))
    cell = spec.load_cell("small.pcg1", root)
    system = run.System(*run.import_system(root))
    times = {}

    @contextlib.contextmanager
    def timed(name):
        t = time.perf_counter()
        yield
        times[name] = time.perf_counter() - t

    A_ref, bound = run.build_session(cell, system, timed)
    solve = run.cell_solver(cell, bound)
    run.warm_up(cell, solve, A_ref.nrows, 4294967311)
    window = traffic.closed_loop(solve, cell.traffic, A_ref.nrows,
                                 4294967311, 0.2)
    iters = [run.iterations_of(r.result) for r in window.requests]
    recorded = spans.recent()
    spans.clear()
    return run.Run(cell, 0.0, times, window, iters, None, {}), recorded


@pytest.fixture
def ring(monkeypatch):
    """Set what ``repro.amg.spans.recent`` returns."""
    from repro.amg import spans

    def use(recorded):
        monkeypatch.setattr(spans, "recent", lambda: list(recorded))
    return use


@pytest.mark.parametrize("name", METRICS)
def test_reader_reads_the_program(name, small, ring):
    r, recorded = small
    ring(recorded)
    value = _reader(name)(r)
    assert isinstance(value, float) and value > 0


@pytest.mark.parametrize("name", METRICS)
def test_reader_reads_nothing_from_an_empty_ring(name, small, ring):
    ring([])
    assert _reader(name)(small[0]) is None


@pytest.mark.parametrize("name", METRICS)
def test_reader_reads_nothing_from_a_program_without_spans(
        name, small, monkeypatch):
    """The readers run against the parent commit too, whose program has no
    ``repro.amg.spans``: they read nothing there instead of failing."""
    import repro.amg

    monkeypatch.delattr(repro.amg, "spans")
    monkeypatch.setitem(sys.modules, "repro.amg.spans", None)
    assert _reader(name)(small[0]) is None


def test_the_warm_up_is_left_out(small, ring):
    r, recorded = small
    ring(recorded)
    solves = [s for s in recorded if s.name == "amg.pcg"]
    assert len(solves) == len(r.window.requests) + 1     # and the warm-up
    window = {s.id for s in solves[1:]}
    steps = [s for s in recorded if s.name == "amg.pcg.step"]
    assert len(steps) == sum(r.iterations) + WARMUP_STEPS
    in_window = [s for s in steps if s.parent_id in window]
    assert len(in_window) == sum(r.iterations)
    assert _reader("iter_dispatch_ms")(r) == pytest.approx(
        sum(s.self_ns for s in in_window) / len(in_window) / 1e6)
    staging = [s.duration_ns for s in recorded if s.parent_id in window
               and s.name in ("amg.pcg.scatter", "amg.pcg.gather")]
    assert len(staging) == 3 * len(window)
    assert _reader("solve_staging_ms")(r) == pytest.approx(
        sum(staging) / len(window) / 1e6)


def test_set_up_spans_fit_inside_the_harness_spans(small, ring):
    r, recorded = small
    ring(recorded)
    read = {name: _reader(name)(r) for name in METRICS[:5]}
    assert read["setup_coarsen_s"] + read["setup_galerkin_s"] <= \
        r.spans["host_setup_s"]
    assert read["lower_plan_s"] + read["lower_factors_s"] \
        + read["lower_place_s"] <= r.spans["lowering_s"]
