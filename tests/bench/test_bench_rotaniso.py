"""The rotated anisotropic cell: the benchmark's own matrix, a whole run of
the harness at a small grid on the CPU (float64 answers pass the check,
the program's float32 path, the control, does not), and the two readers
of the refinement's spans."""
import json
import sys
import types

import numpy as np
import pytest

import run
import smallroot
import spec

REAL = json.loads((smallroot.BENCH / "configs" / "rotaniso512.json")
                  .read_text())


def _generator():
    return spec.load_module(
        smallroot.BENCH / "problems" / "rotated_anisotropic_2d.py",
        "bench_problem_rotated_anisotropic_2d")


@pytest.mark.parametrize("nx", [1, 3, 5, 8, 16])
def test_copy_makes_the_programs_csr(nx):
    from repro.amg.problems import rotated_anisotropic_2d

    mine = _generator().build(nx, REAL["problem"]["eps"],
                              REAL["problem"]["theta"])
    theirs = rotated_anisotropic_2d(nx)
    assert mine.shape == theirs.shape
    np.testing.assert_array_equal(mine.indptr, theirs.indptr)
    np.testing.assert_array_equal(mine.indices, theirs.indices)
    np.testing.assert_array_equal(mine.data, theirs.data)


def test_stencil_is_the_sources():
    """pyamg's FD stencil at ε = 0.001, θ = 45°: corners ±(1 − ε)/4, the
    four edges −(1 + ε)/2, rows summing to zero."""
    st = _generator().stencil(0.001, np.pi / 4)
    a = 0.999 / 4
    np.testing.assert_allclose(st[[0, 2], [0, 2]], -a, rtol=1e-14)
    np.testing.assert_allclose(st[[0, 2], [2, 0]], a, rtol=1e-14)
    np.testing.assert_allclose(st[[0, 1, 1, 2], [1, 0, 2, 1]], -1.001 / 2,
                               rtol=1e-14)
    assert abs(st.sum()) < 1e-14


def test_size_512_counts():
    """262,144 rows and 2,353,156 nonzeros, as the configuration states:
    each axis has 3·512 − 2 (row, neighbour) pairs, and the stencil is
    their product."""
    p = REAL["problem"]
    A = _generator().build(p["nx"], p["eps"], p["theta"])
    assert (A.nrows, A.nnz) == (REAL["rows"], REAL["nnz"])
    assert (REAL["rows"], REAL["nnz"]) == (512 ** 2, (3 * 512 - 2) ** 2)
    inside = np.ones(A.nnz - 1, dtype=bool)      # columns sorted in a row
    inside[A.indptr[1:-1] - 1] = False
    assert np.all(np.diff(A.indices)[inside] > 0)


def _aniso_root(dest, *, nx, iterations, **session):
    """A checkout in miniature with one cell, ``small-aniso.pcg1``: the
    real configuration at an ``nx``² grid."""
    (dest / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "metrics", "problems"):
        (dest / "bench" / sub).symlink_to(smallroot.BENCH / sub)
    (dest / "src").symlink_to(smallroot.REPO / "src")
    peaks = {"devices": {smallroot.CPU_KIND: {"hbm_bytes_per_s": 1e11,
                                              "flops_per_s": 1e12,
                                              "source": "test"}}}
    (dest / "bench" / "peaks.json").write_text(json.dumps(peaks))
    conf = dict(REAL, name="small-aniso", fixed_iterations=iterations,
                problem=dict(REAL["problem"], nx=nx),
                session=dict(REAL["session"], **session))
    path = "bench/configs/small-aniso.json"
    (dest / path).write_text(json.dumps(conf))
    real = json.loads((smallroot.REPO / "BENCHMARK.json").read_text())
    cell = {"name": "small-aniso.pcg1", "config": "small-aniso",
            "traffic": "pcg1", "chips": 1, "why": "test"}
    bench = dict(real, workloads=[cell],
                 configs=[{"name": "small-aniso", "source": "test",
                           "file": path, "reduced": [], "why": "test"}])
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


def _run_cell(root, capsys):
    from repro.amg.api import clear_sessions

    clear_sessions()
    with smallroot.on_cpu(run):
        rc = run.main(["--workload", "small-aniso.pcg1", "--seed",
                       "4294967311", "--seconds", "0.2", "--trace", "0"],
                      root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_float64_cell_is_correct_and_its_float32_control_is_not(tmp_path,
                                                                capsys):
    """At 64², 60 inner iterations: the refined float64 answers read far
    below the limit, 1e-6; the float32 path, the control one precision
    below, stalls near 5e-6 whatever the iteration count."""
    out = _run_cell(_aniso_root(tmp_path / "f64", nx=64, iterations=60),
                    capsys)
    check = out["checks"]["rel_residual_max"]
    assert out["correct"] is True and out["failed"] == 0
    assert check["limit"] == 1e-6 and check["value"] < 1e-7
    assert set(out["metrics"]) == {"solves_per_s", "solve_p95_s",
                                   "setup_s"}
    control = _run_cell(_aniso_root(tmp_path / "f32", nx=64, iterations=60,
                                    dtype="float32"), capsys)
    check = control["checks"]["rel_residual_max"]
    assert control["correct"] is False
    assert control["failed"] == control["attempted"]
    assert check["value"] > 2 * check["limit"]


def _reader(name):
    return spec.load_module(smallroot.BENCH / "metrics" / f"{name}.py",
                            f"bench_metric_{name}").read


def _run_of(requests):
    window = types.SimpleNamespace(requests=[None] * requests)
    return types.SimpleNamespace(window=window)


def _span(i, name, parent=None, duration=1, **attrs):
    from repro.amg.spans import Span
    return Span(i, parent, name, 0, duration, duration, attrs)


@pytest.fixture
def ring(monkeypatch):
    from repro.amg import spans

    def use(recorded):
        monkeypatch.setattr(spans, "recent", lambda: list(recorded))
    return use


def test_readers_take_the_windows_solves(ring):
    """A warm-up solve, then two window solves of two segments each: the
    warm-up's segments are left out."""
    ring([_span(1, "amg.refine"),
          _span(2, "amg.refine.residual", 1, 9_000_000, rel=1.0,
                rec_rel=0.1),
          _span(3, "amg.refine"),
          _span(4, "amg.pcg", 3),
          _span(5, "amg.refine.residual", 3, 2_000_000, rel=1e-3,
                rec_rel=1e-3),
          _span(6, "amg.refine.residual", 3, 3_000_000, rel=1.2e-6,
                rec_rel=1e-6),
          _span(7, "amg.refine"),
          _span(8, "amg.refine.residual", 7, 4_000_000, rel=1.1e-3,
                rec_rel=1e-3),
          _span(9, "amg.refine.residual", 7, 1_000_000, rel=1e-6,
                rec_rel=1e-6)])
    assert _reader("refine_host_ms")(_run_of(2)) == pytest.approx(5.0)
    assert _reader("refine_drift")(_run_of(2)) == pytest.approx(1.2)


@pytest.mark.parametrize("name", ["refine_host_ms", "refine_drift"])
def test_readers_read_nothing_without_refinement(ring, name):
    """A float32 session (both HPCG cells) records PCG spans and no
    refinement; an empty ring reads nothing either."""
    ring([_span(1, "amg.pcg"), _span(2, "amg.pcg.step", 1)])
    assert _reader(name)(_run_of(1)) is None
    ring([])
    assert _reader(name)(_run_of(1)) is None


@pytest.mark.parametrize("name", ["refine_host_ms", "refine_drift"])
def test_readers_of_a_program_without_spans(monkeypatch, name):
    import repro.amg

    monkeypatch.delattr(repro.amg, "spans")
    monkeypatch.setitem(sys.modules, "repro.amg.spans", None)
    assert _reader(name)(_run_of(1)) is None


def test_readers_on_a_real_sessions_spans():
    """The spans a float64 session records on the CPU: host time per solve
    is positive, and float32 segments of a 1e-3 drop drift by well under
    2× from what they predict."""
    from repro.amg import spans
    from repro.amg.api import AMGConfig, AMGSolver, clear_sessions
    from repro.amg.problems import rotated_anisotropic_2d

    clear_sessions()
    A = rotated_anisotropic_2d(32)
    bound = AMGSolver(AMGConfig.from_dict(
        dict(REAL["session"]))).setup(A)
    rng = np.random.default_rng(3)
    spans.clear()
    for _ in range(3):      # one warm-up, two window solves
        bound.pcg(rng.standard_normal(A.nrows).astype(np.float32),
                  tol=0.0, maxiter=40)
    host_ms = _reader("refine_host_ms")(_run_of(2))
    drift = _reader("refine_drift")(_run_of(2))
    assert host_ms is not None and host_ms > 0
    assert drift is not None and 0.5 < drift < 2.0
    clear_sessions()
