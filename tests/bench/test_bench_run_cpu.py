"""A whole run of the harness on the CPU at a small grid: control flow,
the window rule, the result's keys, and that the answer check fails the
control and every planted fault.  Never a time."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import run
import smallroot
import traffic

MIX = {"loop": "closed", "method": "pcg", "columns": 1,
       "rhs": "standard_normal", "x0": "zero"}


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_whole_run_on_cpu(tmp_path, capsys):
    from repro.amg.api import clear_sessions

    clear_sessions()
    root = smallroot.make_root(tmp_path)
    with smallroot.on_cpu(run):
        rc = run.main(["--workload", "small.pcg1", "--seed", "4294967311",
                       "--seconds", "0.2", "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert rc == 0
    last = _last_json(out.out)
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert set(last["metrics"]) == {"solves_per_s", "solve_p95_s",
                                    "setup_s"}
    units = {k: v["unit"] for k, v in last["metrics"].items()}
    assert units == {"solves_per_s": "rhs/s", "solve_p95_s": "s",
                     "setup_s": "s"}
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    check = last["checks"]["rel_residual_max"]
    assert check["value"] <= check["limit"] == 1e-6     # the session's tol
    assert out.err.strip().splitlines()[-1].startswith(
        "check rel_residual_max ")
    assert "compilations inside the window: 0 " in out.out


def _fake_clock(durations):
    """A clock that only solves move: each solve takes the next duration."""
    now = [0.0]
    it = iter(durations)

    def clock():
        return now[0]

    def solve(b):
        now[0] += next(it)
        return b.sum()
    return clock, solve


def test_window_ends_with_the_last_solve_started_inside():
    clock, solve = _fake_clock([4.0, 4.0, 4.0, 4.0])
    w = traffic.closed_loop(solve, MIX, 8, 1, 10.0, clock=clock)
    # starts at 0, 4 and 8 are inside 10 s; the window ends at 12
    assert [r.start_s for r in w.requests] == [0.0, 4.0, 8.0]
    assert w.seconds == 12.0
    assert [r.seconds for r in w.requests] == [4.0, 4.0, 4.0]


def test_window_holds_at_least_one_solve():
    clock, solve = _fake_clock([30.0, 1.0])
    w = traffic.closed_loop(solve, MIX, 8, 1, 10.0, clock=clock)
    assert len(w.requests) == 1 and w.seconds == 30.0


def test_same_seed_same_inputs():
    big = 2 ** 31 + 12345
    a = traffic.draw_rhs(MIX, 100, big, 3)
    np.testing.assert_array_equal(a, traffic.draw_rhs(MIX, 100, big, 3))
    assert a.dtype == np.float32 and a.shape == (100,)
    assert not np.array_equal(a, traffic.draw_rhs(MIX, 100, big, 4))
    assert not np.array_equal(a, traffic.draw_rhs(MIX, 100, big + 1, 3))
    warm = traffic.draw_rhs(MIX, 100, big, 3, stream=traffic.WARMUP)
    assert not np.array_equal(a, warm)
    assert traffic.draw_rhs(dict(MIX, columns=4), 100, 1, 0).shape == (100, 4)


def test_requests_run_a_fixed_iteration_count():
    calls = []

    class Bound:
        def pcg(self, b, **kw):
            calls.append(kw)

    traffic.request_solver(MIX, Bound(), 17)(np.zeros(3))
    assert calls == [{"tol": 0.0, "maxiter": 17}]
    traffic.check_mix(MIX)
    for bad in ({"columns": 0}, {"loop": "open"}, {"rhs": "ones"}):
        with pytest.raises(ValueError):
            traffic.check_mix(dict(MIX, **bad))


def _run_small(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(smallroot.HERE
                                            / "run_small.py"), *args],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return _last_json(p.stdout)


def test_sound_run_on_four_devices_is_correct():
    out = _run_small("--cell", "small-2x2")
    assert out["correct"] is True and out["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["exchange", "unchanged", "altered"])
def test_planted_fault_is_not_correct(fault):
    """The exchange between chips left out, a step that returns its state
    unchanged, an answer altered where it is produced: each fails the
    check.  (The mix sends one right-hand side a request, so no batch can
    lose half of its columns.)"""
    out = _run_small("--cell", "small-2x2", "--fault", fault)
    assert out["correct"] is False and out["failed"] == out["attempted"]
    check = out["checks"]["rel_residual_max"]
    assert check["value"] > check["limit"]


def test_control_is_not_correct():
    """The control: the program's own bfloat16 path, one precision below
    the configuration's float32, fails the check by orders of magnitude."""
    out = _run_small("--cell", "small", "--n", "16", "--dtype", "bfloat16")
    assert out["correct"] is False
    check = out["checks"]["rel_residual_max"]
    assert check["value"] > 100 * check["limit"]


def test_answers_that_are_not_numbers_fail_as_plain_json():
    import types

    import reference

    A = reference.Matrix((2, 2), np.array([0, 1, 2]), np.array([0, 1]),
                         np.array([2.0, 2.0]))
    b = np.ones(2, dtype=np.float32)
    reqs = [traffic.Request(i, 0.0, 1.0, b, types.SimpleNamespace(x=x))
            for i, x in enumerate([np.full(2, 0.5), np.full(2, np.nan),
                                   np.zeros(3), None])]
    worst, failed = run.check_answers(A, traffic.Window(reqs, 1.0), 1e-5)
    assert failed == 3
    assert json.loads(json.dumps(worst)) == worst and np.isfinite(worst)
