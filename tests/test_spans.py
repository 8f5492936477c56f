"""Host spans (repro.amg.spans) and the named scopes of the fused device
programs: nesting and self time, the ring's bound, threads, a profiler
trace of a small distributed PCG, and which scope every device op of the
compiled ``pcg_step`` carries."""
import glob
import os
import re
import subprocess
import sys
import threading
import time
from collections import Counter

import numpy as np
import pytest

from repro.amg import setup, spans
from repro.amg.problems import laplace_3d

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mine(before: set) -> list:
    return [s for s in spans.recent() if s.id not in before]


def test_nesting_parents_and_self_time():
    before = {s.id for s in spans.recent()}
    with spans.span("outer", level=3):
        time.sleep(0.01)
        with spans.span("inner"):
            time.sleep(0.02)
        with spans.span("inner"):
            with spans.span("leaf"):
                time.sleep(0.01)
    got = _mine(before)
    # a span is kept when it closes: children before their parent
    assert [s.name for s in got] == ["inner", "leaf", "inner", "outer"]
    outer = got[-1]
    assert outer.parent_id is None and outer.attrs == {"level": 3}
    assert [s.parent_id for s in got] == [outer.id, got[2].id, outer.id,
                                          None]
    leaf, inner2 = got[1], got[2]
    assert inner2.self_ns == inner2.duration_ns - leaf.duration_ns
    assert outer.self_ns == outer.duration_ns - got[0].duration_ns \
        - inner2.duration_ns
    assert 0.009e9 <= outer.self_ns < outer.duration_ns
    for s in got:
        assert s.start_ns <= s.end_ns and 0 <= s.self_ns <= s.duration_ns


def test_a_span_that_raises_is_kept():
    before = {s.id for s in spans.recent()}
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("fails"):
                raise ValueError("boom")
    got = _mine(before)
    assert [s.name for s in got] == ["fails", "outer"]
    assert got[0].parent_id == got[1].id
    with spans.span("after"):
        pass
    assert spans.recent()[-1].parent_id is None     # the stack unwound


def test_the_ring_drops_the_oldest():
    spans.clear()
    for i in range(spans.RING_SIZE + 10):
        with spans.span("s", i=i):
            pass
    got = spans.recent()
    assert len(got) == spans.RING_SIZE
    assert got[0].attrs["i"] == 10 and got[-1].attrs["i"] == \
        spans.RING_SIZE + 9
    spans.clear()
    assert spans.recent() == []


def test_threads_keep_their_own_parents():
    spans.clear()
    start = threading.Barrier(2)

    def work(tag):
        start.wait()
        for _ in range(200):
            with spans.span("root", tag=tag):
                with spans.span("child", tag=tag):
                    pass

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    got = spans.recent()
    by_id = {s.id: s for s in got}
    assert len(got) == 800 and len(by_id) == 800
    for s in got:
        if s.name == "child":
            parent = by_id[s.parent_id]
            assert parent.name == "root" and parent.attrs == s.attrs
        else:
            assert s.parent_id is None
    spans.clear()


def test_host_setup_spans_cover_every_level():
    spans.clear()
    h = setup(laplace_3d(10), solver="rs")
    got = spans.recent()
    names = Counter(s.name for s in got)
    coarsened = h.n_levels - 1
    for stage in ("strength", "splitting", "interp", "galerkin"):
        assert names[f"amg.setup.{stage}"] == coarsened, names
    assert {s.attrs["level"] for s in got} == set(range(coarsened))
    assert all(s.parent_id is None for s in got)
    spans.clear()


def _small_dist(n=8):
    from repro.amg.dist_solve import DistHierarchy

    A = laplace_3d(n)
    return A, DistHierarchy.build(setup(A, solver="rs"), 1, 1)


def test_profiler_trace_holds_the_solve_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from repro.amg.dist_solve import dist_pcg

    A, dh = _small_dist()
    b = np.ones(A.nrows, np.float32)
    dist_pcg(dh, b, tol=0.0, maxiter=2)             # compile outside
    with jax.profiler.trace(str(tmp_path)):
        for maxiter in (3, 2):
            dist_pcg(dh, b, tol=0.0, maxiter=maxiter)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in host for line in p.lines for e in line.events
              if e.name.startswith("amg.pcg")]
    solves = [e for e in events if e[0] == "amg.pcg"]
    assert len(solves) == 2
    for (_, lo, hi), want in zip(sorted(solves, key=lambda e: e[1]), (3, 2)):
        inside = Counter(name for name, s, e in events
                         if name != "amg.pcg" and lo <= s and e <= hi)
        assert inside["amg.pcg.step"] == want
        assert inside["amg.pcg.sync"] == want + 1
        assert inside["amg.pcg.init"] == 1 and inside["amg.pcg.gather"] == 1
        assert inside["amg.pcg.scatter"] == 2


def test_solve_spans_nest_under_one_root_per_call():
    from repro.amg.dist_solve import dist_pcg

    A, dh = _small_dist()
    b = np.ones((A.nrows, 2), np.float32)
    spans.clear()
    res = dist_pcg(dh, b, tol=0.0, maxiter=4)
    got = spans.recent()
    root = got[-1]
    assert root.name == "amg.pcg" and root.parent_id is None
    assert root.attrs == {"n": A.nrows, "columns": 2, "maxiter": 4}
    assert all(s.parent_id == root.id for s in got[:-1])
    assert Counter(s.name for s in got[:-1])["amg.pcg.step"] == 4
    assert [s.attrs["bytes"] for s in got if s.name.startswith(
        "amg.pcg.scatter")] == [b.nbytes, b.nbytes]
    assert all(c.iterations == 4 for c in res.columns)
    spans.clear()


# ------------------------------------------------------------ named scopes
# which ops must name their level and phase: gathers (the local products),
# dots (block-ELL and the coarse solve) and every collective
SCOPED_OPS = ("gather", "dot", "all-reduce", "all-gather", "all-to-all",
              "collective-permute", "reduce-scatter")
PHASE = re.compile(r"^(L\d+\.(presmooth|residual|restrict|interp|postsmooth"
                   r"|coarse|Ap)|pcg\.(dot|update))$")


def scope_report(text: str) -> dict:
    """Phase components of every scoped op's ``op_name`` in compiled HLO
    text: ops with other than one component, and the components seen."""
    bad, seen, apply_scopes = [], Counter(), Counter()
    for line in text.splitlines():
        m = re.match(r"\s*(ROOT )?%\S+ = .*? ([a-z\-]+)\(", line)
        if not m or m.group(2) not in SCOPED_OPS:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        parts = name.group(1).split("/") if name else []
        phases = [p for p in parts if PHASE.match(p)]
        if len(phases) != 1:
            bad.append(line.strip()[:160])
            continue
        seen[phases[0]] += 1
        apply_scopes.update(p for p in parts
                            if p in ("halo", "local", "remote"))
    return {"bad": bad, "seen": seen, "apply": apply_scopes}


def expected_phases(n_levels: int) -> set:
    want = {"L0.Ap", "pcg.dot", f"L{n_levels - 1}.coarse"}
    for l in range(n_levels - 1):
        want |= {f"L{l}.{p}" for p in ("presmooth", "residual", "restrict",
                                       "interp", "postsmooth")}
    return want


def compiled_pcg_step(dh) -> str:
    import jax.numpy as jnp

    from repro.amg.solve import SolveOptions

    progs, arrs = dh.programs(SolveOptions())
    D = dh.n_pods * dh.lanes
    vec = jnp.zeros((D, dh.levels[0].A.plan.local_n), dh.dtype)
    rz = jnp.zeros((), dh.dtype)
    return progs["pcg_step"].lower(vec, vec, vec, rz, arrs).compile() \
        .as_text()


def test_every_op_of_pcg_step_names_one_level_and_phase():
    _, dh = _small_dist(12)
    rep = scope_report(compiled_pcg_step(dh))
    assert rep["bad"] == []
    assert set(rep["seen"]) >= expected_phases(len(dh.levels)) - {"pcg.dot"}
    assert rep["apply"]["local"] > 0


FOUR_DEVICES = r"""
import sys
sys.path.insert(0, {tests!r})
from repro.amg import setup
from repro.amg.dist_solve import DistHierarchy
from repro.amg.problems import laplace_3d
from test_spans import compiled_pcg_step, expected_phases, scope_report

dh = DistHierarchy.build(setup(laplace_3d(12), solver="rs"), 2, 2)
rep = scope_report(compiled_pcg_step(dh))
assert rep["bad"] == [], rep["bad"][:5]
missing = expected_phases(len(dh.levels)) - set(rep["seen"])
assert not missing, missing
assert rep["apply"]["halo"] > 0 and rep["apply"]["local"] > 0, rep["apply"]
print("SCOPES_OK", dict(rep["apply"]))
"""


def test_scopes_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"), os.environ.get("PYTHONPATH",
                                                              "")]))
    code = FOUR_DEVICES.format(tests=os.path.join(ROOT, "tests"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "SCOPES_OK" in out.stdout
