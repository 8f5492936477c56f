"""Tests for the static-analysis subsystem itself (repro.analysis).

Pass 1: golden collective signatures for the hier collectives on a 1×1
mesh, clean program audits, and the two injected regressions the auditor
exists to catch (flat-psum substitution, empty-halo collective).  The 2×4
traced goldens run in the 8-device subprocess (tests/dist_solve_script.py,
"OK comm_audit").  Pass 2: one unit test per lint rule, including the
deliberately bad coroutine and the marker suppressions, plus the
clean-tree gate.
"""
import pathlib
import textwrap

import numpy as np
import pytest

from repro.analysis import audit_apply, audit_program, audit_setup
from repro.analysis import collective_signature
from repro.analysis.lint import lint_paths, lint_source

SRC = pathlib.Path(__file__).parents[1] / "src"


# ---------------------------------------------------------------- fixtures


@pytest.fixture(scope="module")
def dh11():
    """A small lowered hierarchy on the in-process 1×1 mesh (collectives
    still trace — every halo is empty but hier_psum/hier_all_gather keep
    their strategy lowerings)."""
    pytest.importorskip("jax")
    from repro.amg import setup
    from repro.amg.dist_solve import DistHierarchy
    from repro.amg.problems import laplace_3d
    h = setup(laplace_3d(6), solver="rs", max_coarse=30)
    return DistHierarchy.build(h, 1, 1)


# ------------------------------------------------------- pass 1: comm audit


def test_hier_collective_golden_signatures_1x1():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core.nap_collectives import (GATHER_SIGNATURES,
                                            REDUCE_SIGNATURES,
                                            hier_all_gather, hier_psum)
    P = jax.sharding.PartitionSpec
    mesh = jax.make_mesh((1, 1), ("pod", "lane"))

    def trace(fn):
        sm = jax.shard_map(fn, mesh=mesh, in_specs=P(("pod", "lane")),
                           out_specs=P(("pod", "lane")), check_vma=False)
        return jax.make_jaxpr(sm)(jnp.zeros((1, 8)))

    for strat, expect in REDUCE_SIGNATURES.items():
        jx = trace(lambda x, s=strat: hier_psum(x[0], "pod", "lane", s)[None])
        assert collective_signature(jx) == expect, strat
    for strat, expect in GATHER_SIGNATURES.items():
        jx = trace(lambda x, s=strat:
                   hier_all_gather(x[0], "pod", "lane", s)[None])
        assert collective_signature(jx) == expect, strat


def test_halo_signature_tables_match_operators():
    """Host-side golden: every strategy's DistOperator states the ordered
    signature of the table (the 2×4 *traced* check runs in the subprocess);
    an empty-halo operator states ()."""
    from repro.amg.csr import CSR
    from repro.amg.dist_spmv import build_dist_operator
    from repro.core.nap_collectives import HALO_SIGNATURES
    rng = np.random.default_rng(0)
    n = 96
    band = np.abs(np.subtract.outer(np.arange(n), np.arange(n))) <= 3
    dense = band * rng.normal(size=(n, n))
    r, c = np.nonzero(dense)
    A = CSR.from_coo(r, c, dense[r, c], (n, n))
    for strat, expect in HALO_SIGNATURES.items():
        op = build_dist_operator(A, 2, 4, strat, dtype=np.float64)
        assert not op.halo_empty
        assert op.expected_signature == expect, strat


def test_program_audits_clean_1x1(dh11):
    from repro.amg.solve import SolveOptions
    from repro.analysis import audit_cycle_stats
    for name in ("resid_norm", "vcycle", "pcg_init", "pcg_step_m"):
        a = audit_program(dh11, name)
        assert a.ok, [str(v) for v in a.violations]
        assert a.counts == a.expected
    for cycle in ("V", "W", "F"):
        a = audit_program(dh11, "vcycle", SolveOptions(cycle=cycle))
        assert a.ok, (cycle, [str(v) for v in a.violations])
    for level in range(len(dh11.levels)):
        for op in ("A", "P", "R"):
            if getattr(dh11.levels[level], op) is not None:
                ap = audit_apply(dh11, level, op)
                assert ap.ok and ap.n_collectives == 0, (level, op)
    assert audit_cycle_stats(dh11) == []


def test_injected_flat_psum_detected(monkeypatch):
    """The regression the auditor exists for: hier_psum silently replaced
    by a flat psum passes every runtime-parity gate (same numbers!) but
    must fail the count cross-check on a freshly built hierarchy."""
    jax = pytest.importorskip("jax")
    import repro.amg.dist_solve as ds
    from repro.amg import setup
    from repro.amg.problems import laplace_3d
    monkeypatch.setattr(
        ds, "hier_psum",
        lambda x, slow, fast, strategy="nap3": jax.lax.psum(x, (slow, fast)))
    h = setup(laplace_3d(6), solver="rs", max_coarse=30)
    dh_bad = ds.DistHierarchy.build(h, 1, 1)
    bad = audit_program(dh_bad, "resid_norm")
    assert not bad.ok
    assert any(v.kind == "count-mismatch" for v in bad.violations)
    assert bad.counts.get("psum_scatter", 0) == 0  # the scatter leg vanished
    assert bad.expected["psum_scatter"] >= 1


def test_injected_empty_halo_collective_detected(dh11, monkeypatch):
    """A collective re-introduced on an empty-halo level must be caught:
    forcing the apply down the exchange path while the plan moves nothing
    violates the zero-collective contract."""
    pytest.importorskip("jax")
    from repro.amg.dist_spmv import DistOperator
    assert dh11.levels[0].A.halo_empty          # 1×1: nothing to exchange
    monkeypatch.setattr(DistOperator, "halo_empty",
                        property(lambda self: False))
    a = audit_apply(dh11, 0, "A")
    assert not a.ok
    assert any(v.kind == "empty-halo-collective" for v in a.violations)
    assert a.n_collectives > 0


def test_overlap_independence_taint_sweep():
    """The dataflow check behind the overlapped apply: a contraction feeding
    off the collective's output is serialized; one reading only local data
    is overlappable."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.analysis import check_overlap_independence
    P = jax.sharding.PartitionSpec
    mesh = jax.make_mesh((1,), ("ax",))

    def trace(fn):
        sm = jax.shard_map(fn, mesh=mesh, in_specs=P("ax"), out_specs=P(),
                           check_vma=False)
        return jax.make_jaxpr(sm)(jnp.zeros((8,)))

    def serial(x):
        y = jax.lax.psum(x, "ax")          # exchange ...
        return jnp.sum(y * x)              # ... feeds the only contraction

    def overlapped(x):
        local = jnp.sum(x * x)             # collective-independent
        return local + jnp.sum(jax.lax.psum(x, "ax"))

    assert not check_overlap_independence(trace(serial))
    assert check_overlap_independence(trace(overlapped))


def test_setup_audit_clean_and_tampered():
    import dataclasses
    from repro.amg.dist_setup import dist_setup_partitioned
    from repro.amg.problems import laplace_3d
    plv, recs = dist_setup_partitioned(laplace_3d(6), 2, 2)
    rows, vio = audit_setup(plv, recs)
    assert rows and not vio, [str(v) for v in vio]
    for r in rows:
        assert r["static_inter_msgs"] == r["runtime_inter_msgs"]
        assert r["static_intra_msgs"] == r["runtime_intra_msgs"]
    # a measured counter drifting off the selected schedule must be caught
    bad = [dataclasses.replace(recs[0], inter_msgs=recs[0].inter_msgs + 1)]
    _, vio2 = audit_setup(plv, bad + recs[1:])
    assert any(v.kind == "setup-count-mismatch" for v in vio2)
    # ... as must an exchange that ran a different strategy than cached
    other = "nap3" if recs[0].strategy != "nap3" else "nap2"
    bad2 = [dataclasses.replace(recs[0], strategy=other)]
    _, vio3 = audit_setup(plv, bad2 + recs[1:])
    assert any(v.kind == "strategy-mismatch" for v in vio3)


def test_audit_report_roundtrip(dh11):
    import json
    from repro.analysis import build_report
    a = audit_program(dh11, "resid_norm")
    rep = build_report(audits=[a], meta={"pods": 1, "lanes": 1})
    assert rep["summary"]["ok"]
    assert rep["comm_audit"][0]["counts"] == a.counts
    json.dumps(rep)                                 # fully serializable
    for r in rep["comm_audit"][0]["records"]:
        assert r["primitive"] in ("psum", "psum_scatter", "all_gather",
                                  "all_to_all", "ppermute")
        assert r["bytes"] >= 0 and r["axes"]


# ----------------------------------------------------------- pass 2: lint


def _lint(src):
    return lint_source(textwrap.dedent(src), "mod.py")


def test_lint_async_blocking_bad_coroutine():
    vs = _lint("""
        import time

        async def handler(svc, t):
            x = t.result(timeout=5)
            svc.update_wire(x)
            time.sleep(1)
            return x
        """)
    rules = [v.rule for v in vs]
    assert rules.count("async-blocking") == 3, vs


def test_lint_async_blocking_sanctioned_forms_pass():
    vs = _lint("""
        import asyncio

        async def handler(tenant, payload, t, writer):
            await asyncio.to_thread(tenant.service.update_wire, payload)
            await writer.drain()

            def _resolve():                     # sync scope resets the rule
                return t.result(timeout=0)

            fut = asyncio.get_event_loop().create_future()
            fut.set_result(_resolve())          # set_result is not blocking
            return await fut
        """)
    assert vs == []


def test_lint_raw_collective_and_markers():
    bad = _lint("""
        import jax

        def f(x):
            return jax.lax.psum(x, "ax")
        """)
    assert [v.rule for v in bad] == ["raw-collective"]
    allowed = _lint("""
        import jax

        def f(x):
            return jax.lax.psum(x, "ax")  # comm-audit: allow flat-psum
        """)
    assert allowed == []
    filewide = _lint("""
        # comm-audit: allow-file raw-collective
        import jax

        def f(x):
            return jax.lax.all_gather(x, "ax")
        """)
    assert filewide == []


def test_lint_traced_host_call():
    vs = _lint("""
        import time
        import jax

        def body(x):
            return x * time.time()

        prog = jax.jit(body)

        def host_side():                        # not traced: fine
            return time.perf_counter()
        """)
    assert [v.rule for v in vs] == ["traced-host-call"]
    decorated = _lint("""
        import time
        import jax

        @jax.jit
        def body(x):
            return x * time.perf_counter()
        """)
    assert [v.rule for v in decorated] == ["traced-host-call"]


def test_lint_traced_host_span():
    """A host span or a nanosecond clock inside a traced body would time
    the trace, not the run: flagged under every import form of
    ``repro.amg.spans``; the same calls on the host are fine."""
    vs = _lint("""
        import time
        import jax
        import repro.amg.spans as sp
        from repro.amg import spans
        from repro.amg.spans import span
        from .spans import span as host_span

        def body(x):
            with span("a"):
                x = x + 1
            with spans.span("b"), sp.span("c"), host_span("d"):
                x = x * 2
            return x + time.perf_counter_ns() + time.time_ns()

        prog = jax.jit(body)

        def host_side():                        # not traced: fine
            with span("e"), spans.span("f"):
                return time.perf_counter_ns()
        """)
    assert [v.rule for v in vs] == ["traced-host-call"] * 6, vs
    assert sorted(v.line for v in vs) == [10, 12, 12, 12, 14, 14]


def test_lint_frozen_mutation():
    vs = _lint("""
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class Cfg:
            a: int = 0

            def __post_init__(self):
                object.__setattr__(self, "a", 1)    # allowed here

        def f(c: Cfg):
            c.a = 2
            object.__setattr__(c, "a", 3)
            return dataclasses.replace(c, a=4)      # the sanctioned route

        def g():
            c = Cfg()
            c.a = 5
            return c
        """)
    assert [v.rule for v in vs] == ["frozen-mutation"] * 3, vs


def test_lint_clean_tree():
    """The repo's own src/ carries zero violations (documented exceptions
    are marker-suppressed) — the CI gate for pass 2."""
    assert lint_paths(SRC) == []
