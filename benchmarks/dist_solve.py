"""Solve-phase benchmark: the device-resident fused cycle, standard vs
NAP-2 vs NAP-3 vs model-selected per-level strategies (paper Figs. 16/17's
solve-phase claim, executed rather than simulated), plus a cycle-shape ×
smoother sweep with per-cycle coarse-level message counts
(``cycle_smoother_rows`` — the rows the CI regression gate vets), a
weak-scaling sweep over ≥3 problem sizes (``weak_rows``) and a
cached-vs-cold ``AMGSolver`` session comparison (``session_rows``) showing
the per-call rebuild cost the session API eliminates.  A streaming drift
sweep (``streaming_rows``) pits value-only refreshes against the adaptive
full re-setup that one injected convergence regression triggers.

Emits the ``name,us_per_call,derived`` rows used by :mod:`benchmarks.run`,
and — when run standalone — a ``BENCH_dist_solve.json`` file with the same
rows as structured records:

    PYTHONPATH=src python -m benchmarks.dist_solve [--smoke] [--out PATH]

``--smoke`` (or ``REPRO_BENCH_SMOKE=1``) shrinks the problem and iteration
count so the whole benchmark runs in seconds (the tier-1 smoke test uses it).
Heavy imports are deferred so the standalone entrypoint can force an 8-way
host mesh before JAX initializes.
"""
from __future__ import annotations

import json
import os
import time

STRATEGIES = ("standard", "nap2", "nap3", "auto")


def _mesh_shape(n_devices: int) -> tuple[int, int]:
    if n_devices >= 4 and n_devices % 2 == 0:
        return 2, n_devices // 2
    return 1, n_devices


def rows(smoke: bool | None = None, cycles: int | None = None):
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax

    from repro.amg import setup, solve
    from repro.amg.dist_solve import DistHierarchy
    from repro.amg.problems import laplace_3d
    from repro.core import BLUE_WATERS
    import numpy as np

    n = 8 if smoke else 12
    cycles = cycles or (3 if smoke else 10)
    n_pods, lanes = _mesh_shape(jax.device_count())
    A = laplace_3d(n)
    h = setup(A, solver="rs")
    b = A.matvec(np.ones(A.nrows))
    out = []
    for strat in STRATEGIES:
        kw = ({"params": BLUE_WATERS} if strat == "auto"
              else {"strategy": strat})
        dh = DistHierarchy.build(h, n_pods, lanes, **kw)
        solve(h, b, maxiter=1, tol=0.0, backend="dist", dist=dh)  # compile
        t0 = time.perf_counter()
        res = solve(h, b, maxiter=cycles, tol=0.0, backend="dist", dist=dh)
        dt = time.perf_counter() - t0
        per_level = ";".join(
            f"L{r['level']}.{r['op']}={r['strategy']}"
            for r in dh.selection_table())
        out.append((f"dist_solve_{strat}", dt / cycles * 1e6,
                    f"n={A.nrows};mesh={n_pods}x{lanes};cycles={cycles};"
                    f"conv={res.avg_conv_factor:.3f};{per_level}"))
        if strat == "auto":
            # one row per (level, op): the model-selected strategy + its
            # modeled comm seconds (the quantity the paper's Figs. 14/15
            # plot).  ``us_per_call`` stays a wall-clock-style column (here
            # the modeled phase time, honestly labeled in ``derived`` as
            # modeled_us) so check_bench can gate the field structurally
            # without special-casing these rows.
            for r in dh.selection_table():
                modeled = r["modeled"].get(r["strategy"], 0.0)
                out.append((f"dist_solve_auto_L{r['level']}_{r['op']}",
                            modeled * 1e6,
                            f"strategy={r['strategy']};"
                            f"modeled_us={modeled * 1e6:.3f};"
                            f"level={r['level']};op={r['op']}"))
    return out


def overlap_rows(smoke: bool | None = None):
    """Per-level on/off operator splits.

    The hierarchy is lowered with the *measured* machine parameters
    (:func:`benchmarks.pingpong_model.measure_machine_params`), so the
    overlap-aware selection — max(T_comm, T_on) + T_off — runs on data.
    One ``dist_overlap_L{l}`` row per level records the on/off nnz split and
    the modeled overlap efficiency.
    """
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax

    if jax.device_count() < 2:      # nothing to overlap on one device;
        return []                   # the standalone entrypoint has 8
    from benchmarks.pingpong_model import measure_machine_params
    from repro.amg import setup
    from repro.amg.dist_solve import DistHierarchy
    from repro.amg.problems import laplace_3d
    from repro.core.perf_model import overlap_time

    n = 8 if smoke else 12
    n_pods, lanes = _mesh_shape(jax.device_count())
    params = measure_machine_params(n_pods=n_pods, lanes=lanes)
    h = setup(laplace_3d(n), solver="rs", max_coarse=30)
    dh = DistHierarchy.build(h, n_pods, lanes, params=params)
    out = []
    for l, dl in enumerate(dh.levels):
        oo = dl.onoff
        t_ov = overlap_time(oo["t_comm"], oo["t_on"], oo["t_off"])
        out.append((
            f"dist_overlap_L{l}", t_ov * 1e6,
            f"on_nnz={oo['on_nnz']};off_nnz={oo['off_nnz']};"
            f"local_nnz={oo['local_nnz']};"
            f"halo_empty={int(oo['halo_empty'])};"
            f"eff_modeled={oo['eff_modeled']:.4f};"
            f"strategy={dl.strategies.get('spmv_A', '?')};"
            f"machine={params.name}"))
    return out


def cycle_smoother_rows(smoke: bool | None = None):
    """Cycle-shape × smoother sweep through the fused device program.

    One row per (cycle, smoother) pair on a ≥3-level hierarchy (so W/F
    actually revisit coarse levels): iteration count to tol, convergence
    factor, µs/cycle, and the *modeled per-cycle message counts* split into
    total and coarse-level (ℓ ≥ 1) — the quantity W/F-cycles multiply and
    where the paper's NAP strategies aggregate small inter-node messages.
    ``iters``/``conv`` feed the CI regression gate (scripts/check_bench.py).
    """
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax
    import numpy as np

    from repro.amg import SolveOptions, setup, solve
    from repro.amg.dist_solve import DistHierarchy, cycle_comm_stats
    from repro.amg.problems import laplace_3d
    from repro.amg.solve import CYCLES, SMOOTHERS
    from repro.core import BLUE_WATERS

    n = 8 if smoke else 12
    n_pods, lanes = _mesh_shape(jax.device_count())
    A = laplace_3d(n)
    h = setup(A, solver="rs", max_coarse=30)   # deepen: W/F need ≥3 levels
    b = A.matvec(np.ones(A.nrows))
    dh = DistHierarchy.build(h, n_pods, lanes, params=BLUE_WATERS)
    out = []
    for cycle in CYCLES:
        for sm in SMOOTHERS:
            opts = SolveOptions(cycle=cycle, smoother=sm)
            solve(h, b, maxiter=1, tol=0.0, opts=opts, backend="dist",
                  dist=dh)                     # compile
            t0 = time.perf_counter()
            res = solve(h, b, tol=1e-6, maxiter=40, opts=opts,
                        backend="dist", dist=dh)
            dt = time.perf_counter() - t0
            st = cycle_comm_stats(dh, opts)
            out.append((
                f"dist_cycle_{cycle}_{sm}",
                dt / max(res.iterations, 1) * 1e6,
                f"n={A.nrows};mesh={n_pods}x{lanes};levels={h.n_levels};"
                f"iters={res.iterations};conv={res.avg_conv_factor:.3f};"
                f"inter_msgs={st['inter_msgs']};"
                f"coarse_inter_msgs={st['coarse_inter_msgs']};"
                f"coarse_intra_msgs={st['coarse_intra_msgs']}"))
    return out


def comm_audit_rows(smoke: bool | None = None):
    """Static comm-audit rows: the traced collective counts of the fused
    vcycle per (cycle, smoother) pair vs the counts the cycle structure +
    selected strategies predict, plus the setup-phase static-vs-measured
    SpGEMM exchange counters.  ``us_per_call`` is the audit's own tracing
    wall clock (never gated); the derived fields are what
    ``scripts/check_bench.py`` gates structurally: ``collectives`` ==
    ``expected`` with ``agree=1`` and ``violations=0``, and — for the
    ``comm_audit_setup_L*`` rows — static == runtime message counts."""
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax

    from repro.amg import SolveOptions, setup
    from repro.amg.dist_setup import dist_setup_partitioned
    from repro.amg.dist_solve import DistHierarchy
    from repro.amg.problems import laplace_3d
    from repro.amg.solve import CYCLES, SMOOTHERS
    from repro.analysis import audit_cycle_stats, audit_program, audit_setup
    from repro.core import BLUE_WATERS

    n = 8 if smoke else 12
    n_pods, lanes = _mesh_shape(jax.device_count())
    A = laplace_3d(n)
    h = setup(A, solver="rs", max_coarse=30)
    dh = DistHierarchy.build(h, n_pods, lanes, params=BLUE_WATERS)
    out = []
    for cycle in CYCLES:
        for sm in SMOOTHERS:
            opts = SolveOptions(cycle=cycle, smoother=sm)
            t0 = time.perf_counter()
            a = audit_program(dh, "vcycle", opts)
            stat_v = audit_cycle_stats(dh, opts)
            dt = time.perf_counter() - t0
            n_vio = len(a.violations) + len(stat_v)
            expected = sum((a.expected or {}).values())
            out.append((
                f"comm_audit_{cycle}_{sm}", dt * 1e6,
                f"mesh={n_pods}x{lanes};collectives={a.n_collectives};"
                f"expected={expected};bytes={a.total_bytes};"
                f"agree={int(a.counts == a.expected)};violations={n_vio}"))
    plv, recs = dist_setup_partitioned(A, n_pods, lanes, solver="rs",
                                       max_coarse=30)
    t0 = time.perf_counter()
    audit_rows, vio = audit_setup(plv, recs)
    dt = time.perf_counter() - t0
    for r in audit_rows:
        out.append((
            f"comm_audit_setup_L{r['level']}_{r['op']}",
            dt / max(len(audit_rows), 1) * 1e6,
            f"strategy={r['strategy']};"
            f"static_inter_msgs={r['static_inter_msgs']};"
            f"runtime_inter_msgs={r['runtime_inter_msgs']};"
            f"static_intra_msgs={r['static_intra_msgs']};"
            f"runtime_intra_msgs={r['runtime_intra_msgs']};"
            f"violations={len(vio)}"))
    return out


def weak_rows(smoke: bool | None = None, cycles: int | None = None):
    """Weak-scaling sweep: ≥3 problem sizes through the model-selected
    fused cycle on the same mesh — µs/cycle as DOFs/device grows."""
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax
    import numpy as np

    from repro.amg import setup, solve
    from repro.amg.dist_solve import DistHierarchy
    from repro.amg.problems import laplace_3d
    from repro.core import BLUE_WATERS

    sizes = (6, 8, 10) if smoke else (8, 12, 16)
    cycles = cycles or (3 if smoke else 10)
    n_pods, lanes = _mesh_shape(jax.device_count())
    n_dev = n_pods * lanes
    out = []
    for n in sizes:
        A = laplace_3d(n)
        h = setup(A, solver="rs")
        b = A.matvec(np.ones(A.nrows))
        dh = DistHierarchy.build(h, n_pods, lanes, params=BLUE_WATERS)
        solve(h, b, maxiter=1, tol=0.0, backend="dist", dist=dh)  # compile
        t0 = time.perf_counter()
        res = solve(h, b, maxiter=cycles, tol=0.0, backend="dist", dist=dh)
        dt = time.perf_counter() - t0
        out.append((f"dist_weak_n{A.nrows}", dt / cycles * 1e6,
                    f"mesh={n_pods}x{lanes};dofs_per_dev={A.nrows // n_dev};"
                    f"levels={h.n_levels};conv={res.avg_conv_factor:.3f}"))
    return out


def session_rows(smoke: bool | None = None):
    """Cached vs cold AMGSolver sessions: the cold row pays setup +
    DistHierarchy lowering + program compilation; the cached row shows the
    per-call rebuild cost the session API eliminates."""
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax
    import numpy as np

    from repro.amg.api import AMGConfig, AMGSolver, clear_sessions
    from repro.amg.problems import laplace_3d

    n = 8 if smoke else 12
    cycles = 3 if smoke else 10
    n_pods, lanes = _mesh_shape(jax.device_count())
    A = laplace_3d(n)
    b = A.matvec(np.ones(A.nrows))
    cfg = AMGConfig(backend="dist", n_pods=n_pods, lanes=lanes,
                    machine="blue_waters", tol=0.0, maxiter=cycles)
    clear_sessions()
    t0 = time.perf_counter()
    bound = AMGSolver(cfg).setup(A)       # hierarchy + lowering
    bound.solve(b)                        # + compile + solve
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    bound2 = AMGSolver(cfg).setup(A)      # session-cache hit
    bound2.solve(b)                       # reuses compiled programs
    cached = time.perf_counter() - t0
    assert bound2 is bound, "session cache must return the same bound solver"
    derived = f"n={A.nrows};mesh={n_pods}x{lanes};cycles={cycles}"
    return [("amg_solver_cold", cold * 1e6, derived),
            ("amg_solver_cached", cached * 1e6,
             derived + f";speedup={cold / max(cached, 1e-12):.1f}x")]


def streaming_rows(smoke: bool | None = None):
    """Drift sweep through ONE streaming session: A₀ is solved once (the
    session-cache hit), then a sequence of value-only drifts flows through
    :meth:`AMGService.update` — each refresh replays the Galerkin products
    on the frozen NAP schedules and reuses the compiled fused programs —
    and the final step injects a convergence regression so the adaptive
    full re-setup path is exercised (and timed) deterministically.

    ``streaming_refresh`` records the mean value-only refresh wall clock
    and ``streaming_resetup`` the escalated re-setup wall clock; both carry
    the session counters (``solves == refreshes + resetups + cached``),
    the per-step iteration trajectory and the trigger tallies that
    scripts/check_bench.py gates structurally (refresh must be cheaper
    than re-setup; iteration counts must stay finite)."""
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax
    import numpy as np

    from repro.amg.api import AMGConfig, AMGService, clear_sessions
    from repro.amg.csr import CSR
    from repro.amg.problems import laplace_3d

    n = 8 if smoke else 12
    steps = 4 if smoke else 8
    n_pods, lanes = _mesh_shape(jax.device_count())
    A = laplace_3d(n)
    b = A.matvec(np.ones(A.nrows))
    cfg = AMGConfig(backend="dist", n_pods=n_pods, lanes=lanes,
                    machine="blue_waters", tol=1e-6, maxiter=60)
    clear_sessions()
    svc = AMGService(cfg)
    svc.register("m", A)
    rng = np.random.default_rng(7)

    def drifted(M, scale=0.02):
        # value-only drift on the frozen pattern, resymmetrized so pcg's
        # SPD assumption survives the perturbation
        data = M.data * (1.0 + scale * rng.random(M.nnz))
        Mt = CSR(M.shape, M.indptr.copy(), M.indices.copy(), data).T
        return CSR(M.shape, M.indptr.copy(), M.indices.copy(),
                   0.5 * (data + Mt.data))

    def solve_once() -> int:
        t = svc.submit("m", b, method="pcg")
        svc.drain()
        t.result()
        return int(t.diagnostics["iterations"])

    iters = [solve_once()]      # baseline solve: no update preceded it
    refresh_us: list[float] = []
    resetup_us: list[float] = []
    for step in range(steps):
        A = drifted(A)
        if step == steps - 1:
            # inject a convergence regression: the next update must
            # escalate to a full node-aware re-setup, not a refresh
            bound = svc.bound_for("m")
            bound.last_iterations = 10 * (bound.baseline_iterations or 1) + 100
        t0 = time.perf_counter()
        out = svc.update("m", A)
        # a refresh re-lowers values in-band; a re-setup defers the
        # DistHierarchy lowering to first use — materialize it so both
        # actions are charged their full pre-solve cost
        svc.bound_for("m").dist_hierarchy
        dt = (time.perf_counter() - t0) * 1e6
        (refresh_us if out["action"] == "refresh" else resetup_us).append(dt)
        iters.append(solve_once())
    st = svc.store.stats()
    assert st["refreshes"] == steps - 1 and st["resetups"] == 1, st
    assert all(np.isfinite(i) and 0 <= i <= cfg.maxiter for i in iters), iters
    solves = len(iters)
    cached = solves - st["refreshes"] - st["resetups"]
    mean_refresh = sum(refresh_us) / len(refresh_us)
    triggers = ",".join(f"{k}:{v}" for k, v in sorted(st["triggers"].items()))
    counters = (f"solves={solves};refreshes={st['refreshes']};"
                f"resetups={st['resetups']};cached={cached};"
                f"max_iters={max(iters)};iters={':'.join(map(str, iters))};"
                f"triggers={triggers}")
    timing = (f"refresh_us={mean_refresh:.2f};resetup_us={resetup_us[0]:.2f};"
              f"speedup={resetup_us[0] / max(mean_refresh, 1e-9):.2f}")
    shape = f"n={A.nrows};mesh={n_pods}x{lanes};steps={steps}"
    clear_sessions()
    return [
        ("streaming_refresh", mean_refresh, f"{shape};{counters};{timing}"),
        ("streaming_resetup", resetup_us[0],
         f"{shape};{counters};{timing};trigger=regression(injected)"),
    ]


def serving_rows(smoke: bool | None = None):
    """Serving throughput through :class:`~repro.amg.api.AMGService`:
    solves/s cold (setup + lowering + compile in-band), hot (session-store
    hit, one request per drain) and coalesced (k requests stacked into ONE
    multi-RHS trace), on the host and dist backends.  The ``worst_rel`` /
    ``unconverged`` fields feed the CI gate's presence + divergence check
    (wall-clock derived solves/s stays ungated); ``kernel=`` records which
    local kernel served the row — ``host_csr``, the fine level's layout
    (``ell``/``bcsr``) for single-request dist rows, or the native
    multi-RHS SpMM label (``ell_spmm``/``bcsr_spmm``) for coalesced
    batches."""
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    import jax
    import numpy as np

    from repro.amg.api import AMGConfig, AMGService, AMGSolver, clear_sessions
    from repro.amg.problems import laplace_3d

    n = 8 if smoke else 12
    k = 4 if smoke else 8
    n_pods, lanes = _mesh_shape(jax.device_count())
    A = laplace_3d(n)
    rng = np.random.default_rng(0)
    bs = [rng.standard_normal(A.nrows) for _ in range(k)]
    out = []
    for backend in ("host", "dist"):
        tol = 1e-6 if backend == "dist" else 1e-8
        cfg = AMGConfig(backend=backend,
                        n_pods=n_pods if backend == "dist" else 1,
                        lanes=lanes if backend == "dist" else 1,
                        machine="blue_waters", tol=tol)
        clear_sessions()
        svc = AMGService(cfg, max_rhs=k)
        svc.register("m", A)

        def serving_kernel(multi: bool) -> str:
            """Which local kernel serves a batch on this backend."""
            if backend == "host":
                return "host_csr"
            # session-cache hit: the same bound solver the service drains use
            dh = AMGSolver(cfg).setup(A).dist_hierarchy
            fine = dh.kernel_table()[0]["kernel"]      # 'ell' | 'bcsr'
            if not multi:
                return fine
            return f"{fine}_spmm" if fine == "bcsr" else "ell_spmm"

        def measure(tag, reqs, one_per_drain):
            t0 = time.perf_counter()
            tickets = []
            if one_per_drain:
                for b in reqs:
                    tickets.append(svc.submit("m", b, method="pcg"))
                    svc.drain()
            else:
                tickets = [svc.submit("m", b, method="pcg") for b in reqs]
                svc.drain()
            dt = time.perf_counter() - t0
            worst = max(
                np.linalg.norm(b - A.matvec(t.result())) / np.linalg.norm(b)
                for b, t in zip(reqs, tickets))
            unconv = sum(not t.diagnostics["converged"] for t in tickets)
            kern = serving_kernel(multi=not one_per_drain and len(reqs) > 1)
            return (f"serve_{tag}_{backend}", dt / len(reqs) * 1e6,
                    f"backend={backend};requests={len(reqs)};"
                    f"solves_per_s={len(reqs) / dt:.2f};"
                    f"batches={svc.stats['batches']};kernel={kern};"
                    f"worst_rel={worst:.3e};unconverged={unconv}")

        # cold: ONE request paying setup + lowering + compile in-band
        out.append(measure("cold", bs[:1], one_per_drain=True))
        # hot: k sequential single-request drains against the warm session
        out.append(measure("hot", bs, one_per_drain=True))
        base_batches = svc.stats["batches"]
        # coalesced: the same k requests stacked into ONE multi-RHS trace
        row = measure("coalesced", bs, one_per_drain=False)
        assert svc.stats["batches"] == base_batches + 1, svc.stats
        out.append(row)
    clear_sessions()
    return out


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--out", default="BENCH_dist_solve.json")
    args = parser.parse_args(argv)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        from benchmarks.serve_load import serving_latency_rows
    except ImportError:
        from serve_load import serving_latency_rows
    data = (rows(smoke=args.smoke) + cycle_smoother_rows(smoke=args.smoke)
            + overlap_rows(smoke=args.smoke)
            + comm_audit_rows(smoke=args.smoke)
            + weak_rows(smoke=args.smoke) + session_rows(smoke=args.smoke)
            + streaming_rows(smoke=args.smoke)
            + serving_rows(smoke=args.smoke)
            + serving_latency_rows(smoke=args.smoke))
    print("name,us_per_call,derived")
    for name, us, derived in data:
        print(f"{name},{us:.2f},{derived}")
    with open(args.out, "w") as f:
        json.dump({"benchmark": "dist_solve",
                   "rows": [{"name": n, "us_per_call": u, "derived": d}
                            for n, u, d in data]}, f, indent=2)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
