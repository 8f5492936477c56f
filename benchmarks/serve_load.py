"""Open-loop load generator for the AMGWire socket server.

Closed-loop harnesses (``repro.launch.serve --solver amg``) measure the
service at its own pace — every in-flight request throttles the next, so
overload never happens and tail latency is flattered.  This generator is
**open-loop**: arrivals are a Poisson process at a target rate
(exponential inter-arrival draws), fired down N concurrent connections
whether or not earlier requests have completed — the only regime where
admission control, per-tenant quotas and priority-class shedding
actually get exercised.

Every request is built by :mod:`repro.serve.workload` (the same
construction the closed-loop harness uses), tagged (tenant, priority
class) round-robin, and every response is accounted: ``solution`` frames
are residual-validated, ``rejected`` frames counted as shed load,
``error`` frames as failures — anything else is an *unstructured*
response, which ``--check`` treats as fatal.  Latency is measured from
socket send to the client reader thread seeing the response (harvesting
later does not inflate it).

After the load drains, a **streaming epilogue** (:func:`update_round`)
sends one ΔA ``update`` frame per (tenant, matrix) into the sessions the
load left warm — exercising the schema-v2 value-refresh path over real
sockets — and verifies each with a solve that must track the drifted
operator; ``--check`` fails on any update error or stale residual.

Emits ``serving_latency_{tenant}_{class}`` rows (p50/p99/p999 ms,
solves/s, reject rate, accounting) that ``benchmarks/dist_solve.py``
folds into ``BENCH_dist_solve.json`` and ``scripts/check_bench.py``
gates.  Standalone::

    PYTHONPATH=src python -m benchmarks.serve_load --smoke          # self-host
    PYTHONPATH=src python -m benchmarks.serve_load \\
        --connect 127.0.0.1:8571 --tenants alpha,beta --check --expect-reject
"""
from __future__ import annotations

import argparse
import json
import os
import time

PRIORITIES = ("interactive", "batch")
DEFAULT_TENANTS = (("alpha", 32), ("beta", 2))


def build_plan(problems, tenants, requests: int, rate: float, seed: int,
               method: str):
    """The full open-loop schedule, precomputed so the dispatch loop does
    nothing but sleep-and-send: per request an arrival offset (cumulative
    exponential inter-arrivals at ``rate``/s), a (tenant, priority) tag
    (round-robin over the cross product) and an encoded payload."""
    import numpy as np

    from repro.serve.workload import make_request

    rng = np.random.default_rng(seed)
    ids = sorted(problems)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=requests))
    plan = []
    for i in range(requests):
        tenant = tenants[i % len(tenants)]
        prio = PRIORITIES[(i // len(tenants)) % len(PRIORITIES)]
        b, payload = make_request(rng, problems, ids[i % len(ids)],
                                  method=method, priority=prio)
        plan.append({"t": float(arrivals[i]), "tenant": tenant,
                     "priority": prio, "mid": ids[i % len(ids)],
                     "b": b, "payload": payload})
    return plan


def connect_clients(host: str, port: int, count: int, *,
                    retry_s: float = 30.0):
    """N connections, retrying while the server boots (CI starts it in the
    background and races us to the socket)."""
    from repro.serve import AMGWireClient

    clients, deadline = [], time.perf_counter() + retry_s
    while len(clients) < count:
        try:
            clients.append(AMGWireClient.connect(host, port))
        except OSError:
            if time.perf_counter() > deadline:
                for c in clients:
                    c.close()
                raise
            time.sleep(0.2)
    return clients


def run_load(host: str, port: int, problems, plan, connections: int,
             timeout: float = 300.0):
    """Drive the schedule; returns ``(results, makespan_s)`` where each
    result is ``(request, response_frame, latency_s)`` and makespan spans
    first send to last response seen."""
    from repro.serve.workload import matrix_payloads

    clients = connect_clients(host, port, connections)
    try:
        payloads = matrix_payloads(problems)
        for tenant in sorted({p["tenant"] for p in plan}):
            for payload in payloads.values():
                clients[0].register(tenant, payload)
        sent = []
        t0 = time.perf_counter()
        for i, req in enumerate(plan):
            delay = req["t"] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            c = clients[i % len(clients)]
            seq = c.send("solve", tenant=req["tenant"],
                         payload=req["payload"])
            sent.append((c, seq, time.perf_counter(), req))
        results, t_last = [], t0
        for c, seq, t_send, req in sent:
            frame, t_recv = c.recv_timed(seq, timeout)
            results.append((req, frame, t_recv - t_send))
            t_last = max(t_last, t_recv)
        server_stats = clients[0].stats()
    finally:
        for c in clients:
            c.close()
    return results, max(t_last - t0, 1e-9), server_stats


def update_round(host: str, port: int, problems, tenants, *,
                 method: str = "pcg", seed: int = 1):
    """Streaming epilogue to the load: one ΔA ``update`` frame per
    (tenant, matrix) against the sessions the load left warm, each followed
    by a verification solve that must land on the drifted operator.
    Tenants drift independently (each holds its own registered copy of the
    matrix), so validation tracks a per-tenant view of ``problems`` and the
    caller's dict is never mutated.  Returns accounting for ``--check``:
    every update must come back ``updated`` with a refresh or re-setup
    action and every verification residual must track the new values."""
    import numpy as np

    from repro.serve.workload import make_request, make_update, rel_residual

    acct = {"updates": 0, "refresh": 0, "resetup": 0, "failures": []}
    client = connect_clients(host, port, 1)[0]
    try:
        for tenant in tenants:
            rng = np.random.default_rng(seed)
            live = dict(problems)          # this tenant's drifted view
            for mid in sorted(live):
                payload = make_update(rng, live, mid)
                try:
                    frame = client.update(tenant, payload)
                except Exception as exc:
                    acct["failures"].append(
                        f"{tenant}/{mid[:12]}: update frame failed: {exc}")
                    continue
                acct["updates"] += 1
                action = frame.get("action")
                if action in ("refresh", "resetup"):
                    acct[action] += 1
                else:
                    acct["failures"].append(
                        f"{tenant}/{mid[:12]}: unexpected update action "
                        f"{action!r} in {frame}")
                b, spay = make_request(rng, live, mid, method=method)
                try:
                    x, _diag = client.solve(tenant, spay)
                except Exception as exc:
                    acct["failures"].append(
                        f"{tenant}/{mid[:12]}: post-update solve failed: "
                        f"{exc}")
                    continue
                rel = rel_residual(live[mid], x, b)
                if not (np.isfinite(rel) and rel < 1e-4):
                    acct["failures"].append(
                        f"{tenant}/{mid[:12]}: post-update residual "
                        f"{rel:.3e} does not track the drifted operator")
    finally:
        client.close()
    return acct


def aggregate(results, problems, validate: bool = True):
    """Per-(tenant, priority) accounting; ``unstructured`` collects any
    response that is not a solution/rejected/error frame (must stay
    empty)."""
    from repro.amg.api import array_from_wire
    from repro.serve.workload import rel_residual

    classes, unstructured = {}, []
    for req, frame, lat in results:
        key = (req["tenant"], req["priority"])
        cs = classes.setdefault(key, {
            "offered": 0, "completed": 0, "rejected": 0, "errors": 0,
            "unconverged": 0, "latencies": [], "worst_rel": 0.0})
        cs["offered"] += 1
        kind = frame.get("kind")
        if kind == "solution":
            cs["completed"] += 1
            cs["latencies"].append(lat)
            diag = frame.get("diagnostics") or {}
            if not diag.get("converged", True):
                cs["unconverged"] += 1
            if validate:
                x = array_from_wire(frame["x"])
                cs["worst_rel"] = max(cs["worst_rel"], rel_residual(
                    problems[req["mid"]], x, req["b"]))
        elif kind == "rejected":
            cs["rejected"] += 1
        elif kind == "error":
            cs["errors"] += 1
        else:
            unstructured.append(frame)
    return classes, unstructured


def _class_row(name: str, cs: dict, makespan: float):
    from repro.serve.workload import summarize_latencies

    lat = summarize_latencies(cs["latencies"])
    reject_rate = cs["rejected"] / max(cs["offered"], 1)
    derived = (f"offered={cs['offered']};completed={cs['completed']};"
               f"rejected={cs['rejected']};errors={cs['errors']};"
               f"reject_rate={reject_rate:.4f};"
               f"solves_per_s={cs['completed'] / makespan:.2f}")
    if lat:
        derived += (f";p50_ms={lat['p50_ms']:.3f}"
                    f";p99_ms={lat['p99_ms']:.3f}"
                    f";p999_ms={lat['p999_ms']:.3f}")
    if cs["completed"]:
        derived += (f";worst_rel={cs['worst_rel']:.3e}"
                    f";unconverged={cs['unconverged']}")
    return (name, lat.get("p50_ms", 0.0) * 1e3, derived)


def rows_from_results(results, problems, makespan: float,
                      validate: bool = True):
    """BENCH rows: one ``serving_latency_{tenant}_{priority}`` per class
    plus the ``serving_latency_total`` aggregate.  ``us_per_call`` is the
    class's p50 latency (0 for a fully-shed class, which has no latency
    distribution)."""
    classes, unstructured = aggregate(results, problems, validate)
    rows = []
    total = {"offered": 0, "completed": 0, "rejected": 0, "errors": 0,
             "unconverged": 0, "latencies": [], "worst_rel": 0.0}
    for (tenant, prio) in sorted(classes):
        cs = classes[(tenant, prio)]
        for k in ("offered", "completed", "rejected", "errors",
                  "unconverged"):
            total[k] += cs[k]
        total["latencies"] += cs["latencies"]
        total["worst_rel"] = max(total["worst_rel"], cs["worst_rel"])
        rows.append(_class_row(f"serving_latency_{tenant}_{prio}", cs,
                               makespan))
    rows.append(_class_row("serving_latency_total", total, makespan))
    return rows, classes, unstructured


def print_table(classes, makespan: float) -> None:
    from repro.serve.workload import summarize_latencies

    head = (f"{'tenant':<8} {'class':<12} {'offered':>7} {'ok':>6} "
            f"{'rej':>6} {'err':>5} {'rej%':>6} {'sol/s':>8} "
            f"{'p50ms':>8} {'p99ms':>8} {'p999ms':>8}")
    print(head)
    print("-" * len(head))
    for (tenant, prio) in sorted(classes):
        cs = classes[(tenant, prio)]
        lat = summarize_latencies(cs["latencies"])
        print(f"{tenant:<8} {prio:<12} {cs['offered']:>7} "
              f"{cs['completed']:>6} {cs['rejected']:>6} "
              f"{cs['errors']:>5} "
              f"{100 * cs['rejected'] / max(cs['offered'], 1):>5.1f}% "
              f"{cs['completed'] / makespan:>8.1f} "
              f"{lat.get('p50_ms', float('nan')):>8.2f} "
              f"{lat.get('p99_ms', float('nan')):>8.2f} "
              f"{lat.get('p999_ms', float('nan')):>8.2f}")


def serving_latency_rows(smoke: bool | None = None):
    """Self-hosted load run for the BENCH baseline: two tenants ("alpha"
    roomy, "beta" starved at ``max_inflight=2`` so overload sheds its
    batch class first), Poisson arrivals over 32 connections, host
    backend (deterministic, no accelerator dependency)."""
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    from repro.amg.api import AMGConfig
    from repro.serve import ServerThread, TenantSpec
    from repro.serve.workload import build_problems, default_tol

    n = 6 if smoke else 8
    requests = 240 if smoke else 2000
    rate = 300.0 if smoke else 600.0
    cfg = AMGConfig(backend="host", tol=default_tol("host"))
    tenants = {name: TenantSpec(config=cfg, max_inflight=quota)
               for name, quota in DEFAULT_TENANTS}
    problems = build_problems(n)
    plan = build_plan(problems, [t for t, _ in DEFAULT_TENANTS], requests,
                      rate, seed=0, method="pcg")
    with ServerThread(tenants) as srv:
        results, makespan, server_stats = run_load(
            srv.host, srv.port, problems, plan, connections=32)
    rows, classes, unstructured = rows_from_results(results, problems,
                                                    makespan)
    if unstructured:
        rows.append(("serving_latency_ERROR", 0.0,
                     f"unstructured_responses={len(unstructured)}"))
    dropped = server_stats.get("dropped_connections", 0)
    if dropped:
        rows.append(("serving_latency_ERROR", 0.0,
                     f"dropped_connections={dropped}"))
    return rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--connect", metavar="HOST:PORT",
                        help="target an already-running AMGWire server "
                             "(default: self-host one on a free port)")
    parser.add_argument("--tenants", default="alpha:32,beta:2",
                        help="comma-separated NAME[:MAX_INFLIGHT] list "
                             "(quotas apply when self-hosting)")
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--rate", type=float, default=None,
                        help="target Poisson arrival rate, requests/s")
    parser.add_argument("--connections", type=int, default=32)
    parser.add_argument("--n", type=int, default=None,
                        help="largest Laplacian grid size")
    parser.add_argument("--method", choices=("solve", "pcg"),
                        default="pcg")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--smoke", action="store_true",
                        help="small problem + short schedule")
    parser.add_argument("--out", help="write BENCH-style json rows here")
    parser.add_argument("--check", action="store_true",
                        help="exit non-zero on unstructured responses, "
                             "dropped connections or inconsistent "
                             "accounting (CI smoke gate)")
    parser.add_argument("--expect-reject", action="store_true",
                        help="with --check: require at least one "
                             "rejected frame (proves shedding engaged)")
    args = parser.parse_args(argv)

    from repro.amg.api import AMGConfig
    from repro.serve.workload import build_problems, default_tol

    smoke = args.smoke or os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    n = args.n if args.n is not None else (6 if smoke else 8)
    requests = args.requests if args.requests is not None else (
        240 if smoke else 2000)
    rate = args.rate if args.rate is not None else (
        300.0 if smoke else 600.0)
    tenant_specs = []
    for part in args.tenants.split(","):
        name, _, quota = part.strip().partition(":")
        tenant_specs.append((name, int(quota) if quota else 32))
    problems = build_problems(n)
    plan = build_plan(problems, [t for t, _ in tenant_specs], requests,
                      rate, args.seed, args.method)

    srv_cm = None
    if args.connect:
        host, _, port = args.connect.rpartition(":")
        host, port = host or "127.0.0.1", int(port)
    else:
        from repro.launch.compile_cache import enable_compile_cache
        from repro.serve import ServerThread, TenantSpec

        enable_compile_cache()
        cfg = AMGConfig(backend="host", tol=default_tol("host"))
        srv_cm = ServerThread({name: TenantSpec(config=cfg,
                                                max_inflight=quota)
                               for name, quota in tenant_specs})
        srv_cm.__enter__()
        host, port = srv_cm.host, srv_cm.port
    try:
        results, makespan, server_stats = run_load(
            host, port, problems, plan, connections=args.connections)
        # streaming epilogue: ΔA update frames against the warm sessions,
        # each verified by a solve on the drifted operator (never mutates
        # ``problems`` — the main load's validation below stays exact)
        upd = update_round(host, port, problems,
                           [t for t, _ in tenant_specs],
                           method=args.method, seed=args.seed + 1)
    finally:
        if srv_cm is not None:
            srv_cm.__exit__(None, None, None)

    rows, classes, unstructured = rows_from_results(results, problems,
                                                    makespan)
    total = sum(cs["completed"] for cs in classes.values())
    rejected = sum(cs["rejected"] for cs in classes.values())
    print(f"[serve_load] {len(plan)} requests over "
          f"{args.connections} connections at {rate:.0f}/s target: "
          f"{total} completed ({total / makespan:.1f} solves/s), "
          f"{rejected} rejected, makespan {makespan:.2f}s")
    print(f"[serve_load] streaming epilogue: {upd['updates']} update "
          f"frames ({upd['refresh']} refresh, {upd['resetup']} resetup), "
          f"{len(upd['failures'])} failures")
    print_table(classes, makespan)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"benchmark": "serve_load",
                       "rows": [{"name": nm, "us_per_call": us,
                                 "derived": d} for nm, us, d in rows]},
                      f, indent=2)
        print(f"# wrote {args.out}")

    failures = []
    failures.extend(upd["failures"])
    if upd["updates"] == 0:
        failures.append("streaming epilogue sent no update frames")
    if unstructured:
        failures.append(f"{len(unstructured)} unstructured responses: "
                        f"{unstructured[:3]}")
    dropped = server_stats.get("dropped_connections")
    if dropped:
        failures.append(f"{dropped} server-side dropped connections")
    for key, cs in sorted(classes.items()):
        if cs["completed"] + cs["rejected"] + cs["errors"] != cs["offered"]:
            failures.append(f"{key}: accounting mismatch {cs}")
        if cs["errors"]:
            failures.append(f"{key}: {cs['errors']} error frames")
        if cs["completed"] and cs["worst_rel"] > 1e-4:
            failures.append(f"{key}: worst rel residual "
                            f"{cs['worst_rel']:.3e}")
    if args.expect_reject and rejected == 0:
        failures.append("expected at least one rejected frame; the "
                        "schedule never overloaded admission")
    if args.check and failures:
        for fail in failures:
            print(f"[serve_load] CHECK FAILED: {fail}")
        return 1
    if failures:
        for fail in failures:
            print(f"[serve_load] warning: {fail}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
