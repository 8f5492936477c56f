"""Production serving driver: bring up an engine and drain a request file
or a synthetic workload.

Two engines share this entrypoint:

* ``--solver lm`` (default) — the LM generation ``repro.serve.Engine``::

      PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced

* ``--solver amg`` — the :class:`~repro.amg.api.AMGService`: solve
  requests admitted through tickets, same-(matrix, knobs) right-hand
  sides coalesced into one multi-RHS device trace.  ``--coalesce-window``
  (seconds, > 0) runs the background admission worker so requests
  submitted in separate bursts coalesce; ``--wire`` drives the service
  purely through the versioned wire codec — matrices registered by
  fingerprint from encoded CSR payloads, every request an encoded dict
  passed through an actual JSON byte hop (the codec round-trip proven
  end-to-end)::

      PYTHONPATH=src python -m repro.launch.serve --solver amg --requests 16
      PYTHONPATH=src python -m repro.launch.serve --solver amg --wire \\
          --amg-backend dist --n 10 --coalesce-window 0.2

* ``--solver amg --listen HOST:PORT`` — the AMGWire socket server
  (:class:`~repro.serve.server.AMGWireServer`): multi-tenant admission
  over length-prefixed JSON frames, each ``--tenant
  NAME[:MAX_INFLIGHT[:MAX_MATRIX_BYTES]]`` getting its own service,
  session store and quotas.  Drive it with
  ``benchmarks/serve_load.py``::

      PYTHONPATH=src python -m repro.launch.serve --solver amg \\
          --listen 127.0.0.1:8571 --tenant alpha:32 --tenant beta:2
"""
from __future__ import annotations

import argparse
import time


def run_lm(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..configs import get_arch
    from ..models import init_params
    from ..serve import Engine, Request

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=4, d_model=128, n_heads=4, vocab=1024)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = Engine(cfg, params, max_batch=args.batch,
                 ctx_len=args.prompt_len + args.new_tokens + 8)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for rid in range(args.requests):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                dtype=np.int32),
            max_new_tokens=args.new_tokens,
            temperature=args.temperature))
    out = eng.run()
    dt = time.perf_counter() - t0
    s = eng.stats
    print(f"[serve] {len(out)} requests in {dt:.2f}s; "
          f"decode {s['tokens'] / max(s['decode_s'], 1e-9):.1f} tok/s")


def run_amg(args):
    import numpy as np

    from ..amg.api import AMGConfig, AMGService
    from ..serve.workload import (build_problems, default_tol, make_request,
                                  matrix_payloads, rel_residual)

    tol = default_tol(args.amg_backend, args.tol)
    cfg = AMGConfig(backend=args.amg_backend, n_pods=args.n_pods,
                    lanes=args.lanes, tol=tol)
    svc = AMGService(cfg, max_rhs=args.batch,
                     coalesce_window=args.coalesce_window)
    # the matrix family and request stream are the same construction the
    # open-loop socket load generator (benchmarks/serve_load.py) drives —
    # the two serving harnesses stay honest against each other
    mats = build_problems(args.n)
    if args.wire:
        # wire-only operation: the matrix id IS the verified content
        # fingerprint of the encoded payload (one real JSON byte hop)
        for payload in matrix_payloads(mats).values():
            svc.register_wire(payload)
    else:
        for mid, A in mats.items():
            svc.register(mid, A)
    ids = sorted(mats)
    rng = np.random.default_rng(0)

    def admit(rid):
        mid = ids[rid % len(ids)]
        b, payload = make_request(rng, mats, mid, method=args.method,
                                  rid=rid)
        ticket = (svc.submit_wire(payload) if args.wire
                  else svc.submit(mid, b, method=args.method, rid=rid))
        return mid, b, ticket

    t0 = time.perf_counter()
    admitted = [admit(rid) for rid in range(args.requests)]
    if args.coalesce_window > 0:
        with svc:                       # background admission worker
            out = {t.rid: t.result(timeout=600) for _, _, t in admitted}
    else:
        out = svc.drain()
    dt = time.perf_counter() - t0
    worst = 0.0
    for mid, b, ticket in admitted:
        worst = max(worst, rel_residual(mats[mid], out[ticket.rid], b))
    s = svc.stats
    mode = "wire" if args.wire else "direct"
    print(f"[serve/amg] {len(out)} solves ({len(ids)} matrices, "
          f"backend={args.amg_backend}, {mode}, "
          f"window={args.coalesce_window}s) in {dt:.2f}s: "
          f"{len(out) / dt:.1f} solves/s, {s['batches']} batches "
          f"({s['batched_rhs']} RHS batched, {s['wire_requests']} wire), "
          f"{s['setups']} setups, {s['unconverged']} unconverged, "
          f"worst rel residual {worst:.2e}")
    print("[serve/amg] " + svc.report().summary().replace("\n", "\n[serve/amg] "))
    if worst > tol * 100:
        raise SystemExit(f"residual check failed: {worst:.2e}")


def parse_tenant_spec(spec: str, config, *, max_rhs: int,
                      coalesce_window: float):
    """``NAME[:MAX_INFLIGHT[:MAX_MATRIX_BYTES]]`` -> (name, TenantSpec)."""
    from ..serve import TenantSpec

    name, _, rest = spec.partition(":")
    if not name:
        raise SystemExit(f"--tenant {spec!r}: empty tenant name")
    parts = rest.split(":") if rest else []
    try:
        max_inflight = int(parts[0]) if parts and parts[0] else 32
        max_bytes = (int(parts[1]) if len(parts) > 1 and parts[1]
                     else None)
    except ValueError:
        raise SystemExit(f"--tenant {spec!r}: quotas must be integers "
                         f"(NAME[:MAX_INFLIGHT[:MAX_MATRIX_BYTES]])")
    return name, TenantSpec(config=config, max_inflight=max_inflight,
                            max_matrix_bytes=max_bytes, max_rhs=max_rhs,
                            coalesce_window=coalesce_window)


def run_listen(args):
    import asyncio

    from ..amg.api import AMGConfig
    from ..serve import AMGWireServer
    from ..serve.workload import default_tol

    tol = default_tol(args.amg_backend, args.tol)
    cfg = AMGConfig(backend=args.amg_backend, n_pods=args.n_pods,
                    lanes=args.lanes, tol=tol)
    tenants = dict(
        parse_tenant_spec(spec, cfg, max_rhs=args.batch,
                          coalesce_window=args.coalesce_window)
        for spec in (args.tenant or ["default"]))
    host, _, port = args.listen.rpartition(":")
    server = AMGWireServer(tenants)

    async def _serve():
        h, p = await server.start(host or "127.0.0.1", int(port or 0))
        print(f"[serve/amg] AMGWire listening on {h}:{p} (backend="
              f"{args.amg_backend}, tenants: "
              + ", ".join(f"{n}[inflight<={t.max_inflight}]"
                          for n, t in sorted(tenants.items()))
              + ")", flush=True)
        try:
            await server.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            await server.aclose()

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--solver", choices=("lm", "amg"), default="lm")
    ap.add_argument("--arch", help="LM architecture (required for --solver lm)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    # lm knobs
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # amg knobs
    ap.add_argument("--amg-backend", default="host",
                    help="AMG backend registry name (host | dist)")
    ap.add_argument("--n", type=int, default=8,
                    help="largest Laplacian grid size for --solver amg")
    ap.add_argument("--n-pods", type=int, default=1)
    ap.add_argument("--lanes", type=int, default=1)
    ap.add_argument("--tol", type=float, default=None,
                    help="convergence tolerance (default 1e-8 host, "
                         "1e-6 dist/fp32)")
    ap.add_argument("--method", choices=("solve", "pcg"), default="pcg")
    ap.add_argument("--wire", action="store_true",
                    help="drive the AMG service purely through encoded "
                         "wire payloads (matrices registered by "
                         "fingerprint, requests JSON round-tripped)")
    ap.add_argument("--coalesce-window", type=float, default=0.0,
                    help="seconds the admission worker holds a group open "
                         "to coalesce same-matrix RHS across bursts "
                         "(0 = synchronous drain)")
    ap.add_argument("--listen", metavar="HOST:PORT",
                    help="run the AMGWire socket server instead of the "
                         "in-process harness (--solver amg only); PORT 0 "
                         "picks a free port")
    ap.add_argument("--tenant", action="append", metavar="SPEC",
                    help="tenant spec NAME[:MAX_INFLIGHT[:MAX_MATRIX_"
                         "BYTES]], repeatable (default: one 'default' "
                         "tenant); only with --listen")
    args = ap.parse_args()

    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.solver == "amg":
        if args.listen:
            run_listen(args)
            return
        run_amg(args)
    else:
        if not args.arch:
            raise SystemExit("--solver lm requires --arch")
        run_lm(args)


if __name__ == "__main__":
    main()
