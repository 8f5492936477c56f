"""Multi-device shard_map validation — run as a SUBPROCESS by
test_nap_collectives.py (device count must be set before jax init; the main
pytest process keeps 1 device).

Prints "OK <check>" per passing check; any exception fails the run.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import CommGraph, Partition, Topology  # noqa: E402
from repro.core.nap_collectives import (build_halo_plan, halo_exchange,  # noqa: E402
                                        hier_all_gather, hier_all_to_all,
                                        hier_psum)
from repro.amg.dist_spmv import build_dist_spmv  # noqa: E402
from repro.amg.problems import laplace_3d_7pt, laplace_3d  # noqa: E402

N_PODS, LANES = 2, 4
mesh = jax.make_mesh((N_PODS, LANES), ("pod", "lane"))
DEV = P(("pod", "lane"))




def shmap(f, n_in, out_specs=DEV):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(DEV,) * n_in,
                                 out_specs=out_specs, check_vma=False))


def check_hier_psum():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 37)).astype(np.float32)  # odd size -> padding

    for strat in ("flat", "nap3"):
        f = shmap(lambda a, s=strat: hier_psum(a[0], "pod", "lane", s)[None], 1)
        out = np.asarray(f(x))
        expect = x.sum(axis=0)
        for d in range(8):
            np.testing.assert_allclose(out[d], expect, rtol=1e-5)
    print("OK hier_psum")


def check_hier_all_gather():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((8, 5)).astype(np.float32)
    for strat in ("flat", "nap3"):
        f = shmap(lambda a, s=strat: hier_all_gather(a[0], "pod", "lane", s)[None], 1,
                  out_specs=DEV)
        out = np.asarray(f(x))
        for d in range(8):
            np.testing.assert_allclose(out[d], x.reshape(-1), rtol=1e-6)
    print("OK hier_all_gather")


def check_hier_all_to_all():
    # chunk (src d -> dst e) carries value 100*d + e
    D = 8
    x = np.zeros((D, D, 3), dtype=np.float32)
    for d in range(D):
        for e in range(D):
            x[d, e] = 100 * d + e
    for strat in ("flat", "nap3"):
        f = shmap(lambda a, s=strat: hier_all_to_all(a[0], "pod", "lane", s)[None], 1)
        out = np.asarray(f(x))
        for e in range(D):
            for d in range(D):
                assert (out[e, d] == 100 * d + e).all(), (strat, e, d, out[e, d])
    print("OK hier_all_to_all")


def check_halo_exchange():
    rng = np.random.default_rng(2)
    topo = Topology(n_nodes=N_PODS, ppn=LANES)
    n = 103
    part = Partition.balanced(n, topo)
    need = []
    for q in range(topo.n_procs):
        lo, hi = part.local_range(q)
        cand = np.setdiff1d(np.arange(n), np.arange(lo, hi))
        need.append(np.sort(rng.choice(cand, size=17, replace=False)))
    g = CommGraph.from_offproc_columns(part, need)
    x = rng.standard_normal(n).astype(np.float32)
    x_dev = np.zeros((8, part.max_local_size), dtype=np.float32)
    for d in range(8):
        lo, hi = part.local_range(d)
        x_dev[d, : hi - lo] = x[lo:hi]
    for strat in ("standard", "nap2", "nap3"):
        plan = build_halo_plan(g, N_PODS, LANES, strat)
        psel = plan.pool_sel if plan.pool_sel is not None else np.zeros(
            (8, 1), np.int32)

        def body(xl, si, rs, ps, plan=plan):
            ps_ = None if plan.pool_sel is None else ps[0]
            return halo_exchange(xl[0], plan, si[0], rs[0], ps_)[None]

        f = shmap(body, 4)
        halo = np.asarray(f(x_dev, plan.send_idx, plan.recv_sel, psel))
        for d in range(8):
            expect = x[np.sort(need[d])]
            np.testing.assert_allclose(halo[d, : expect.size], expect, rtol=1e-6,
                                       err_msg=f"{strat} dev {d}")
    print("OK halo_exchange")


def check_dist_spmv():
    A = laplace_3d_7pt(6)  # 216 rows over 8 devices
    rng = np.random.default_rng(3)
    x = rng.standard_normal(A.nrows)
    y_ref = A.matvec(x)
    for strat in ("standard", "nap2", "nap3"):
        sp = build_dist_spmv(A, N_PODS, LANES, strat, mesh=mesh)
        y = sp.matvec(x)
        np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
    print("OK dist_spmv")


def check_collective_bytes_ordering():
    """Lowered HLO: nap3 halo exchange moves fewer bytes across the pod axis
    than standard (dedup), and uses fewer pod-crossing collectives."""
    from repro.launch.roofline import collective_bytes_from_text
    A = laplace_3d(6)
    stats = {}
    for strat in ("standard", "nap2", "nap3"):
        sp = build_dist_spmv(A, N_PODS, LANES, strat, mesh=mesh)
        x = sp.scatter_x(np.ones(A.nrows))
        lowered = jax.jit(sp.fn).lower(x)
        txt = lowered.compile().as_text()
        stats[strat] = collective_bytes_from_text(txt, pod_size=LANES, n_devices=8)
    # cross-pod collective bytes: nap3 <= nap2 <= standard
    s = {k: v["cross_slow_bytes"] for k, v in stats.items()}
    assert s["nap3"] <= s["nap2"] <= s["standard"], s
    print("OK collective_bytes_ordering", s)


def check_grad_sync():
    from repro.train.grad_sync import hier_grad_sync, init_error_feedback
    rng = np.random.default_rng(4)
    # per-device gradient trees (leading dim 8 = device axis)
    g1 = rng.standard_normal((8, 33)).astype(np.float32)
    g2 = rng.standard_normal((8, 5, 7)).astype(np.float32)
    expect1, expect2 = g1.mean(0), g2.mean(0)

    def body(a, b, strat, compress):
        grads = {"a": a[0], "b": b[0]}
        ef = init_error_feedback(grads, LANES) if compress else None
        synced, _ = hier_grad_sync(grads, "pod", "lane", strat,
                                   compress_slow=compress, error_feedback=ef)
        return synced["a"][None], synced["b"][None]

    for strat, compress, tol in (("flat", False, 1e-5), ("nap3", False, 1e-5),
                                 ("nap3", True, 3e-2)):
        f = shmap(lambda a, b, s=strat, c=compress: body(a, b, s, c), 2,
                  out_specs=(DEV, DEV))
        o1, o2 = f(g1, g2)
        for d in range(8):
            np.testing.assert_allclose(np.asarray(o1)[d], expect1, atol=tol)
            np.testing.assert_allclose(np.asarray(o2)[d], expect2, atol=tol)
    # error feedback: repeated syncs of the SAME gradient average out the
    # quantization error (residual is re-injected)
    def body_ef(a):
        grads = {"a": a[0]}
        ef = init_error_feedback(grads, LANES)
        total = jnp.zeros((33,), jnp.float32)
        for _ in range(8):
            synced, ef = hier_grad_sync(grads, "pod", "lane", "nap3",
                                        compress_slow=True, error_feedback=ef)
            total = total + synced["a"]
        return (total / 8.0)[None]
    f = shmap(body_ef, 1)
    avg = np.asarray(f(g1))[0]
    np.testing.assert_allclose(avg, expect1, atol=5e-3)  # tighter than 1 shot
    print("OK grad_sync")


if __name__ == "__main__":
    check_grad_sync()
    check_hier_psum()
    check_hier_all_gather()
    check_hier_all_to_all()
    check_halo_exchange()
    check_dist_spmv()
    try:
        check_collective_bytes_ordering()
    except ImportError:
        print("SKIP collective_bytes_ordering (roofline module not built yet)")
    print("ALL_OK")
