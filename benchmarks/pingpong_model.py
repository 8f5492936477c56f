"""Fig. 8/9 model curves + measured machine calibration.

Two halves:

* :func:`rows` — the original *modeled* curves: single-message cost by
  locality and inter-node max-rate vs active process count, evaluated from
  the documented ``BLUE_WATERS`` constants.
* :func:`measure_machine_params` — the ROADMAP "measured machine models"
  slice: time real ppermute ping-pongs over the mesh's pod (inter) and lane
  (intra) axes across a size sweep, time a local ELL SpMV for the sustained
  flop rate, and calibrate a :class:`~repro.core.perf_model.MachineParams`
  via :meth:`from_measurements`.  The result is registered in
  ``repro.core.MACHINES`` so the overlap-aware selector can run on data
  instead of the documented ``TPU_V5E`` constants
  (:func:`benchmarks.dist_solve.overlap_rows` consumes it).
"""
from __future__ import annotations

import time

from repro.core.perf_model import (BLUE_WATERS, maxrate_internode_time,
                                   single_message_time)


def rows():
    out = []
    for nbytes in (64, 1024, 16384, 262144, 4 << 20):
        for loc in ("socket", "node", "network"):
            t = single_message_time(BLUE_WATERS, nbytes, loc)
            out.append((f"fig8_pingpong_{loc}_{nbytes}B", t * 1e6,
                        f"bytes={nbytes}"))
    total = 4 << 20
    for k in (1, 2, 4, 8, 16):
        t = maxrate_internode_time(BLUE_WATERS, total, k)
        out.append((f"fig9_maxrate_active{k}", t * 1e6,
                    f"total=4MiB,procs={k}"))
    return out


# --------------------------------------------------------------- measurement

_SIZES = (1024, 8192, 65536, 524288)      # bytes per ping-pong message


def _time_fn(fn, *args, reps: int = 5) -> float:
    """Median-of-reps wall time of an already-compiled jitted call."""
    fn(*args)                             # warm (compile outside the clock)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        r = fn(*args)
        try:
            r.block_until_ready()
        except AttributeError:
            pass
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def measure_machine_params(name: str = "measured_mesh",
                           n_pods: int | None = None,
                           lanes: int | None = None,
                           sizes: tuple[int, ...] = _SIZES,
                           reps: int = 5):
    """Measure (bytes, seconds) ping-pong samples per mesh axis + the local
    SpMV flop rate, fit them through ``MachineParams.from_measurements`` and
    register the result under ``name``.

    ``pod``-axis ppermutes cross the slower tier (inter-node in the paper's
    vocabulary, inter-pod DCI on TPU), ``lane``-axis ppermutes stay inside a
    node — the same two tiers the Eq. (2)/(3) models price.  On a
    host-platform mesh both axes ride the same memory fabric, so the fitted
    tiers come out nearly equal; the *shape* of the calibration (postal-model
    lstsq per tier, flop rate for the overlap split) is what the selector
    consumes either way.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.perf_model import MachineParams, register_machine

    if n_pods is None or lanes is None:
        nd = jax.device_count()
        n_pods, lanes = (2, nd // 2) if nd >= 4 and nd % 2 == 0 else (1, nd)
    mesh = jax.make_mesh((n_pods, lanes), ("pod", "lane"))
    spec = jax.sharding.PartitionSpec(("pod", "lane"))
    D = n_pods * lanes

    def axis_samples(axis: str, size: int):
        samples = []
        for nbytes in sizes:
            n = max(nbytes // 4, 1)       # float32 payload

            def body(x):
                perm = [(i, (i + 1) % size) for i in range(size)]
                return jax.lax.ppermute(x[0], axis, perm)[None]

            fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                       out_specs=spec, check_vma=False))
            x = jnp.zeros((D, n), jnp.float32)
            samples.append((float(nbytes), _time_fn(fn, x, reps=reps)))
        return samples

    inter = axis_samples("pod", n_pods) if n_pods > 1 else None
    intra = axis_samples("lane", lanes) if lanes > 1 else None
    # degenerate axes (1 pod / 1 lane) borrow the other tier's samples so
    # the fit stays well-posed on any mesh shape
    inter = inter or intra
    intra = intra or inter
    if inter is None:
        raise RuntimeError("mesh has a single device; nothing to measure")

    # local SpMV flop rate: the inline ELL gather product apply() runs
    rows_l, K = 4096, 16
    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.integers(0, rows_l, size=(rows_l, K)),
                       dtype=jnp.int32)
    vals = jnp.asarray(rng.standard_normal((rows_l, K)), dtype=jnp.float32)
    xv = jnp.asarray(rng.standard_normal(rows_l), dtype=jnp.float32)

    @jax.jit
    def ell(cols, vals, x):
        return (vals * x[cols]).sum(axis=1)

    t_spmv = _time_fn(ell, cols, vals, xv, reps=reps)
    Rf = 2.0 * rows_l * K / max(t_spmv, 1e-12)

    return register_machine(MachineParams.from_measurements(
        name, ppn=lanes, inter=inter, intra=intra, Rf=Rf))


def measured_rows(smoke: bool | None = None):
    """Bench rows for the calibrated machine: fitted α / R_b per tier and
    the measured flop rate (wall-clock-derived — structurally gated only).

    Skipped (empty) on a single-device process — there is no exchange to
    time; the standalone ``benchmarks.dist_solve`` entrypoint forces the
    8-way host mesh and emits the real rows into the committed baseline.
    """
    import jax

    if jax.device_count() < 2:
        return []
    params = measure_machine_params()
    p_i, p_l = params.inter[0], params.intra[0]
    return [
        ("machine_measured_inter", p_i.alpha * 1e6,
         f"machine={params.name};Rb={p_i.Rb:.3e};tier=inter"),
        ("machine_measured_intra", p_l.alpha * 1e6,
         f"machine={params.name};Rb={p_l.Rb:.3e};tier=intra"),
        ("machine_measured_flops", 2.0 / max(params.Rf, 1e-12) * 1e6,
         f"machine={params.name};Rf={params.Rf:.3e}"),
    ]
