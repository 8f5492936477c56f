"""A checkout in miniature for running the harness on the CPU in tests.

``make_root(dest)`` writes a ``BENCHMARK.json`` whose cells are the real
cells' configurations at a small grid, beside links to the real traffic
mixes, metric readers, generators and ``src/``, and a peaks table that
names the CPU.  ``on_cpu()`` lets the harness's device check take CPU
devices; nothing outside a test relaxes it.
"""
from __future__ import annotations

import contextlib
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCH = REPO / "bench"
CPU_KIND = "cpu"


def small_config(name: str, n: int, n_pods: int, lanes: int,
                 **session) -> dict:
    real = json.loads((BENCH / "configs" / "hpcg104.json").read_text())
    conf = dict(real, name=name, chips=n_pods * lanes,
                problem={"generator": "laplace_3d", "nx": n, "ny": n,
                         "nz": n})
    conf["session"] = dict(real["session"], n_pods=n_pods, lanes=lanes,
                           **session)
    return conf


def make_root(dest: pathlib.Path, *, n: int = 10, peaks_kind: str = CPU_KIND,
              with_src: bool = True, **session) -> pathlib.Path:
    """``session`` overrides the configurations' solver-session fields
    (``dtype="bfloat16"`` runs the program's lower-precision path)."""
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    (dest / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "metrics", "problems"):
        (dest / "bench" / sub).symlink_to(BENCH / sub)
    if with_src:
        (dest / "src").symlink_to(REPO / "src")
    peaks = {"devices": {peaks_kind: {"hbm_bytes_per_s": 1e11,
                                      "flops_per_s": 1e12,
                                      "source": "test"}}}
    (dest / "bench" / "peaks.json").write_text(json.dumps(peaks))
    configs, cells = [], []
    for name, pods, lanes in (("small", 1, 1), ("small-2x2", 2, 2)):
        path = f"bench/configs/{name}.json"
        (dest / path).write_text(json.dumps(
            small_config(name, n, pods, lanes, **session)))
        configs.append({"name": name, "source": "test", "file": path,
                        "reduced": [], "why": "test"})
        cells.append({"name": f"{name}.pcg1", "config": name,
                      "traffic": "pcg1", "chips": pods * lanes,
                      "why": "test"})
    bench = dict(real, configs=configs, workloads=cells)
    bench["per_layer"] = [dict(m, workloads=[c["name"] for c in cells])
                          for m in real["per_layer"]]
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@contextlib.contextmanager
def on_cpu(run_module):
    """Let ``run_module.check_device`` take the CPU's devices."""
    import jax

    saved = run_module.check_device

    def check_device(chips):
        devs = jax.devices()
        if len(devs) < chips:
            raise run_module.Refused(f"{chips} devices needed")
        return devs

    run_module.check_device = check_device
    try:
        yield
    finally:
        run_module.check_device = saved
