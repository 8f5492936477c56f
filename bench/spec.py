"""Find a cell's parts by name.

``BENCHMARK.json`` at the root of the checkout names every cell, its
configuration and its traffic mix, and every metric.  Each part sits in a
file of its own under ``bench/``:

* ``bench/configs/<config>.json`` — one deployment: the problem generator
  and its size, the solver session, the precision and the limit the answer
  check holds it to;
* ``bench/traffic/<mix>.json`` — one traffic mix, read by
  :mod:`traffic`;
* ``bench/problems/<generator>.py`` — one matrix generator, named by the
  configuration;
* ``bench/metrics/<metric>.py`` — one per-layer metric: a ``read(run)``
  that returns a number, or ``None`` where the run has nothing to read;
* ``bench/peaks.json`` — the chips' published peaks, keyed by
  ``device_kind``.

Adding a cell, a configuration, a mix or a metric adds files and
``BENCHMARK.json`` entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, configuration, mix or metric that cannot be found or read."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<mix>.json
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]
    root: pathlib.Path


def _load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell called ``name`` in ``root/BENCHMARK.json``, with its parts."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reported_in(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reported_in(m, name)],
                root=root)


def load_module(path: pathlib.Path, name: str):
    """Import one file of the benchmark by path."""
    if not path.is_file():
        raise SpecError(f"missing {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def problem_module(cell: Cell):
    gen = cell.config["problem"]["generator"]
    return load_module(cell.root / "bench" / "problems" / f"{gen}.py",
                       f"bench_problem_{gen}")


def metric_reader(cell: Cell, metric: str):
    """``read(run) -> float | None`` of ``bench/metrics/<metric>.py``."""
    mod = load_module(cell.root / "bench" / "metrics" / f"{metric}.py",
                      "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def peaks_for(kind: str, root: pathlib.Path = ROOT) -> dict:
    """Published peaks of ``kind``; a chip missing from the table is an
    error, never a default."""
    table = _load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise SpecError(f"device_kind {kind!r} is not in bench/peaks.json; "
                        f"known: {sorted(table)}")
    return table[kind]
