"""Every part of a cell is found by name; BENCHMARK.json keeps its form."""
import json
import re

import pytest

import smallroot
import spec
import traffic

BENCHMARK = json.loads((smallroot.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_real_cells_resolve(cell):
    c = spec.load_cell(cell)
    w = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert c.config["name"] == w["config"]
    assert c.chips == w["chips"] == c.config["chips"]
    traffic.check_mix(c.traffic)
    assert spec.problem_module(c).build
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(c, m["name"]))
    session = c.config["session"]
    assert session["n_pods"] * session["lanes"] == c.chips


def test_added_parts_are_found_by_name(tmp_path):
    """A new mix, metric and cell are new files and new entries only."""
    root = smallroot.make_root(tmp_path)
    bench = root / "bench"
    for sub in ("traffic", "metrics"):           # own copies, not links
        real = (bench / sub).resolve()
        (bench / sub).unlink()
        (bench / sub).mkdir()
        for f in real.iterdir():
            if f.is_file():
                (bench / sub / f.name).write_text(f.read_text())
    mix = json.loads((bench / "traffic" / "pcg1.json").read_text())
    (bench / "traffic" / "pcg4.json").write_text(
        json.dumps(dict(mix, columns=4)))
    (bench / "metrics" / "answer_count.py").write_text(
        "def read(run):\n    return 42\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "small.pcg4", "config": "small",
                           "traffic": "pcg4", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "answer_count", "unit": "n",
                           "better": "higher", "source": "program_counter",
                           "layer": "test", "moves": "solves_per_s",
                           "workloads": ["small.pcg4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.load_cell("small.pcg4", root)
    assert c.traffic["columns"] == 4
    assert [m["name"] for m in c.per_layer] == ["answer_count"]
    assert spec.metric_reader(c, "answer_count")(None) == 42
    # the cell that was already there does not see the new metric
    assert "answer_count" not in [
        m["name"] for m in spec.load_cell("small.pcg1", root).per_layer]


def test_unknown_parts_raise(tmp_path):
    root = smallroot.make_root(tmp_path)
    with pytest.raises(spec.SpecError):
        spec.load_cell("nope.pcg1", root)
    c = spec.load_cell("small.pcg1", root)
    with pytest.raises(spec.SpecError):
        spec.metric_reader(c, "no_such_metric")


def test_benchmark_json_form():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench", "tests/bench"]
    assert b["command"] == ["python3", "bench/run.py"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and NAME.match(c["name"])
        assert c["name"] in {w["config"] for w in b["workloads"]}
        conf = json.loads((smallroot.REPO / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
        assert int(conf["fixed_iterations"]) >= 1
        assert 0 < float(conf["session"]["tol"]) < 1
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (smallroot.REPO / "bench" / "traffic"
                / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (smallroot.REPO / "bench" / "metrics"
                / f"{m['name']}.py").is_file()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.add(m["name"])
    assert len(json.dumps(b)) < 64 * 1024
