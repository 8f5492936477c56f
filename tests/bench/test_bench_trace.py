"""From a trace to busy time, collectives, gaps and the per-layer
metrics: on a hand-made trace, and on small traces recorded on a v5e."""
import json
import pathlib
import types

import pytest

import leastbytes
import spec
import devicetrace as t

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = sorted(p.name.split(".")[0] for p in DATA.glob("*.expected.json"))


def test_interval_arithmetic():
    assert t.union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) == [
        (0, 4), (5, 10)]
    assert t.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert t.subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert t.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert t.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def _hand_trace():
    ops = [(0, 100, "%while.1 = (f32[8]) while(f32[8] %p)"),
           (10, 40, "%fusion.2 = f32[8] fusion(f32[8] %a)"),
           (50, 90, "%fusion.3 = f32[8] fusion(f32[8] %b)"),
           (120, 150, "%all-reduce.4 = f32[] all-reduce(f32[] %c)"),
           (140, 160, "%fusion.5 = f32[8] fusion(f32[] %all-reduce.4)"),
           (200, 210, "%psum.6 = f32[8] all-reduce(f32[8] %x)"),
           (220, 300, "%fusion.7 = f32[8] fusion(f32[8] %d)")]
    spans = [(0, 320, "bench.window"), (0, 162, "bench.solve"),
             (162, 198, "bench.stage"), (198, 215, "bench.solve")]
    return t.Trace([t.Chip(0, ops)], spans)


def test_hand_trace_by_hand():
    s = t.summarize(_hand_trace())
    c = s.chips[0]
    assert s.window_ns == 320
    assert c.busy_ns == 100 + 40 + 10 + 80
    assert c.busy_in_solves_ns == 100 + 40 + 10     # fusion.7 is after
    # by opcode: fusion.5 reads an all-reduce but is none; psum.6 is one
    assert c.collective_ns == 30 + 10
    assert c.op_ns["%while.1 = (f32[8]) while(f32[8] %p)"] == 100 - 30 - 40
    assert c.gaps == [(100, 120), (160, 200), (210, 220), (300, 320)]
    assert s.gap_labels == [("bench.stage", 40), ("bench.solve", 20),
                            ("between", 20), ("bench.solve", 10)]
    b = t.breakdown(s, top=2)
    assert b["device_ops"] == [["%fusion.7 = f32[8] fusion(f32[8] %d)",
                                80e-9],
                               ["%fusion.3 = f32[8] fusion(f32[8] %b)",
                                40e-9]]
    assert b["idle_gaps"] == [["bench.stage", 40e-9], ["bench.solve", 20e-9]]


def test_trace_without_chip_operations_is_refused():
    empty = t.Trace([t.Chip(0, [])], [(0, 10, "bench.window")])
    with pytest.raises(ValueError):
        t.summarize(empty)
    with pytest.raises(ValueError):
        t.summarize(t.Trace([t.Chip(0, [(0, 1, "x")])], []))


def _run(expected, summary, chips=None):
    chips = chips or expected.get("chips_of_cell", 1)
    return types.SimpleNamespace(
        trace=summary, iterations=expected["iterations"],
        work=leastbytes.Work(**expected["work"]), peaks=expected["peaks"],
        cell=types.SimpleNamespace(chips=chips))


def _reader(name):
    return spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py",
                            name).read


def test_readers_on_the_hand_trace():
    s = t.summarize(_hand_trace())
    r = _run({"iterations": [2, 3], "work": {"bytes": 819.0, "flops": 0.0},
              "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 1e12}}, s)
    assert _reader("iter_device_ms")(r) == pytest.approx(150 / 5 / 1e6)
    assert _reader("device_idle")(r) == pytest.approx(100 * (1 - 230 / 320))
    assert _reader("collective_ms")(r) == pytest.approx(40 / 5 / 1e6)
    # least time: (2 + 1) + (3 + 1) inits and iterations of 1 ns each
    assert _reader("pcg_iter_roofline")(r) == pytest.approx(100 * 7 / 150)


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_chip_trace(name):
    expected = json.loads((DATA / f"{name}.expected.json").read_text())
    s = t.summarize(t.read_xplane(DATA / f"{name}.xplane.pb.gz"))
    assert s.window_ns == expected["window_ns"]
    got = [{"busy_ns": c.busy_ns, "busy_in_solves_ns": c.busy_in_solves_ns,
            "collective_ns": c.collective_ns,
            "ops": len(c.op_ns), "gaps": len(c.gaps)} for c in s.chips]
    assert got == expected["chips"]
    assert t.breakdown(s) == expected["breakdown"]
    assert 0 < s.mean("busy_ns") <= s.window_ns
    r = _run(expected, s)
    for metric, value in expected.get("metrics", {}).items():
        assert _reader(metric)(r) == value, metric
        assert value is None or value <= 100 or not metric.endswith(
            "roofline")


def test_roofline_is_taken_per_chip():
    """Each of a cell's chips does a share of the whole system's least
    work, so the share of one chip's roofline falls with the chips the
    same busy time is spread over."""
    expected = json.loads((DATA / "small-2x2.expected.json").read_text())
    assert expected["chips_of_cell"] == 4
    s = t.summarize(t.read_xplane(DATA / "small-2x2.xplane.pb.gz"))
    read = _reader("pcg_iter_roofline")
    shares = [read(_run(expected, s, chips)) for chips in (1, 2, 4)]
    assert shares[0] == pytest.approx(2 * shares[1]) == pytest.approx(
        4 * shares[2])
    assert shares[2] == expected["metrics"]["pcg_iter_roofline"]


def test_a_recorded_trace_is_committed():
    assert RECORDED, "tests/bench/data holds no recorded chip trace"
