"""From a profiler trace to device busy time, collectives and idle gaps.

A traced run wraps its measured window in ``jax.profiler`` and writes host
spans with ``jax.profiler.TraceAnnotation``: ``bench.window`` around the
window, ``bench.solve`` around each call into the solver and
``bench.stage`` around each right-hand side the caller draws.  The trace
(an ``.xplane.pb``) holds one plane per chip, ``/device:TPU:<i>``, whose
``XLA Ops`` line has one event per operation the chip ran, on the same
clock as the host's spans.

Busy time is the union of a chip's operation intervals; an idle gap is a
stretch of the window that none covers, named by the host span it falls
in.  Operations nest: a ``while`` loop's event spans the operations of its
body, so per-operation time is self time.  Collectives are the operations
whose HLO opcode is all-reduce, all-gather, all-to-all, reduce-scatter or
collective-permute.  Only the ``XLA Ops`` line is read, on which a chip
runs one operation at a time, so no overlap of a collective with other
work can be seen here.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.solve", "bench.stage")
# the HLO opcode of a collective, as it stands before its operand list:
# ``%psum.21 = f32[1]{0} all-reduce(f32[1]{0} %x)``.  An operation's own
# name follows the JAX primitive (``psum``, ``all_to_all``) and an operand
# may be a collective's result (``fusion(... %all-reduce.5)``), so neither
# says what the operation is.
COLLECTIVE = re.compile(
    r"(?<![%\w.-])(?:all-reduce|all-gather|all-to-all|reduce-scatter"
    r"|collective-permute)(?:-start|-done)?\(")


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def intersect(a, b) -> list[tuple[int, int]]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[int, int]]:
    """The parts of sorted disjoint ``a`` that sorted disjoint ``b`` leaves
    uncovered."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Chip:
    index: int
    ops: list[tuple[int, int, str]]     # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    chips: list[Chip]
    spans: list[tuple[int, int, str]]   # the harness's host spans

    def window(self) -> tuple[int, int]:
        found = [(s, e) for s, e, name in self.spans if name == WINDOW_SPAN]
        if len(found) != 1:
            raise ValueError(f"trace holds {len(found)} {WINDOW_SPAN!r} "
                             f"spans, not one")
        return found[0]

    def spans_named(self, name: str) -> list[tuple[int, int]]:
        return union((s, e) for s, e, n in self.spans if n == name)


def read_xplane(path: str | pathlib.Path) -> Trace:
    """The chips' operations and the harness's spans in one trace file
    (``.xplane.pb``, or gzipped ``.xplane.pb.gz``)."""
    import gzip

    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    chips, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = [(int(ev.start_ns), int(ev.end_ns), ev.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            chips.append(Chip(int(m.group(1)), ops))
        elif plane.name == HOST_PLANE:
            spans += [(int(ev.start_ns), int(ev.end_ns), ev.name)
                      for line in plane.lines for ev in line.events
                      if ev.name in (WINDOW_SPAN, *HOST_SPANS)]
    chips.sort(key=lambda c: c.index)
    return Trace(chips, spans)


def find_xplane(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"{log_dir} holds {len(found)} traces, not one")
    return found[0]


@dataclasses.dataclass
class ChipSummary:
    busy_ns: int                 # in the window
    busy_in_solves_ns: int       # inside the bench.solve spans
    collective_ns: int
    op_ns: dict[str, int]        # self time per operation, in the window
    gaps: list[tuple[int, int]]  # idle stretches of the window


@dataclasses.dataclass
class Summary:
    window_ns: int
    chips: list[ChipSummary]
    gap_labels: list[tuple[str, int]]   # (host span, gap ns), longest first

    def mean(self, field: str) -> float:
        return sum(getattr(c, field) for c in self.chips) / len(self.chips)


def nest(ops):
    """Self time of each operation.  On the ops line a control-flow
    operation (a ``while`` loop) spans the operations of its body; those
    are its children.  Returns ``(start, end, name, self_ns)`` in start
    order."""
    out, stack = [], []
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[1]) - s
        out.append([s, e, n, e - s])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def summarize(trace: Trace) -> Summary:
    """Reduce a trace to what the per-layer metrics and the breakdown
    read.  A trace with no chip plane or no operation is an error: the run
    then measured nothing on the device."""
    lo, hi = trace.window()
    solves = trace.spans_named("bench.solve")
    if not trace.chips or not any(c.ops for c in trace.chips):
        raise ValueError("the trace holds no operation on a chip")
    out = []
    for chip in trace.chips:
        ops = nest([(s, e, n) for s, e, n in chip.ops if e > lo and s < hi])
        busy = union(clip([(s, e) for s, e, *_ in ops], lo, hi))
        coll = union(clip([(s, e) for s, e, n, _ in ops
                           if COLLECTIVE.search(n)], lo, hi))
        op_ns: dict[str, int] = {}
        for s, e, n, self_ns in ops:
            inside = min(e, hi) - max(s, lo)
            op_ns[n] = op_ns.get(n, 0) + min(self_ns, inside)
        out.append(ChipSummary(
            busy_ns=total(busy),
            busy_in_solves_ns=total(intersect(busy, solves)),
            collective_ns=total(coll),
            op_ns=op_ns,
            gaps=subtract([(lo, hi)], busy)))
    return Summary(hi - lo, out, label_gaps(out[0].gaps, trace))


def label_gaps(gaps, trace: Trace) -> list[tuple[str, int]]:
    """Each idle gap, named by the host span that holds most of it
    (``between`` where none does), longest first."""
    named = {name: trace.spans_named(name) for name in HOST_SPANS}
    labelled = []
    for s, e in gaps:
        cover = {name: total(intersect([(s, e)], spans))
                 for name, spans in named.items()}
        best = max(cover, key=cover.get)
        labelled.append((best if cover[best] > 0 else "between", e - s))
    return sorted(labelled, key=lambda g: -g[1])


def breakdown(summary: Summary, top: int = 10, width: int = 160) -> dict:
    """The device operations that took most self time (mean over chips),
    each named by the first ``width`` characters of its HLO line, and the
    longest idle gaps of the first chip, each in seconds."""
    op_ns: dict[str, float] = {}
    for chip in summary.chips:
        for name, ns in chip.op_ns.items():
            op_ns[name] = op_ns.get(name, 0) + ns / len(summary.chips)
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[name[:width], ns / 1e9] for name, ns in ops],
            "idle_gaps": [[name, ns / 1e9]
                          for name, ns in summary.gap_labels[:top]]}
