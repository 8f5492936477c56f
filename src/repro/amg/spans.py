"""Host spans: where the program's host time goes, on the profiler's clock.

``with span("amg.setup.galerkin", level=l): ...`` times its block with
``time.perf_counter_ns`` and

* opens a ``jax.profiler.TraceAnnotation`` of the same name once JAX is
  imported, so a running profiler puts the span on the trace's host plane
  beside the chip's operations (with no profiler running it records
  nothing there; before JAX is imported no profiler can run, and the
  host-only setup never imports it);
* keeps the innermost open span of each thread or task in a context
  variable, so each finished span knows its parent and its self time (its
  duration less the time its direct children took);
* appends the finished span to a bounded ring shared by all threads, the
  oldest dropped first.

``with span(...) as attrs:`` hands the block the span's attributes: what
it adds there, such as a choice made inside, reaches the ring, while the
profiler's annotation carries those given at entry.

:func:`recent` returns a copy of the ring, :func:`clear` empties it.  A span
times the host: never open one inside a function that ``jax.jit`` or
``shard_map`` traces, where it would time the trace (the lint's
``traced-host-call`` rule flags it).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import itertools
import sys
import threading
import time

RING_SIZE = 65_536


@dataclasses.dataclass(frozen=True)
class Span:
    id: int
    parent_id: int | None       # the span open around this one, or None
    name: str
    start_ns: int               # time.perf_counter_ns at entry
    end_ns: int
    self_ns: int                # duration less the direct children's
    attrs: dict

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class _Open:
    id: int
    child_ns: int = 0


_ring: collections.deque[Span] = collections.deque(maxlen=RING_SIZE)
_lock = threading.Lock()
_ids = itertools.count(1)
_open: contextvars.ContextVar[tuple[_Open, ...]] = contextvars.ContextVar(
    "amg_open_spans", default=())


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the block as one span called ``name`` (see the module doc)."""
    outer = _open.get()
    me = _Open(next(_ids))
    token = _open.set(outer + (me,))
    jax = sys.modules.get("jax")
    start = time.perf_counter_ns()
    try:
        with (contextlib.nullcontext() if jax is None
              else jax.profiler.TraceAnnotation(name, **attrs)):
            yield attrs
    finally:
        end = time.perf_counter_ns()
        _open.reset(token)
        took = end - start
        with _lock:     # a child may run in a thread that copied the context
            if outer:
                outer[-1].child_ns += took
            _ring.append(Span(me.id, outer[-1].id if outer else None, name,
                              start, end, took - me.child_ns, attrs))


def recent() -> list[Span]:
    """The finished spans still in the ring, oldest first."""
    with _lock:
        return list(_ring)


def clear() -> None:
    with _lock:
        _ring.clear()
