"""The one generator every traffic mix is read by.

A mix (``bench/traffic/<mix>.json``) holds only parameters:

* ``loop`` — ``"closed"``: one caller sends its next right-hand side only
  after the answer to the last one is on the host (the only loop so far);
* ``method`` — the session method each request calls (``"pcg"``);
* ``columns`` — right-hand sides per request (1, or k for a block);
* ``rhs`` — how each right-hand side is drawn (``"standard_normal"``);
* ``x0`` — the initial guess (``"zero"``).

How many iterations a request runs is the configuration's, not the mix's
(``fixed_iterations`` in ``bench/configs/<config>.json``): exactly that
many, with no early stop, HPCG's rule of a fixed iteration count per set.
So every seed asks the same work of the chip; how many iterations a random
right-hand side needs to reach a tolerance varies from seed to seed, and a
window holds only a few solves.  The answer check then holds every answer
to the configuration's tolerance.

Right-hand side ``i`` of a run is drawn from ``(seed, i)`` alone, in the
session's staging precision (float32), so the same seed gives the same
inputs in the same order and the answer check reads exactly what the
solver was given.

**The window.**  Requests run back to back from the window's start.  A
request starts inside the window when it starts before ``seconds`` have
passed; the window ends when the last such request returns.  Every
request that started inside it is counted and checked.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import numpy as np

# stream tags: the window's right-hand sides and the warm-up's never meet
WINDOW, WARMUP = 0, 1


@dataclasses.dataclass
class Request:
    index: int
    start_s: float      # call into the solver, from the window's start
    end_s: float        # answer on the host, from the window's start
    b: np.ndarray
    result: object      # what the solver returned

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


@dataclasses.dataclass
class Window:
    requests: list[Request]
    seconds: float      # from the start to the return of the last request


def check_mix(mix: dict) -> None:
    want = {"loop": ("closed",), "rhs": ("standard_normal",),
            "x0": ("zero",)}
    for key, allowed in want.items():
        if mix.get(key) not in allowed:
            raise ValueError(f"traffic {key}={mix.get(key)!r}: this "
                             f"generator knows {allowed}")
    if int(mix.get("columns", 1)) < 1:
        raise ValueError("traffic columns must be >= 1")


def request_solver(mix: dict, bound, iterations: int):
    """The call one request makes: the session's ``method`` for exactly
    ``iterations`` iterations (a tolerance of 0 never stops early)."""
    return functools.partial(getattr(bound, mix["method"]), tol=0.0,
                             maxiter=int(iterations))


def draw_rhs(mix: dict, n: int, seed: int, index: int,
             stream: int = WINDOW) -> np.ndarray:
    """Right-hand side ``index`` of a run seeded ``seed``: [n] or [n, k]."""
    rng = np.random.default_rng([abs(int(seed)), stream, index])
    k = int(mix.get("columns", 1))
    shape = (n,) if k == 1 else (n, k)
    return rng.standard_normal(shape, dtype=np.float32)


def closed_loop(solve, mix: dict, n: int, seed: int, seconds: float, *,
                clock=time.perf_counter,
                span=lambda name: contextlib.nullcontext()) -> Window:
    """Run ``solve(b)`` back to back under the window rule above.

    ``span(name)`` wraps the host's work so a traced run can tell where the
    host was: ``bench.stage`` draws a right-hand side, ``bench.solve`` is
    one call into the solver up to its answer on the host.
    """
    requests: list[Request] = []
    t0 = clock()
    while not requests or clock() - t0 < seconds:
        i = len(requests)
        with span("bench.stage"):
            b = draw_rhs(mix, n, seed, i)
        start = clock()
        with span("bench.solve"):
            result = solve(b)
        requests.append(Request(i, start - t0, clock() - t0, b, result))
    return Window(requests, requests[-1].end_s)
