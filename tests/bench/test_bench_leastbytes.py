"""The least-work count of one PCG iteration, against a hand count."""
import types

import numpy as np
import pytest

import leastbytes


def _op(m, n, nnz):
    indptr = np.zeros(m + 1, dtype=np.int64)
    indptr[-1] = nnz
    return types.SimpleNamespace(shape=(m, n), indptr=indptr)


def _levels():
    L = types.SimpleNamespace
    return [L(A=_op(10, 10, 28), P=_op(10, 4, 10), R=_op(4, 10, 10)),
            L(A=_op(4, 4, 10), P=_op(4, 2, 4), R=_op(2, 4, 4)),
            L(A=_op(2, 2, 4), P=None, R=None)]


V11 = {"cycle": "V", "smoother": "jacobi", "presweeps": 1, "postsweeps": 1}


def test_hand_count_float32():
    # 4 bytes a value; an m×n apply with z values moves 4(z + n + m) bytes
    # and does 2z operations.  V(1,1): A three times a level, R and P once.
    by_hand_bytes = (4 * (28 + 10 + 10)             # A0·p of the CG step
                     + 3 * 4 * (28 + 10 + 10)       # L0: 2 sweeps + residual
                     + 4 * (10 + 10 + 4)            # R0
                     + 4 * (10 + 4 + 10)            # P0
                     + 3 * 4 * (10 + 4 + 4)         # L1 A
                     + 4 * (4 + 4 + 2)              # R1
                     + 4 * (4 + 2 + 4)              # P1
                     + 4 * (2 * 2 + 2 * 2))         # dense 2×2 coarse solve
    by_hand_flops = (2 * 28 + 3 * 2 * 28 + 2 * 10 + 2 * 10
                     + 3 * 2 * 10 + 2 * 4 + 2 * 4 + 2 * 4)
    assert by_hand_bytes == 1288 and by_hand_flops == 348
    w = leastbytes.iteration_work(_levels(), V11, "float32")
    assert (w.bytes, w.flops) == (by_hand_bytes, by_hand_flops)


def test_precision_scales_bytes_only():
    w32 = leastbytes.iteration_work(_levels(), V11, "float32")
    w16 = leastbytes.iteration_work(_levels(), V11, "bfloat16")
    assert w16.bytes * 2 == w32.bytes and w16.flops == w32.flops


def test_sweeps_count():
    w = leastbytes.iteration_work(_levels(), dict(V11, presweeps=2),
                                  "float32")
    base = leastbytes.iteration_work(_levels(), V11, "float32")
    assert w.bytes - base.bytes == 4 * (28 + 10 + 10) + 4 * (10 + 4 + 4)


def test_least_time_is_the_larger_bound():
    w = leastbytes.Work(bytes=819e9, flops=1.0)
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    assert w.seconds(peaks) == pytest.approx(1.0)
    assert leastbytes.Work(1.0, 197e12).seconds(peaks) == pytest.approx(1.0)


def test_other_cycles_are_refused():
    with pytest.raises(ValueError):
        leastbytes.iteration_work(_levels(), dict(V11, cycle="W"), "float32")


def test_real_hierarchy_floor():
    """On a real hierarchy the count is at least the fine operator's
    values read four times (three in the cycle, one in the CG step)."""
    from repro.amg.hierarchy import setup
    from repro.amg.problems import laplace_3d

    h = setup(laplace_3d(8))
    w = leastbytes.iteration_work(h.levels, V11, "float32")
    assert w.bytes > 4 * 4 * h.levels[0].A.nnz
