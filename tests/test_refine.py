"""Float64 answers from a float32 device: a ``dtype="float64"`` dist
session whose device arrays JAX narrowed to float32 (``jax_enable_x64``
off, as here and on a TPU) refines around its float32 device solve.

On rotated anisotropic diffusion (ε = 0.001, θ = 45°) at 64² the float32
path alone stalls at a true residual of about 5e-6 (‖x‖/‖b‖ ≈ 15, and the
exact answer rounded to float32 reads 8.8e-7), so the float64 tolerance
below is out of its reach; the refined session meets
them, agrees with the host float64 reference, keeps to the iteration cap,
and leaves float32 sessions exactly as they were.
"""
import numpy as np
import pytest

from repro.amg import spans
from repro.amg.api import AMGConfig, AMGSolver, clear_sessions
from repro.amg.api.sessions import REFINE_DROP
from repro.amg.dist_solve import dist_pcg, dist_solve
from repro.amg.problems import rotated_anisotropic_2d
from repro.amg.solve import MultiSolveResult

N = 64
TOL = 1e-8


@pytest.fixture(autouse=True)
def _fresh_sessions():
    clear_sessions()
    yield
    clear_sessions()


@pytest.fixture(scope="module")
def A():
    return rotated_anisotropic_2d(N)


@pytest.fixture(scope="module")
def b(A):
    return np.random.default_rng(7).standard_normal(A.nrows)


def _config(dtype, **kw):
    return AMGConfig(backend="dist", dtype=dtype, tol=TOL, theta=0.25, **kw)


def _rel(A, x, b):
    return (np.linalg.norm(b - A.matvec(np.asarray(x, np.float64)))
            / np.linalg.norm(b))


def _spans(name):
    return [s for s in spans.recent() if s.name == name]


# the stationary iteration contracts far more slowly than PCG here
MAXITER = {"pcg": 400, "solve": 3000}


@pytest.mark.parametrize("method", ["pcg", "solve"])
def test_float64_session_reaches_a_float64_tolerance(A, b, method):
    bound = AMGSolver(_config("float64")).setup(A)
    res = getattr(bound, method)(b, maxiter=MAXITER[method])
    assert bound.dist_hierarchy.dtype == np.float32    # what JAX holds
    assert res.x.dtype == np.float64
    assert res.converged
    assert _rel(A, res.x, b) <= TOL
    # the history is the float64 true residual at each segment's end
    assert res.residuals[-1] / np.linalg.norm(b) == pytest.approx(
        _rel(A, res.x, b), rel=1e-9)


@pytest.mark.parametrize("method", ["pcg", "solve"])
def test_float32_session_cannot_reach_it(A, b, method):
    bound = AMGSolver(_config("float32")).setup(A)
    res = getattr(bound, method)(b, maxiter=MAXITER[method])
    assert res.x.dtype == np.float32
    assert _rel(A, res.x, b) > 10 * TOL


def test_answer_agrees_with_the_host_float64_reference(A, b):
    """The refined answer to ``TOL`` against the host backend's float64
    PCG run to 1e-12: they agree to ``TOL`` (relative error 8e-10 here),
    and their difference, put through A, is within ``TOL`` of ‖b‖."""
    dist = AMGSolver(_config("float64")).setup(A).pcg(b, maxiter=400)
    host = AMGSolver(AMGConfig(backend="host", tol=1e-12, theta=0.25)
                     ).setup(A).pcg(b, maxiter=400)
    assert host.converged and dist.converged
    diff = dist.x - host.x
    assert np.linalg.norm(diff) / np.linalg.norm(host.x) <= TOL
    assert np.linalg.norm(A.matvec(diff)) / np.linalg.norm(b) <= TOL


@pytest.mark.parametrize("method,steps", [("pcg", "amg.pcg.step"),
                                          ("solve", None)])
@pytest.mark.parametrize("maxiter", [23, 61])
def test_tol_zero_runs_exactly_maxiter_inner_iterations(A, b, method,
                                                       steps, maxiter):
    bound = AMGSolver(_config("float64")).setup(A)
    spans.clear()
    res = getattr(bound, method)(b, tol=0.0, maxiter=maxiter)
    assert res.iterations == maxiter and not res.converged
    if steps is not None:
        assert len(_spans(steps)) == maxiter
    (call,) = _spans("amg.refine")
    segments = _spans("amg.refine.residual")
    assert call.attrs["iterations"] == maxiter
    assert call.attrs["segments"] == len(segments) == len(res.residuals) - 1
    assert all(s.parent_id == call.id for s in segments)
    # every segment but the last stopped once its own residual fell by
    # REFINE_DROP; the last ran out of the cap
    rel_in = [1.0] + [s.attrs["rel"] for s in segments]
    drops = [s.attrs["rec_rel"] / r for s, r in zip(segments, rel_in)]
    assert all(d <= REFINE_DROP for d in drops[:-1])
    assert call.attrs["rel_residual"] == segments[-1].attrs["rel"]


def test_segments_restart_at_the_drop(A, b):
    """A 61-iteration cap spans more than one segment here, and each
    segment's true residual tracks what its float32 recursion predicts."""
    bound = AMGSolver(_config("float64")).setup(A)
    spans.clear()
    bound.pcg(b, tol=0.0, maxiter=61)
    segments = _spans("amg.refine.residual")
    assert len(segments) >= 2
    solves = _spans("amg.pcg")
    assert len(solves) == len(segments)
    assert [s.attrs["maxiter"] for s in solves][0] == 61
    for s in segments:
        assert 0.5 < s.attrs["rel"] / s.attrs["rec_rel"] < 2.0


def test_several_right_hand_sides_and_a_warm_start(A, b):
    bound = AMGSolver(_config("float64")).setup(A)
    B = np.stack([b, np.random.default_rng(8).standard_normal(A.nrows)],
                 axis=1)
    res = bound.pcg(B, maxiter=400)
    assert isinstance(res, MultiSolveResult)
    assert res.x.shape == B.shape and res.x.dtype == np.float64
    for j, col in enumerate(res.columns):
        assert col.converged
        assert _rel(A, res.x[:, j], B[:, j]) <= TOL
        np.testing.assert_array_equal(col.x, res.x[:, j])
    # a warm start near the answer needs fewer inner iterations
    x0 = res.x + 1e-4 * np.random.default_rng(9).standard_normal(B.shape)
    warm = bound.pcg(B, maxiter=400, x0=x0)
    for j, col in enumerate(warm.columns):
        assert _rel(A, warm.x[:, j], B[:, j]) <= TOL
        assert col.iterations < res.columns[j].iterations
    one = bound.pcg(b, maxiter=400, x0=x0[:, 0])
    assert one.converged and _rel(A, one.x, b) <= TOL


def test_float32_sessions_are_untouched(A, b):
    """A float32 session runs the device solve as it is: the same answer
    bit for bit as `dist_pcg` called on its lowering, and no refinement."""
    bound = AMGSolver(_config("float32")).setup(A)
    spans.clear()
    res = bound.pcg(b, maxiter=40)
    assert not _spans("amg.refine") and not _spans("amg.refine.residual")
    direct = dist_pcg(bound.dist_hierarchy, b.astype(np.float32), tol=TOL,
                      maxiter=40, opts=bound.opts)
    np.testing.assert_array_equal(res.x, direct.x)
    assert res.residuals == direct.residuals
    stat = bound.solve(b, maxiter=7)
    np.testing.assert_array_equal(
        stat.x, dist_solve(bound.dist_hierarchy, b.astype(np.float32),
                           tol=TOL, maxiter=7, opts=bound.opts).x)
    assert bound.staging_dtype() == np.float32


@pytest.mark.parametrize("setup_backend", ["host", "dist"])
def test_update_refines_against_the_new_operator(A, b, setup_backend):
    bound = AMGSolver(_config("float64", setup_backend=setup_backend)
                      ).setup(A)
    bound.pcg(b, maxiter=400)
    diag = A.rows_expanded() == A.indices
    delta = np.where(diag, 0.05 * A.data, 0.0)      # same pattern
    action = bound.update(delta=delta)
    assert action == "refresh"
    A_new = type(A)(A.shape, A.indptr, A.indices, A.data + delta)
    res = bound.pcg(b, maxiter=400)
    assert _rel(A_new, res.x, b) <= TOL
    assert _rel(A, res.x, b) > 1e3 * TOL
