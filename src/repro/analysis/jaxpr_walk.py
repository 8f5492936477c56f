"""Structural jaxpr traversal: find every collective primitive a traced
program contains, with axes / operand shapes / static byte counts, and
check the overlap dataflow property.

This replaces the fragile ``str(jax.make_jaxpr(...))`` substring checks the
tests used to carry — primitive *reprs* change across JAX versions, but the
primitive *names* and the equation dataflow do not.  Everything here is
version-proofed by duck-typing (an object with ``.eqns`` is a Jaxpr, one
with ``.jaxpr`` is a ClosedJaxpr) rather than by importing jax internals.
"""
from __future__ import annotations

import itertools

import numpy as np

from .records import CollectiveRecord

# jaxpr primitive names that move data between devices.  ``reduce_scatter``
# is what ``jax.lax.psum_scatter`` traces to; it is normalized to the
# canonical ``psum_scatter`` so audit records and the expected-signature
# tables in repro.core.nap_collectives speak one vocabulary.
COLLECTIVE_PRIMS = frozenset({
    "psum", "reduce_scatter", "all_gather", "all_to_all", "ppermute",
    "pmax", "pmin", "pmean",
})
CANONICAL = {"reduce_scatter": "psum_scatter"}

# local contraction work an overlapped exchange can hide behind: the ELL
# and BCSR products are a scan over their stored slots (fori_loop with a
# static trip count), the dense-factor forms a dot_general, norms and dots
# a reduce_sum
CONTRACTION_PRIMS = frozenset({"reduce_sum", "dot_general", "scan"})


def _as_jaxpr(obj):
    """ClosedJaxpr -> Jaxpr (identity on a Jaxpr)."""
    inner = getattr(obj, "jaxpr", None)
    return inner if inner is not None and hasattr(inner, "eqns") else obj


def _sub_jaxprs(params: dict):
    """Every Jaxpr nested in an equation's params (pjit ``jaxpr``,
    shard_map ``jaxpr``, custom-call ``call_jaxpr``, scan ``jaxpr``, lists
    of branches, ...)."""
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for u in items:
            j = _as_jaxpr(u)
            if hasattr(j, "eqns"):
                yield j


def _axes_of(eqn) -> tuple[str, ...]:
    """Named mesh axes of one collective equation (``axes`` for psum-family,
    ``axis_name`` for gather/scatter/a2a/ppermute; bare name or tuple)."""
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(ax, (list, tuple)):
        ax = (ax,)
    return tuple(str(a) for a in ax)


def _record(eqn, idx: int) -> CollectiveRecord:
    op_aval = next(v.aval for v in eqn.invars if hasattr(v, "aval"))
    out_aval = eqn.outvars[0].aval
    nbytes = int(np.prod(op_aval.shape, dtype=np.int64)
                 * np.dtype(op_aval.dtype).itemsize)
    return CollectiveRecord(
        primitive=CANONICAL.get(eqn.primitive.name, eqn.primitive.name),
        axes=_axes_of(eqn),
        operand_shape=tuple(int(d) for d in op_aval.shape),
        operand_dtype=str(np.dtype(op_aval.dtype)),
        out_shape=tuple(int(d) for d in out_aval.shape),
        bytes=nbytes, eqn_index=idx)


def collect_collectives(jaxpr) -> list[CollectiveRecord]:
    """Every collective primitive in ``jaxpr`` (a Jaxpr or ClosedJaxpr),
    recursing into pjit / shard_map / control-flow sub-jaxprs, in trace
    order."""
    out: list[CollectiveRecord] = []
    counter = itertools.count()

    def walk(jx):
        for eqn in jx.eqns:
            idx = next(counter)
            if eqn.primitive.name in COLLECTIVE_PRIMS:
                out.append(_record(eqn, idx))
            for sub in _sub_jaxprs(eqn.params):
                walk(sub)

    walk(_as_jaxpr(jaxpr))
    return out


def collective_signature(jaxpr) -> tuple[str, ...]:
    """Ordered canonical collective-primitive names of ``jaxpr`` — the
    structural replacement for substring-matching the jaxpr's repr."""
    return tuple(r.primitive for r in collect_collectives(jaxpr))


def _collective_scopes(jaxpr):
    """Yield every (sub)jaxpr that contains a collective equation at its own
    scope — the scopes where the overlap dataflow property is checkable."""
    def walk(jx):
        if any(e.primitive.name in COLLECTIVE_PRIMS for e in jx.eqns):
            yield jx
        for eqn in jx.eqns:
            for sub in _sub_jaxprs(eqn.params):
                yield from walk(sub)

    yield from walk(_as_jaxpr(jaxpr))


def _scope_has_independent_contraction(jx) -> bool:
    """True when some contraction equation in ``jx`` does not transitively
    depend on any collective output.

    In the overlapped apply the exchange is issued first but ``A_on · x``
    consumes only local data, so its contraction is collective-independent;
    an apply that first concatenated ``[x | halo]`` into one product would
    taint every contraction.  Equations are in topological order in a jaxpr, so one
    forward sweep propagating a taint set decides it.
    """
    tainted: set = set()
    found = False
    for eqn in jx.eqns:
        depends = any((not hasattr(v, "val")) and v in tainted
                      for v in eqn.invars)
        if (eqn.primitive.name in CONTRACTION_PRIMS) and not depends:
            found = True
        if depends or eqn.primitive.name in COLLECTIVE_PRIMS:
            tainted.update(eqn.outvars)
    return found


def check_overlap_independence(jaxpr) -> bool:
    """The overlap property: in every scope that communicates,
    at least one local contraction is dataflow-independent of the exchange
    (so XLA is free to run them concurrently).  Vacuously true for a
    collective-free program."""
    return all(_scope_has_independent_contraction(jx)
               for jx in _collective_scopes(jaxpr))
