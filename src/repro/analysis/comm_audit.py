"""Pass 1 — the communication auditor.

Walks the ClosedJaxpr of every compiled fused program a
:class:`~repro.amg.dist_solve.DistHierarchy` exposes (all cycle×smoother
pairs, PCG, the ``*_m`` multi-RHS variants) plus the per-operator applies,
extracts each collective primitive, and cross-checks against:

* the selected strategy's predicted structure (the per-strategy signature
  tables in :mod:`repro.core.nap_collectives`) — e.g. NAP-3 ``hier_psum``
  must lower to psum_scatter(fast) + psum(slow) + all_gather(fast), a
  ``halo_empty`` level must lower to zero collectives;
* the overlap dataflow property — in every apply that communicates, the
  halo exchange must be dataflow-independent of the ``A_on`` contraction
  (checked by a taint sweep over the jaxpr's topological equation order);
* :func:`~repro.amg.dist_solve.cycle_comm_stats`' modeled counters — a
  level/op the model says communicates must have a non-empty plan, and vice
  versa;
* the setup-phase SpGEMM exchanges — the *measured* message/byte counters
  each :class:`~repro.amg.dist_setup.SetupCommRecord` carries must equal
  the static :class:`~repro.core.schedules.ScheduleStats` of the schedule
  that was selected and cached for replay.

Any mismatch is a typed :class:`~repro.analysis.records.AuditViolation`
with the offending equation and level/op attribution.
"""
from __future__ import annotations

import math
from collections import Counter

from .jaxpr_walk import check_overlap_independence, collect_collectives
from .records import AuditViolation, CommAudit

#: the fused programs DistHierarchy.programs exposes (single-RHS + _m)
PROGRAM_NAMES = ("resid_norm", "cycle", "vcycle", "pcg_init", "pcg_step",
                 "resid_norm_m", "cycle_m", "vcycle_m", "pcg_init_m",
                 "pcg_step_m")


def _counts(records) -> dict[str, int]:
    return dict(Counter(r.primitive for r in records))


def audit_jaxpr(jaxpr, program: str, *,
                expected_signature: tuple[str, ...] | None = None,
                expected_counts: dict[str, int] | None = None,
                require_overlap: bool = False,
                level: int | None = None, op: str | None = None) -> CommAudit:
    """Audit one traced program against an expected structure.

    ``expected_signature`` checks the *ordered* primitive sequence (the
    per-operator granularity — exact strategy lowering); ``expected_counts``
    checks per-primitive totals (the fused-program granularity, where many
    applies interleave).  ``require_overlap`` additionally demands a
    collective-independent local contraction in every communicating scope.
    """
    records = collect_collectives(jaxpr)
    audit = CommAudit(program=program, records=records,
                      counts=_counts(records), level=level, op=op)
    sig = audit.signature()
    if expected_signature is not None:
        audit.expected = dict(Counter(expected_signature))
        if sig != tuple(expected_signature):
            eqn = next((r for r in records
                        if r.primitive not in expected_signature),
                       records[0] if records else None)
            kind = ("empty-halo-collective" if not expected_signature
                    else "signature-mismatch")
            audit.violations.append(AuditViolation(
                kind,
                f"lowered collectives {list(sig)} != expected "
                f"{list(expected_signature)}",
                program=program, level=level, op=op, eqn=eqn))
    if expected_counts is not None:
        audit.expected = {k: v for k, v in expected_counts.items() if v}
        actual = audit.counts
        if audit.expected != {k: v for k, v in actual.items() if v}:
            prims = sorted(set(audit.expected) | set(actual))
            diff = "; ".join(
                f"{p}: expected {audit.expected.get(p, 0)}, "
                f"got {actual.get(p, 0)}"
                for p in prims
                if audit.expected.get(p, 0) != actual.get(p, 0))
            surplus = next(
                (r for r in records
                 if actual.get(r.primitive, 0)
                 > audit.expected.get(r.primitive, 0)), None)
            audit.violations.append(AuditViolation(
                "count-mismatch", diff, program=program, level=level, op=op,
                eqn=surplus))
    if require_overlap and records and not check_overlap_independence(jaxpr):
        audit.violations.append(AuditViolation(
            "overlap-serialized",
            "every local contraction depends on the halo exchange — the "
            "overlapped apply has been serialized",
            program=program, level=level, op=op))
    return audit


def audit_apply(dh, level: int, op: str = "A") -> CommAudit:
    """Per-operator audit: one SpMV apply of ``levels[level].<op>`` must
    lower to exactly the selected strategy's ordered halo signature (empty
    for an empty-halo plan), and keep the on-process contraction
    dataflow-independent of the exchange."""
    jaxpr = dh.trace_apply(level, op)
    return audit_jaxpr(
        jaxpr, f"apply_{op}",
        expected_signature=dh.expected_apply_signature(level, op),
        require_overlap=True, level=level, op=op)


def audit_program(dh, name: str, opts=None, k: int = 2,
                  label: str | None = None) -> CommAudit:
    """Fused-program audit: per-primitive collective counts of the traced
    program must equal the counts the cycle structure + selected strategies
    predict (:meth:`DistHierarchy.expected_collectives`).  ``label``
    overrides the record's program name (e.g. ``vcycle[W+chebyshev]``)."""
    jaxpr = dh.trace_program(name, opts, k=k)
    return audit_jaxpr(jaxpr, label or name,
                       expected_counts=dh.expected_collectives(opts, name),
                       require_overlap=True)


def audit_cycle_stats(dh, opts=None) -> list[AuditViolation]:
    """Model-vs-static agreement: a (level, op) whose modeled per-cycle
    counters (:func:`cycle_comm_stats`' per-level rows, from the selected
    schedule's :class:`ScheduleStats`) say it communicates must have a
    non-empty halo plan, and vice versa — plus finiteness of the totals."""
    from ..amg.dist_solve import cycle_comm_stats
    out: list[AuditViolation] = []
    stats = cycle_comm_stats(dh, opts)
    for key in ("inter_msgs", "intra_msgs", "inter_bytes", "intra_bytes"):
        if not math.isfinite(stats[key]) or stats[key] < 0:
            out.append(AuditViolation(
                "stats-nonfinite", f"cycle_comm_stats[{key}]={stats[key]}",
                program="cycle_comm_stats"))
    for l, dl in enumerate(dh.levels):
        for stat_key, attr in (("spmv_A", "A"), ("interp", "P"),
                               ("restrict", "R")):
            if stat_key not in dl.comm_stats:
                continue
            dop = getattr(dl, attr)
            if dop is None:
                continue
            row = dl.comm_stats[stat_key]
            modeled_msgs = row["inter_msgs"] + row["intra_msgs"]
            static_empty = dop.plan.total_halo == 0
            if static_empty and modeled_msgs > 0:
                out.append(AuditViolation(
                    "model-static-disagreement",
                    f"model prices {modeled_msgs} msgs/apply but the halo "
                    f"plan is empty", program="cycle_comm_stats",
                    level=l, op=attr))
            if not static_empty and modeled_msgs == 0:
                out.append(AuditViolation(
                    "model-static-disagreement",
                    f"halo plan moves {dop.plan.total_halo} entries but the "
                    f"model prices zero messages",
                    program="cycle_comm_stats", level=l, op=attr))
    return out


def audit_setup(plevels, records) -> tuple[list[dict], list[AuditViolation]]:
    """Setup-phase SpGEMM audit: for every exchange whose schedule was
    cached for replay (:attr:`PartitionedLevel.plans`), the *measured*
    message/byte counters of the executed
    :func:`~repro.core.nap_collectives.matrix_halo_exchange` must equal the
    counts statically derivable from the selected schedule.  Inter-node
    counts come from :class:`~repro.core.schedules.ScheduleStats`; the
    intra count is re-derived with the exchange's own semantics (EVERY
    same-node message — ``ScheduleStats`` deliberately excludes the
    direct on-node messages common to all strategies, paper §3.3).
    Returns (summary rows, violations)."""
    from ..core.schedules import ScheduleStats

    def static_intra(schedule):
        g, topo = schedule.graph, schedule.graph.topo
        cnt = 0
        for _kind, msg in schedule.all_messages():
            if topo.on_same_node(msg.src, msg.dst):
                cnt += 1
        return cnt

    rows: list[dict] = []
    violations: list[AuditViolation] = []
    by_key = {}
    for rec in records:                     # refresh replays: last one wins
        by_key[(rec.level, rec.op)] = rec
    for l, plv in enumerate(plevels):
        for op, (strat, plan) in sorted(plv.plans.items()):
            rec = by_key.get((l, op))
            if rec is None:
                violations.append(AuditViolation(
                    "missing-record",
                    f"schedule cached for {op} but no SetupCommRecord was "
                    f"measured", program="dist_setup", level=l, op=op))
                continue
            st = ScheduleStats.of(plan.schedule)
            row = {"level": l, "op": op, "strategy": strat,
                   "static_inter_msgs": st.inter_msg_count,
                   "runtime_inter_msgs": rec.inter_msgs,
                   "static_intra_msgs": static_intra(plan.schedule),
                   "runtime_intra_msgs": rec.intra_msgs,
                   "static_inter_bytes": st.inter_bytes_total,
                   "runtime_inter_bytes": rec.inter_bytes}
            rows.append(row)
            if rec.strategy != strat:
                violations.append(AuditViolation(
                    "strategy-mismatch",
                    f"record ran {rec.strategy!r} but the cached schedule "
                    f"is {strat!r}", program="dist_setup", level=l, op=op))
            for static, runtime in (("static_inter_msgs",
                                     "runtime_inter_msgs"),
                                    ("static_intra_msgs",
                                     "runtime_intra_msgs")):
                if row[static] != row[runtime]:
                    violations.append(AuditViolation(
                        "setup-count-mismatch",
                        f"{runtime}={row[runtime]} != {static}={row[static]}"
                        f" for the selected {strat} schedule",
                        program="dist_setup", level=l, op=op))
            if not math.isclose(row["static_inter_bytes"],
                                row["runtime_inter_bytes"],
                                rel_tol=1e-9, abs_tol=1e-6):
                violations.append(AuditViolation(
                    "setup-bytes-mismatch",
                    f"measured inter bytes {row['runtime_inter_bytes']} != "
                    f"modeled {row['static_inter_bytes']}",
                    program="dist_setup", level=l, op=op))
    return rows, violations


def audit_hierarchy(dh, *, pairs=None, pair_programs=("vcycle", "vcycle_m"),
                    full_opts=None, k: int = 2,
                    ) -> tuple[list[CommAudit], list[AuditViolation]]:
    """The whole Pass-1 sweep over one lowered hierarchy.

    * every (cycle, smoother) pair in ``pairs`` (default: the full 15-pair
      grid) through ``pair_programs``,
    * the complete program set (PCG included, ``*_m`` variants included)
      for ``full_opts`` (default ``SolveOptions()``),
    * every per-level operator apply (exact ordered strategy signature +
      overlap independence),
    * the modeled-counter agreement of :func:`cycle_comm_stats` per pair.

    Returns ``(audits, violations)`` — ``violations`` aggregates every
    audit's findings plus the stats-agreement findings.
    """
    from ..amg.solve import CYCLES, SMOOTHERS, SolveOptions
    if pairs is None:
        pairs = [(c, s) for c in CYCLES for s in SMOOTHERS]
    full_opts = full_opts or SolveOptions()
    audits: list[CommAudit] = []
    violations: list[AuditViolation] = []
    for cycle, smoother in pairs:
        opts = SolveOptions(cycle=cycle, smoother=smoother)
        for name in pair_programs:
            audits.append(audit_program(
                dh, name, opts, k=k, label=f"{name}[{cycle}+{smoother}]"))
        violations.extend(audit_cycle_stats(dh, opts))
    for name in PROGRAM_NAMES:
        audits.append(audit_program(dh, name, full_opts, k=k))
    for l, dl in enumerate(dh.levels):
        for op in ("A", "P", "R"):
            if getattr(dl, op) is not None:
                audits.append(audit_apply(dh, l, op))
    for a in audits:
        violations.extend(a.violations)
    return audits, violations
