"""``python -m repro.analysis`` — run both CI-gated passes and exit 1 on
any violation.

Pass 1 lowers a small Laplace hierarchy onto a (pods × lanes) host-device
mesh (XLA's host-platform device override — tracing is abstract, nothing
needs real accelerators) and audits every compiled fused program: the full
cycle×smoother grid, PCG, the ``*_m`` multi-RHS variants, every per-level
operator apply, and the setup-phase SpGEMM exchanges (a plain and an
aggressive-coarsening run, the latter exercising the distance-2 ``S²``
exchange).  Pass 2 lints ``src/`` with the repo-invariant rule engine.

``--json report.json`` writes the machine-readable report CI archives;
``--lint-only`` skips the (slower) tracing pass.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path


def run_comm_audit(n: int, pods: int, lanes: int):
    """Build + audit; returns (audits, violations, setup_rows, meta)."""
    # must precede the first jax import anywhere in the process
    flag = f"--xla_force_host_platform_device_count={pods * lanes}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax

    from ..amg import setup
    from ..amg.dist_setup import dist_setup_partitioned
    from ..amg.dist_solve import DistHierarchy
    from ..amg.problems import laplace_3d
    from .comm_audit import audit_hierarchy, audit_setup

    A = laplace_3d(n)
    h = setup(A, solver="rs", max_coarse=30)     # >= 3 levels: W/F revisit
    dh = DistHierarchy.build(h, pods, lanes)
    audits, violations = audit_hierarchy(dh)

    setup_rows = []
    plv, recs = dist_setup_partitioned(A, pods, lanes, max_coarse=30)
    rows, svio = audit_setup(plv, recs)
    setup_rows += rows
    violations += svio
    plv2, recs2 = dist_setup_partitioned(laplace_3d(6), pods, lanes,
                                         aggressive=True)
    rows2, svio2 = audit_setup(plv2, recs2)
    setup_rows += rows2
    violations += svio2
    if not any(r["op"] == "spgemm_S2" for r in rows2):
        from .records import AuditViolation
        violations.append(AuditViolation(
            "missing-record", "aggressive setup ran but no spgemm_S2 "
            "exchange was audited", program="dist_setup"))

    meta = {"n": n, "pods": pods, "lanes": lanes,
            "levels": len(dh.levels), "jax": jax.__version__}
    return audits, violations, setup_rows, meta


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="comm audit (pass 1) + repo-invariant lint (pass 2)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the machine-readable JSON report here")
    ap.add_argument("--lint-only", action="store_true",
                    help="skip the jaxpr tracing pass")
    ap.add_argument("--n", type=int, default=8,
                    help="Laplace grid edge for the audited hierarchy")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--lanes", type=int, default=4)
    args = ap.parse_args(argv)

    from .lint import lint_paths
    from .report import build_report, format_summary, write_report

    src_root = Path(__file__).resolve().parents[2]       # .../src
    lint_violations = lint_paths(src_root)

    audits, violations, setup_rows, meta = [], [], [], {}
    if not args.lint_only:
        audits, violations, setup_rows, meta = run_comm_audit(
            args.n, args.pods, args.lanes)

    report = build_report(audits=audits, audit_violations=violations,
                          lint_violations=lint_violations,
                          setup_rows=setup_rows, meta=meta)
    if args.json:
        write_report(report, args.json)
    print(format_summary(report))
    return 0 if report["summary"]["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
