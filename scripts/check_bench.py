"""Benchmark regression gate: compare a fresh smoke-run BENCH json against
the committed baseline and fail on real regressions.

Wall-clock is deliberately NOT gated (shared CI runners make it pure
noise).  What is gated, per row matched by name:

* ``iters=``  — iteration count to tolerance: fail when the new count
  exceeds ``--max-ratio`` × baseline (+1 absolute slack for tiny counts).
* ``conv=``   — average convergence factor: fail when the new factor
  exceeds ``--max-ratio`` × baseline, or crosses 1.0 (divergence) when the
  baseline converged.
* ``levels=`` — hierarchy depth: fail on any mismatch (a structural
  change, not noise — regenerate the baseline deliberately).
* ``worst_rel=`` — serving-row residual: fail only on *divergence*
  (non-finite or ≥ 1.0).  Serving throughput (``solves_per_s``) is
  wall-clock derived and deliberately NOT gated; presence of the serving
  rows comes from the row-presence check.
* ``unconverged=`` — fail when a run reports unconverged solves where the
  baseline had none (a convergence break, not noise).
* ``parity=`` — kernel-vs-host-CSR max relative error (BENCH_kernels
  rows): fail when non-finite or more than 100× the baseline error (with a
  1e-6 absolute floor so fp32 rounding jitter never trips it) — a
  correctness break in the kernel tier, not noise.
* ``pct_peak=`` — % of the *measured* ERT roofline a kernel achieves:
  gated on presence/finiteness/positivity only (the magnitude is
  wall-clock derived and as noisy as any timing on shared runners).
* ``serving_latency_*`` rows (the open-loop socket load generator) —
  latency percentiles (``p50_ms``/``p99_ms``/``p999_ms``), when present,
  must be finite and positive (a fully-shed class legitimately has no
  latency distribution and omits them); the reject accounting must be
  consistent (``completed + rejected + errors == offered``, with a
  finite ``reject_rate`` in [0, 1]).  The magnitudes are wall-clock
  derived and NOT gated.
* ``dist_overlap_L*`` rows (per-level on/off operator split) — must be
  present whenever the run contains the dist-solve cycle rows, with
  ``on_nnz + off_nnz == local_nnz`` (the split must partition the local
  block exactly), finite non-negative counts and a finite modeled overlap
  efficiency.
* ``streaming_*`` rows (the drift sweep through one streaming session) —
  must be present whenever the run contains the dist-solve cycle rows,
  with the value-only refresh cheaper than the full re-setup it avoids
  (``refresh_us < resetup_us`` — the one timing ordering that IS gated,
  because it is the point of the streaming API), balanced solve
  accounting (``solves == refreshes + resetups + cached``) and a finite
  positive ``max_iters`` (the worst iteration count across the sweep).
* ``comm_audit_*`` rows (the static communication audit,
  ``repro.analysis``) — per-pair traced collective counts must be finite
  and equal to the model-predicted counts (``collectives == expected``,
  ``agree=1``) with zero recorded violations; ``comm_audit_setup_L*``
  rows must show the measured SpGEMM exchange counters equal to the
  static counts of the cached schedule (``static == runtime`` for both
  inter and intra message counts — these exchanges never overlap, so
  exact equality is the contract).  Both row families are required
  whenever the run contains the dist-solve cycle rows.
* ``modeled_us=`` — model-selected strategy rows: the modeled phase time
  must be finite and non-negative.
* row presence — every baseline row must exist in the new run (catches
  silently dropped benchmarks and ``*_ERROR`` harness rows).

Usage (what the CI workflow runs after the smoke benchmarks)::

    python scripts/check_bench.py --baseline BENCH_dist_solve.json \
        --new /tmp/bench_dist_solve.json

Exit code 0 = no regression; 1 = regression(s), listed on stderr.
Baselines are regenerated by re-running the benchmark standalone with
``--smoke --out BENCH_<name>.json`` and committing the result.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import sys

# any ;-free token: float() decides below what is numeric (this must also
# capture "nan"/"inf" so a diverged worst_rel cannot hide from the gate)
NUM = r"([^;=\s]+)"


def parse_derived(derived: str) -> dict[str, float]:
    """Numeric ``key=value`` fields of a derived string (non-numeric values
    like strategy names are skipped; nan/inf parse as floats)."""
    out = {}
    for key, val in re.findall(rf"([A-Za-z_][A-Za-z0-9_]*)={NUM}(?:;|$)",
                               derived):
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


def load_rows(path: str) -> dict[str, dict[str, float]]:
    with open(path) as f:
        data = json.load(f)
    return {r["name"]: parse_derived(r.get("derived", ""))
            for r in data["rows"]}


def check_serving_latency(name: str, vals: dict[str, float]) -> list[str]:
    """Structural checks for one open-loop load-generator row: finite
    positive latency percentiles (when the class completed anything) and
    shed-accounting consistency.  Wall-clock magnitudes stay ungated."""
    out = []
    for key in ("p50_ms", "p99_ms", "p999_ms"):
        if key in vals and not (math.isfinite(vals[key])
                                and vals[key] > 0):
            out.append(f"{name}: {key}={vals[key]} is not a finite "
                       f"positive latency")
    acct = ("offered", "completed", "rejected", "errors")
    if all(k in vals for k in acct):
        served = (vals["completed"] + vals["rejected"] + vals["errors"])
        if served != vals["offered"]:
            out.append(
                f"{name}: reject accounting inconsistent — completed "
                f"{vals['completed']:.0f} + rejected "
                f"{vals['rejected']:.0f} + errors {vals['errors']:.0f} "
                f"!= offered {vals['offered']:.0f}")
        if vals["completed"] > 0 and "p50_ms" not in vals:
            out.append(f"{name}: {vals['completed']:.0f} completed solves "
                       f"but no latency percentiles")
    else:
        out.append(f"{name}: missing accounting field(s) "
                   f"{[k for k in acct if k not in vals]}")
    rate = vals.get("reject_rate")
    if rate is None or not math.isfinite(rate) or not 0 <= rate <= 1:
        out.append(f"{name}: reject_rate={rate} is not a finite rate "
                   f"in [0, 1]")
    return out


def check_overlap_level(name: str, vals: dict[str, float]) -> list[str]:
    """Structural checks for one per-level on/off split row: the split must
    partition the local block exactly, with finite modeled efficiency."""
    out = []
    need = ("on_nnz", "off_nnz", "local_nnz")
    missing = [k for k in need if k not in vals]
    if missing:
        return [f"{name}: missing split field(s) {missing}"]
    on, off, loc = (vals[k] for k in need)
    for k in need:
        if not (math.isfinite(vals[k]) and vals[k] >= 0):
            out.append(f"{name}: {k}={vals[k]} is not a finite "
                       f"non-negative count")
    if on + off != loc:
        out.append(f"{name}: on_nnz {on:.0f} + off_nnz {off:.0f} != "
                   f"local_nnz {loc:.0f} (split does not partition the "
                   f"local block)")
    eff = vals.get("eff_modeled")
    if eff is None or not math.isfinite(eff):
        out.append(f"{name}: eff_modeled={eff} is not a finite overlap "
                   f"efficiency")
    return out


def check_streaming(name: str, vals: dict[str, float]) -> list[str]:
    """Structural checks for one streaming drift-sweep row: the value-only
    refresh must be cheaper than the full re-setup it avoids, the solve
    accounting must balance (solves == refreshes + resetups + cached) and
    the iteration trajectory must stay finite.  Everything else about the
    magnitudes is wall-clock derived and NOT gated."""
    out = []
    need = ("solves", "refreshes", "resetups", "cached")
    missing = [k for k in need if k not in vals]
    if missing:
        return [f"{name}: missing counter field(s) {missing}"]
    for k in need:
        if not (math.isfinite(vals[k]) and vals[k] >= 0):
            out.append(f"{name}: {k}={vals[k]} is not a finite "
                       f"non-negative count")
    booked = vals["refreshes"] + vals["resetups"] + vals["cached"]
    if booked != vals["solves"]:
        out.append(
            f"{name}: solve accounting inconsistent — refreshes "
            f"{vals['refreshes']:.0f} + resetups {vals['resetups']:.0f} + "
            f"cached {vals['cached']:.0f} != solves {vals['solves']:.0f}")
    mi = vals.get("max_iters")
    if mi is None or not (math.isfinite(mi) and mi > 0):
        out.append(f"{name}: max_iters={mi} is not a finite positive "
                   f"iteration count")
    ref, res = vals.get("refresh_us"), vals.get("resetup_us")
    for key, v in (("refresh_us", ref), ("resetup_us", res)):
        if v is None or not (math.isfinite(v) and v > 0):
            out.append(f"{name}: {key}={v} is not a finite positive timing")
    if (ref is not None and res is not None and math.isfinite(ref)
            and math.isfinite(res) and ref >= res):
        out.append(f"{name}: value-only refresh ({ref:.0f}us) is not "
                   f"cheaper than the full re-setup ({res:.0f}us)")
    return out


def check_comm_audit(name: str, vals: dict[str, float]) -> list[str]:
    """Structural checks for one static comm-audit row: the traced
    collective counts must be finite and exactly the model-predicted
    counts, with zero violations recorded by the auditor."""
    out = []
    for key in ("collectives", "expected", "bytes"):
        v = vals.get(key)
        if v is None or not (math.isfinite(v) and v >= 0):
            out.append(f"{name}: {key}={v} is not a finite non-negative "
                       f"count")
    c, e = vals.get("collectives"), vals.get("expected")
    if (c is not None and e is not None and math.isfinite(c)
            and math.isfinite(e) and c != e):
        out.append(f"{name}: traced collectives {c:.0f} != model-predicted "
                   f"{e:.0f}")
    if vals.get("agree") != 1:
        out.append(f"{name}: agree={vals.get('agree')} — traced "
                   f"per-primitive counts diverge from the model")
    if vals.get("violations") != 0:
        out.append(f"{name}: violations={vals.get('violations')} audit "
                   f"violations recorded")
    return out


def check_comm_audit_setup(name: str, vals: dict[str, float]) -> list[str]:
    """Structural checks for one setup-phase audit row: the measured
    SpGEMM exchange counters must equal the static counts of the cached
    schedule — these exchanges never overlap, so exact equality is the
    contract."""
    out = []
    need = ("static_inter_msgs", "runtime_inter_msgs",
            "static_intra_msgs", "runtime_intra_msgs")
    missing = [k for k in need if k not in vals]
    if missing:
        return [f"{name}: missing counter field(s) {missing}"]
    for k in need:
        if not (math.isfinite(vals[k]) and vals[k] >= 0):
            out.append(f"{name}: {k}={vals[k]} is not a finite "
                       f"non-negative count")
    for scope in ("inter", "intra"):
        s, r = vals[f"static_{scope}_msgs"], vals[f"runtime_{scope}_msgs"]
        if s != r:
            out.append(f"{name}: measured {scope} messages {r:.0f} != "
                       f"static {s:.0f} for the cached schedule")
    if vals.get("violations") != 0:
        out.append(f"{name}: violations={vals.get('violations')} "
                   f"setup-audit violations recorded")
    return out


def compare(base: dict, new: dict, max_ratio: float) -> list[str]:
    """List of human-readable regressions (empty = gate passes)."""
    problems = []
    for name in base:
        if any(tag in name for tag in ("_ERROR",)):
            problems.append(f"{name}: baseline itself contains an error row")
    for name in new:
        if "_ERROR" in name:
            problems.append(f"{name}: new run emitted an error row")
    compared = 0
    for name, bvals in base.items():
        nvals = new.get(name)
        if nvals is None:
            problems.append(f"{name}: row missing from new run")
            continue
        if "iters" in bvals and "iters" in nvals:
            compared += 1
            if nvals["iters"] > max_ratio * bvals["iters"] + 1:
                problems.append(
                    f"{name}: iters {bvals['iters']:.0f} -> "
                    f"{nvals['iters']:.0f} (> {max_ratio:g}x)")
        if "conv" in bvals and "conv" in nvals:
            compared += 1
            if nvals["conv"] > max_ratio * bvals["conv"] + 1e-9:
                problems.append(
                    f"{name}: conv {bvals['conv']:.3f} -> "
                    f"{nvals['conv']:.3f} (> {max_ratio:g}x)")
            elif bvals["conv"] < 1.0 <= nvals["conv"]:
                problems.append(
                    f"{name}: conv {nvals['conv']:.3f} diverged "
                    f"(baseline {bvals['conv']:.3f})")
        if "levels" in bvals and "levels" in nvals:
            compared += 1
            if nvals["levels"] != bvals["levels"]:
                problems.append(
                    f"{name}: levels {bvals['levels']:.0f} -> "
                    f"{nvals['levels']:.0f} (structural change)")
        if "worst_rel" in bvals and "worst_rel" in nvals:
            compared += 1
            wr = nvals["worst_rel"]
            if not math.isfinite(wr) or wr >= 1.0:
                problems.append(
                    f"{name}: worst_rel {wr:.3e} diverged "
                    f"(baseline {bvals['worst_rel']:.3e})")
        if "unconverged" in bvals and "unconverged" in nvals:
            compared += 1
            if nvals["unconverged"] > 0 and bvals["unconverged"] == 0:
                problems.append(
                    f"{name}: {nvals['unconverged']:.0f} unconverged "
                    f"solves (baseline had none)")
        if "parity" in bvals and "parity" in nvals:
            compared += 1
            p = nvals["parity"]
            if not math.isfinite(p) or p > max(100.0 * bvals["parity"],
                                               1e-6):
                problems.append(
                    f"{name}: parity {bvals['parity']:.3e} -> {p:.3e} "
                    f"(kernel no longer matches the host CSR oracle)")
        if "pct_peak" in bvals and "pct_peak" in nvals:
            compared += 1
            pp = nvals["pct_peak"]
            if not math.isfinite(pp) or pp <= 0:
                problems.append(
                    f"{name}: pct_peak {pp} is not a positive finite % of "
                    f"the measured roofline")
        if name.startswith("serving_latency") and "_ERROR" not in name:
            compared += 1
            problems.extend(check_serving_latency(name, nvals))
        if "modeled_us" in bvals:
            compared += 1
            mu = nvals.get("modeled_us")
            if mu is None or not (math.isfinite(mu) and mu >= 0):
                problems.append(f"{name}: modeled_us={mu} is not a finite "
                                f"non-negative modeled time")
    # overlap rows are gated structurally over the NEW run (so a freshly
    # added row is vetted even before a baseline records it), and required
    # outright whenever the run exercises the dist-solve cycle sweep
    for name, nvals in new.items():
        if name.startswith("dist_overlap_L"):
            compared += 1
            problems.extend(check_overlap_level(name, nvals))
        elif name.startswith("streaming_"):
            compared += 1
            problems.extend(check_streaming(name, nvals))
        elif name.startswith("comm_audit_setup_"):
            compared += 1
            problems.extend(check_comm_audit_setup(name, nvals))
        elif name.startswith("comm_audit_"):
            compared += 1
            problems.extend(check_comm_audit(name, nvals))
    if any(n.startswith("dist_cycle_") for n in new):
        if not any(n.startswith("dist_overlap_L") for n in new):
            problems.append("dist_overlap_L*: per-level on/off split rows "
                            "missing from a dist-solve run")
        if not any(n.startswith("streaming_") for n in new):
            problems.append("streaming_*: drift-sweep rows missing from a "
                            "dist-solve run")
        if not any(n.startswith("comm_audit_")
                   and not n.startswith("comm_audit_setup_") for n in new):
            problems.append("comm_audit_*: static comm-audit rows missing "
                            "from a dist-solve run")
        if not any(n.startswith("comm_audit_setup_") for n in new):
            problems.append("comm_audit_setup_*: setup-phase audit rows "
                            "missing from a dist-solve run")
    if compared == 0:
        problems.append(
            "no comparable iters/conv/levels metrics between baseline and "
            "new run — row names do not overlap (regenerate the baseline "
            "with the same --smoke setting CI uses)")
    return problems


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--baseline", required=True,
                        help="committed BENCH_*.json baseline")
    parser.add_argument("--new", required=True, dest="new_path",
                        help="freshly generated BENCH json to vet")
    parser.add_argument("--max-ratio", type=float, default=2.0,
                        help="allowed iters/conv growth factor (default 2x)")
    args = parser.parse_args(argv)
    base = load_rows(args.baseline)
    new = load_rows(args.new_path)
    problems = compare(base, new, args.max_ratio)
    if problems:
        print(f"bench regression gate FAILED "
              f"({args.baseline} vs {args.new_path}):", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    n = sum(1 for name in base if name in new)
    print(f"bench regression gate OK: {n}/{len(base)} baseline rows "
          "matched, no iters/conv/levels regression")
    return 0


if __name__ == "__main__":
    sys.exit(main())
