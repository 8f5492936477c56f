"""Tests for the CI benchmark regression gate (scripts/check_bench.py)."""
import importlib.util
import json
import pathlib

SCRIPT = pathlib.Path(__file__).parents[1] / "scripts" / "check_bench.py"
_spec = importlib.util.spec_from_file_location("check_bench", SCRIPT)
check_bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_bench)


def write(path, rows):
    path.write_text(json.dumps({"benchmark": "t", "rows": rows}))
    return str(path)


def row(name, derived):
    return {"name": name, "us_per_call": 1.0, "derived": derived}


def run(tmp_path, base_rows, new_rows, max_ratio=2.0):
    base = write(tmp_path / "base.json", base_rows)
    new = write(tmp_path / "new.json", new_rows)
    return check_bench.main(["--baseline", base, "--new", new,
                             "--max-ratio", str(max_ratio)])


def test_identical_passes(tmp_path):
    rows = [row("a", "iters=10;conv=0.25;levels=3")]
    assert run(tmp_path, rows, rows) == 0


def test_wallclock_is_not_gated(tmp_path):
    base = [row("a", "iters=10;conv=0.25")]
    new = [{"name": "a", "us_per_call": 1e9,
            "derived": "iters=10;conv=0.25"}]
    assert run(tmp_path, base, new) == 0


def test_iteration_regression_fails(tmp_path):
    base = [row("a", "iters=10;conv=0.25")]
    assert run(tmp_path, base, [row("a", "iters=22;conv=0.25")]) == 1
    # within 2x (+1 slack) passes
    assert run(tmp_path, base, [row("a", "iters=20;conv=0.25")]) == 0


def test_conv_regression_and_divergence_fail(tmp_path):
    base = [row("a", "iters=10;conv=0.25")]
    assert run(tmp_path, base, [row("a", "iters=10;conv=0.60")]) == 1
    base2 = [row("a", "conv=0.80")]
    assert run(tmp_path, base2, [row("a", "conv=1.10")]) == 1
    assert run(tmp_path, base2, [row("a", "conv=0.90")]) == 0


def test_missing_row_and_error_rows_fail(tmp_path):
    base = [row("a", "conv=0.25"), row("b", "conv=0.30")]
    assert run(tmp_path, base, [row("a", "conv=0.25")]) == 1
    new = base + [row("dist_solve_ERROR", "boom")]
    assert run(tmp_path, base, new) == 1


def test_levels_mismatch_fails(tmp_path):
    base = [row("a", "levels=3;conv=0.2")]
    assert run(tmp_path, base, [row("a", "levels=2;conv=0.2")]) == 1


def test_serving_rows_gate_presence_and_divergence_only(tmp_path):
    """Serving rows: throughput (solves_per_s) may move freely — only a
    missing row, a diverged worst_rel, or new unconverged solves fail."""
    base = [row("serve_coalesced_host",
                "backend=host;requests=4;solves_per_s=120.0;batches=1;"
                "worst_rel=3.1e-09;unconverged=0")]
    # 100x slower serving still passes (wall-clock derived, not gated)
    ok = [row("serve_coalesced_host",
              "backend=host;requests=4;solves_per_s=1.2;batches=1;"
              "worst_rel=8.0e-07;unconverged=0")]
    assert run(tmp_path, base, ok) == 0
    # a diverged residual fails
    bad = [row("serve_coalesced_host",
               "backend=host;requests=4;solves_per_s=120.0;batches=1;"
               "worst_rel=2.5e+00;unconverged=0")]
    assert run(tmp_path, base, bad) == 1
    # fresh unconverged solves fail when the baseline had none
    unc = [row("serve_coalesced_host",
               "backend=host;requests=4;solves_per_s=120.0;batches=1;"
               "worst_rel=3.1e-09;unconverged=2")]
    assert run(tmp_path, base, unc) == 1
    # a missing serving row fails (presence)
    assert run(tmp_path, base, [row("other", "conv=0.2")]) == 1
    # a NaN residual must parse and fail — it cannot hide from the gate
    nan = [row("serve_coalesced_host",
               "backend=host;requests=4;solves_per_s=120.0;batches=1;"
               "worst_rel=nan;unconverged=0")]
    assert check_bench.parse_derived(nan[0]["derived"])["worst_rel"] != \
        check_bench.parse_derived(nan[0]["derived"])["worst_rel"]  # is NaN
    assert run(tmp_path, base, nan) == 1


def test_no_overlap_fails(tmp_path):
    base = [row("a_n4096", "conv=0.25")]
    assert run(tmp_path, base, [row("a_n512", "conv=0.25")]) == 1


def test_parse_derived_skips_non_numeric():
    d = check_bench.parse_derived(
        "n=512;mesh=2x4;conv=0.166;strategy=nap2;speedup=45.9x;iters=7")
    assert d["n"] == 512 and d["conv"] == 0.166 and d["iters"] == 7
    assert "mesh" not in d and "strategy" not in d and "speedup" not in d


def test_overlap_level_rows_gate_split_exactness(tmp_path):
    """dist_overlap_L* rows: on+off must equal local nnz, fields finite."""
    good = row("dist_overlap_L0",
               "on_nnz=3872;off_nnz=6776;local_nnz=10648;halo_empty=0;"
               "eff_modeled=0.0004;strategy=standard")
    assert run(tmp_path, [good], [good]) == 0
    # split that does not partition the local block fails
    bad = [row("dist_overlap_L0",
               "on_nnz=3872;off_nnz=6776;local_nnz=10000;halo_empty=0;"
               "eff_modeled=0.0004;strategy=standard")]
    assert run(tmp_path, [good], bad) == 1
    # a missing split field fails
    missing = [row("dist_overlap_L0",
                   "on_nnz=3872;local_nnz=10648;eff_modeled=0.1")]
    assert run(tmp_path, [good], missing) == 1
    # non-finite efficiency fails
    nan_eff = [row("dist_overlap_L0",
                   "on_nnz=1;off_nnz=1;local_nnz=2;eff_modeled=nan")]
    assert run(tmp_path, [good], nan_eff) == 1


STREAMING_DERIVED = ("n=512;mesh=2x4;steps=4;solves=5;refreshes=3;"
                     "resetups=1;cached=1;max_iters=7;iters=7:7:7:7:7;"
                     "triggers=drift:3,regression:1;refresh_us=16000.0;"
                     "resetup_us=38000.0;speedup=2.4")


COMM_AUDIT_DERIVED = ("mesh=2x4;collectives=24;expected=24;bytes=15480;"
                      "agree=1;violations=0")
COMM_AUDIT_SETUP_DERIVED = ("strategy=standard;static_inter_msgs=2;"
                            "runtime_inter_msgs=2;static_intra_msgs=12;"
                            "runtime_intra_msgs=12;violations=0")


def test_overlap_rows_required_with_cycle_sweep(tmp_path):
    """A run with the dist-solve cycle sweep but no overlap (or streaming,
    or comm-audit) rows fails."""
    cyc = row("dist_cycle_V_jacobi", "iters=7;conv=0.17;inter_msgs=10")
    ovl = row("dist_overlap_L0",
              "on_nnz=1;off_nnz=1;local_nnz=2;eff_modeled=0.0")
    stm = row("streaming_refresh", STREAMING_DERIVED)
    aud = row("comm_audit_V_jacobi", COMM_AUDIT_DERIVED)
    aus = row("comm_audit_setup_L0_spgemm_AP", COMM_AUDIT_SETUP_DERIVED)
    assert run(tmp_path, [cyc], [cyc]) == 1              # all missing
    assert run(tmp_path, [cyc], [cyc, stm, aud, aus]) == 1   # split missing
    assert run(tmp_path, [cyc], [cyc, ovl]) == 1         # streaming missing
    assert run(tmp_path, [cyc], [cyc, ovl, stm]) == 1    # audit missing
    assert run(tmp_path, [cyc],
               [cyc, ovl, stm, aud]) == 1            # setup audit missing
    assert run(tmp_path, [cyc], [cyc, ovl, stm, aud, aus]) == 0


def test_comm_audit_rows_gate_model_agreement(tmp_path):
    """comm_audit_* rows: traced collective counts must equal the model's
    predicted counts with zero violations; comm_audit_setup_L* rows must
    show measured == static exchange counters."""
    good = row("comm_audit_V_jacobi", COMM_AUDIT_DERIVED)
    assert run(tmp_path, [good], [good]) == 0
    drift = [row("comm_audit_V_jacobi",
                 COMM_AUDIT_DERIVED.replace("expected=24", "expected=23"))]
    assert run(tmp_path, [good], drift) == 1
    disagree = [row("comm_audit_V_jacobi",
                    COMM_AUDIT_DERIVED.replace("agree=1", "agree=0"))]
    assert run(tmp_path, [good], disagree) == 1
    vio = [row("comm_audit_V_jacobi",
               COMM_AUDIT_DERIVED.replace("violations=0", "violations=2"))]
    assert run(tmp_path, [good], vio) == 1
    nan_c = [row("comm_audit_V_jacobi",
                 COMM_AUDIT_DERIVED.replace("collectives=24",
                                            "collectives=nan"))]
    assert run(tmp_path, [good], nan_c) == 1
    setup_good = row("comm_audit_setup_L0_spgemm_AP",
                     COMM_AUDIT_SETUP_DERIVED)
    assert run(tmp_path, [setup_good], [setup_good]) == 0
    setup_bad = [row("comm_audit_setup_L0_spgemm_AP",
                     COMM_AUDIT_SETUP_DERIVED.replace(
                         "runtime_intra_msgs=12", "runtime_intra_msgs=11"))]
    assert run(tmp_path, [setup_good], setup_bad) == 1
    setup_short = [row("comm_audit_setup_L0_spgemm_AP",
                       "strategy=standard;static_inter_msgs=2;violations=0")]
    assert run(tmp_path, [setup_good], setup_short) == 1


def test_streaming_rows_gate_refresh_beats_resetup(tmp_path):
    """streaming_* rows: refresh_us < resetup_us is the one gated timing
    ordering; counters must balance; iteration counts must stay finite."""
    good = row("streaming_refresh", STREAMING_DERIVED)
    assert run(tmp_path, [good], [good]) == 0
    # a refresh that costs as much as (or more than) the re-setup fails
    slow = [row("streaming_refresh",
                STREAMING_DERIVED.replace("refresh_us=16000.0",
                                          "refresh_us=99000.0"))]
    assert run(tmp_path, [good], slow) == 1
    # unbalanced solve accounting fails
    unbal = [row("streaming_refresh",
                 STREAMING_DERIVED.replace("cached=1", "cached=3"))]
    assert run(tmp_path, [good], unbal) == 1
    # a missing counter field fails
    short = [row("streaming_refresh",
                 "refresh_us=1.0;resetup_us=2.0;max_iters=7")]
    assert run(tmp_path, [good], short) == 1
    # non-finite iteration trajectory fails
    nan_it = [row("streaming_refresh",
                  STREAMING_DERIVED.replace("max_iters=7", "max_iters=nan"))]
    assert run(tmp_path, [good], nan_it) == 1


def test_modeled_us_must_be_finite(tmp_path):
    base = [row("dist_solve_auto_L0_spmv_A",
                "strategy=nap2;modeled_us=12.3;level=0;op=spmv_A")]
    assert run(tmp_path, base, base) == 0
    bad = [row("dist_solve_auto_L0_spmv_A",
               "strategy=nap2;modeled_us=nan;level=0;op=spmv_A")]
    assert run(tmp_path, base, bad) == 1


def test_committed_baselines_pass_against_themselves():
    root = pathlib.Path(__file__).parents[1]
    for name in ("BENCH_dist_solve.json", "BENCH_dist_setup.json"):
        path = root / name
        assert check_bench.main(["--baseline", str(path),
                                 "--new", str(path)]) == 0
