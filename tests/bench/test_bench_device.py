"""No TPU, too few chips, an unknown chip or no program: a non-zero exit
and no result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
import smallroot
import spec


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") for line in stdout.splitlines())


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg104.pcg1",
         "--seed", "5", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run_script(smallroot.REPO)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_checkout_of_only_the_benchmark_fails(tmp_path):
    """BENCHMARK.json and the files under its paths alone hold no system
    to measure."""
    shutil.copy(smallroot.REPO / "BENCHMARK.json", tmp_path)
    bench = json.loads((smallroot.REPO / "BENCHMARK.json").read_text())
    for path in bench["paths"]:
        shutil.copytree(smallroot.REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_script(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_unknown_device_kind_is_refused(tmp_path, capsys):
    root = smallroot.make_root(tmp_path, peaks_kind="TPU v5 lite")
    with smallroot.on_cpu(run):
        rc = run.main(["--workload", "small.pcg1", "--seed", "1",
                       "--seconds", "0.1", "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert rc != 0 and _no_result(out.out)
    assert "not in bench/peaks.json" in out.err


def test_configuration_without_fixed_iterations_is_refused(tmp_path,
                                                          capsys):
    root = smallroot.make_root(tmp_path)
    path = root / "bench" / "configs" / "small.json"
    conf = json.loads(path.read_text())
    del conf["fixed_iterations"]
    path.write_text(json.dumps(conf))
    with smallroot.on_cpu(run):
        rc = run.main(["--workload", "small.pcg1", "--seed", "1",
                       "--seconds", "0.1", "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert rc != 0 and _no_result(out.out)
    assert "fixed_iterations" in out.err


def test_too_few_chips_is_refused(tmp_path, capsys):
    root = smallroot.make_root(tmp_path)
    with smallroot.on_cpu(run):
        rc = run.main(["--workload", "small-2x2.pcg1", "--seed", "1",
                       "--seconds", "0.1", "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert rc != 0 and _no_result(out.out)


def test_peaks_table():
    v5e = spec.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    assert v5e["source"] == "Google Cloud documentation, TPU v5e"
    with pytest.raises(spec.SpecError):
        spec.peaks_for("cpu")
    table = json.loads((smallroot.BENCH / "peaks.json").read_text())
    assert all("source" in d for d in table["devices"].values())
