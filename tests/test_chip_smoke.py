"""``chip_smoke.py``: refuses to run without a TPU, and its phases pass on
the CPU at a tiny size with the device check stood in for."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _last_line_is_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]).get("ok") is True
    except (IndexError, ValueError, AttributeError):
        return False


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(SCRIPT)], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "[device] FAIL: no TPU attached" in out.stdout
    assert not _last_line_is_result(out.stdout)


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, SCRIPT.name], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert not _last_line_is_result(out.stdout)


def test_chip_smoke_phases_on_cpu(monkeypatch, capsys):
    jax = pytest.importorskip("jax")
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "N", 12)
    monkeypatch.setattr(chip_smoke, "wire_size", lambda: 10)
    monkeypatch.setattr(
        chip_smoke, "device_phase",
        lambda want: {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind, "count": 1})
    # keep this worker's compiles out of the checkout's cache
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "off")
    assert chip_smoke.main([]) == 0
    out = capsys.readouterr().out
    for phase in ("[problem]", "[session] L0", "[solves] pcg k=1",
                  "[solves] pcg k=8", "[reference]", "[served] solve 2",
                  "[served] update: refresh"):
        assert phase in out, out
    assert _last_line_is_result(out)


def test_load_client_never_imports_jax():
    """The CI server smoke runs the load generator as a second process
    beside the server; on a chip host that process must stay off JAX, or
    it would hold the chip the server needs."""
    from repro.amg.api import AMGConfig
    from repro.serve import ServerThread, TenantSpec

    code = ("import sys\n"
            "from benchmarks.serve_load import main\n"
            "rc = main(sys.argv[1:])\n"
            "assert 'jax' not in sys.modules, 'the load client imported jax'\n"
            "sys.exit(rc)\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                                       str(ROOT)]))
    tenants = {"alpha": TenantSpec(config=AMGConfig(), max_inflight=32)}
    with ServerThread(tenants) as srv:
        out = subprocess.run(
            [sys.executable, "-c", code, "--connect",
             f"{srv.host}:{srv.port}", "--tenants", "alpha", "--smoke",
             "--requests", "24", "--check"],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
