"""JAX's persistent compilation cache lands where the entry points say."""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def test_cache_defaults_to_checkout_dir(monkeypatch):
    jax = pytest.importorskip("jax")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert CACHE_DIR == SRC.parent / ".jax_cache"


def test_cache_follows_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a compile is written there."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir())
