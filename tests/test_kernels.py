"""Kernel validation against pure-jnp oracles, swept over shapes/dtypes:
the local ELL product the distributed applies run, and the flash-attention
Pallas kernel in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.spmv.ref import ell_spmv_ref
from repro.kernels.spmv.spmv import ell_apply


# ------------------------------------------------------------------- spmv
def _random_ell(rng, n, m, k, dtype):
    cols = rng.integers(0, m, size=(n, k)).astype(np.int32)
    mask = rng.random((n, k)) < 0.3
    cols[mask] = -1
    vals = rng.standard_normal((n, k)).astype(dtype)
    vals[mask] = 0.0
    x = rng.standard_normal(m).astype(dtype)
    return jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x)


@pytest.mark.parametrize("n,m,k", [(8, 16, 3), (100, 64, 7), (257, 300, 27),
                                   (1024, 512, 9)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_spmv_kernel_matches_ref(n, m, k, dtype):
    rng = np.random.default_rng(n + k)
    cols, vals, x = _random_ell(rng, n, m, k, np.float32)
    vals = vals.astype(jnp.dtype(dtype))
    x = x.astype(jnp.dtype(dtype))
    ref = ell_spmv_ref(cols, vals, x)
    out = ell_apply(cols, vals, x)
    tol = 1e-5 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_spmv_matches_csr_matvec():
    from repro.amg.problems import laplace_3d_7pt
    A = laplace_3d_7pt(8)
    K = int(np.diff(A.indptr).max())
    n = A.nrows
    cols = np.full((n, K), -1, dtype=np.int32)
    vals = np.zeros((n, K), dtype=np.float32)
    for i in range(n):
        s = slice(int(A.indptr[i]), int(A.indptr[i + 1]))
        cols[i, : s.stop - s.start] = A.indices[s]
        vals[i, : s.stop - s.start] = A.data[s]
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    y = ell_apply(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), A.matvec(x), rtol=2e-4, atol=2e-4)


# -------------------------------------------------------------- attention
@pytest.mark.parametrize("b,hq,hkv,sq,skv,d", [
    (1, 4, 4, 64, 64, 32),       # MHA
    (2, 8, 2, 128, 128, 64),     # GQA 4:1
    (1, 14, 2, 96, 96, 64),      # qwen2-style 7:1, non-pow2 seq
    (1, 4, 1, 64, 64, 128),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, hq, hkv, sq, skv, d, dtype):
    rng = np.random.default_rng(hq * sq)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dtype=dtype)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [16, 48, 128])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.standard_normal((1, 4, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True, window=window)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_decode_alignment():
    """Sq < Skv (queries right-aligned): the KV-cache decode case."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 4, 8, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 4, 96, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 4, 96, 32)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_attention_wrapper_time_major():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 64, 8, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 64, 2, 32)), jnp.float32)
    out_k = attention(q, k, v, use_kernel=True, block_q=32, block_k=32)
    out_r = attention(q, k, v, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
    assert out_k.shape == q.shape
