"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


@pytest.mark.parametrize("B,H,S,hd", [(1, 1, 128, 64), (2, 4, 256, 64),
                                      (1, 2, 512, 128), (2, 1, 128, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, S, hd, dtype, causal):
    q, k, v = (_rand(i, (B, H, S, hd), dtype) for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_cross_length():
    q = _rand(0, (1, 2, 64, 64), jnp.float32)
    k = _rand(1, (1, 2, 256, 64), jnp.float32)
    v = _rand(2, (1, 2, 256, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("Bt,L,D,N,chunk", [(1, 64, 8, 4, 16),
                                            (2, 128, 16, 8, 32),
                                            (2, 96, 4, 16, 32),
                                            (1, 32, 4096, 4, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_sweep(Bt, L, D, N, chunk, dtype):
    # D=4096 is wider than one channel tile: two tiles share each chunk
    dt = jax.nn.softplus(_rand(0, (Bt, L, D), jnp.float32)).astype(dtype)
    x = _rand(1, (Bt, L, D), dtype)
    A = -jnp.exp(_rand(2, (D, N), jnp.float32) * 0.3)
    B = _rand(3, (Bt, L, N), dtype)
    C = _rand(4, (Bt, L, N), dtype)
    out = mamba_scan(dt, x, A, B, C, chunk=chunk)
    ref = mamba_scan_ref(dt, x, A, B, C)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_mamba_scan_state_carries_across_chunks():
    """A constant decay ~1 accumulates across chunk boundaries; a kernel
    that reset state per chunk would diverge from the oracle."""
    Bt, L, D, N = 1, 128, 4, 2
    dt = jnp.full((Bt, L, D), 0.05)
    x = jnp.ones((Bt, L, D))
    A = -jnp.full((D, N), 0.01)
    B = jnp.ones((Bt, L, N))
    C = jnp.ones((Bt, L, N))
    out = mamba_scan(dt, x, A, B, C, chunk=16)
    ref = mamba_scan_ref(dt, x, A, B, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4)
    assert float(out[0, -1, 0]) > float(out[0, 15, 0])  # grows across chunks


@pytest.mark.parametrize("rows,d", [(128, 64), (256, 512), (64, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, d, dtype):
    x = _rand(0, (rows, d), dtype)
    scale = _rand(1, (d,), jnp.float32)
    out = rmsnorm(x, scale, block_rows=64)
    ref = rmsnorm_ref(x, scale)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
