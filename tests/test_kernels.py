"""Pallas kernel sweeps (interpret mode on CPU) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ref as flash_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.ssd_scan import ref as ssd_ref
from repro.kernels.ssd_scan.kernel import ssd_chunked_pallas


def _mk_qkv(key, B, T, S, H, hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, hd), dtype)
    k = jax.random.normal(kk, (B, S, H, hd), dtype)
    v = jax.random.normal(kv, (B, S, H, hd), dtype)
    return q, k, v


FLASH_CASES = [
    # (T, S, hd, causal, window, block_q, block_k, dtype, tol)
    (64, 64, 32, True, None, 32, 32, jnp.float32, 2e-6),
    (128, 128, 64, True, None, 64, 64, jnp.float32, 2e-6),
    (96, 96, 32, True, None, 32, 32, jnp.float32, 2e-6),  # padding path
    (64, 64, 32, False, None, 32, 32, jnp.float32, 2e-6),
    (128, 128, 32, True, 48, 32, 32, jnp.float32, 2e-6),  # sliding window
    (64, 64, 64, True, None, 32, 32, jnp.bfloat16, 2e-2),
    (64, 64, 32, True, 16, 32, 16, jnp.bfloat16, 2e-2),
]


@pytest.mark.parametrize("T,S,hd,causal,window,bq,bk,dtype,tol", FLASH_CASES)
def test_flash_attention_vs_oracle(T, S, hd, causal, window, bq, bk, dtype, tol):
    B, H = 2, 3
    q, k, v = _mk_qkv(jax.random.PRNGKey(0), B, T, S, H, hd, dtype)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, window=window,
        block_q=bq, block_k=bk, interpret=True,
    )
    ref = flash_ref.attention(q, k, v, causal=causal, window=window)
    ref = ref.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


SSD_CASES = [
    # (B, T, H, P, N, chunk, dtype, tol)
    (2, 32, 4, 16, 8, 8, jnp.float32, 1e-4),
    (1, 64, 2, 32, 16, 16, jnp.float32, 1e-4),
    (2, 64, 4, 64, 128, 32, jnp.float32, 1e-3),  # production-ish N
    (2, 32, 4, 16, 8, 8, jnp.bfloat16, 5e-2),
    (1, 16, 8, 8, 4, 16, jnp.float32, 1e-4),  # chunk == T
]


@pytest.mark.parametrize("B,T,H,P,N,chunk,dtype,tol", SSD_CASES)
def test_ssd_kernel_vs_sequential_oracle(B, T, H, P, N, chunk, dtype, tol):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H), jnp.float32))
    A = -jnp.abs(jax.random.normal(ks[2], (H,), jnp.float32)) - 0.1
    Bm = jax.random.normal(ks[3], (B, T, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, T, N), jnp.float32)
    out = ssd_chunked_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_ref.ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.slow
def test_ssd_chunked_jnp_matches_sequential():
    """The chunked jnp path (what models run on CPU) vs the recurrence."""
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 5)
    B, T, H, P, N = 2, 48, 3, 16, 8
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.abs(jax.random.normal(ks[2], (H,))) - 0.1
    Bm = jax.random.normal(ks[3], (B, T, 1, N))
    Cm = jax.random.normal(ks[4], (B, T, 1, N))
    out = ssd_ref.ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    ref = ssd_ref.ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_flash_wrapper_layout_roundtrip():
    from repro.kernels.flash_attention.ops import flash_attention

    B, T, H, hd = 2, 64, 4, 32
    q, k, v = _mk_qkv(jax.random.PRNGKey(3), B, T, T, H, hd, jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = flash_ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6, rtol=2e-6)
