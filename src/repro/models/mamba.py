"""Mamba2 (SSD — state-space duality) block, arXiv:2405.21060.

Train path uses the chunked SSD algorithm (quadratic within a chunk,
linear recurrence across chunks) — the math lives in
:mod:`repro.kernels.ssd_scan.ref` (pure jnp oracle) with a Pallas TPU kernel
in the same package; decode carries an explicit ``[B, H, P, N]`` recurrent
state, the SSM analogue of a KV cache (O(1) per token — this is why the
SSM/hybrid architectures run the ``long_500k`` shape natively).

Structure (minimal official mamba2):
  in_proj -> (z, x, B, C, dt); causal depthwise conv over (x, B, C);
  dt = softplus(dt + bias); A = -exp(A_log);
  y = SSD(x, dt, A, B, C) + D * x;  y = rmsnorm(y * silu(z)); out_proj.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.layers import cache_rows, rmsnorm, set_cache_rows

__all__ = ["mamba_init", "mamba_train", "mamba_prefill", "mamba_decode", "init_ssm_cache"]


def _dims(cfg: ModelConfig):
    d_in = cfg.d_model * cfg.ssm_expand
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    G = 1  # single B/C group (standard mamba2 default)
    return d_in, H, P, N, G


def mamba_init(key, cfg: ModelConfig):
    d = cfg.d_model
    d_in, H, P, N, G = _dims(cfg)
    conv_dim = d_in + 2 * G * N
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_in + 2 * G * N + H
    scale = 1.0 / math.sqrt(d)
    dt = jnp.exp(
        jax.random.uniform(ks[2], (H,), jnp.float32) * (math.log(0.1) - math.log(0.001))
        + math.log(0.001)
    )
    return {
        "in_proj": jax.random.normal(ks[0], (d, proj_out), cfg.param_dtype) * scale,
        "conv_w": jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_dim), cfg.param_dtype)
        * (1.0 / math.sqrt(cfg.ssm_conv_width)),
        "conv_b": jnp.zeros((conv_dim,), cfg.param_dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.param_dtype),  # inv softplus
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)).astype(cfg.param_dtype),
        "D": jnp.ones((H,), cfg.param_dtype),
        "norm_scale": jnp.ones((d_in,), cfg.param_dtype),
        "out_proj": jax.random.normal(ks[3], (d_in, d), cfg.param_dtype) * (1.0 / math.sqrt(d_in)),
    }


def _split_proj(cfg: ModelConfig, zxbcdt):
    d_in, H, P, N, G = _dims(cfg)
    z, xx, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + G * N, 2 * d_in + 2 * G * N], axis=-1
    )
    return z, xx, Bc, Cc, dt


def _causal_conv(seq, w, b):
    """Depthwise causal conv along time.  seq: [B, T, C]; w: [K, C]."""
    K = w.shape[0]
    pad = jnp.pad(seq, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + seq.shape[1], :] * w[i] for i in range(K))
    return out + b


def mamba_train(p, x, cfg: ModelConfig, use_kernel: bool = False):
    """x: [B, T, d] -> [B, T, d] (full-sequence chunked SSD)."""
    from repro.kernels.ssd_scan import ref as ssd_ref
    from repro.kernels.ssd_scan.kernel import ssd_chunked_pallas

    B_, T, d = x.shape
    d_in, H, P, N, G = _dims(cfg)
    dt_f = cfg.dtype
    zxbcdt = x.astype(dt_f) @ p["in_proj"].astype(dt_f)
    z, xx, Bc, Cc, dtv = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([xx, Bc, Cc], axis=-1)
    conv_out = jax.nn.silu(
        _causal_conv(conv_in, p["conv_w"].astype(dt_f), p["conv_b"].astype(dt_f))
    )
    xx, Bc, Cc = jnp.split(conv_out, [d_in, d_in + G * N], axis=-1)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # [B,T,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # [H]

    xh = xx.reshape(B_, T, H, P)
    Bh = Bc.reshape(B_, T, G, N)
    Ch = Cc.reshape(B_, T, G, N)
    fn = ssd_chunked_pallas if use_kernel else ssd_ref.ssd_chunked
    y = fn(xh, dtv, A, Bh, Ch, chunk=cfg.ssm_chunk)  # [B,T,H,P]
    y = y + xh * p["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B_, T, d_in)
    y = rmsnorm({"scale": p["norm_scale"]}, y * jax.nn.silu(z))
    return y @ p["out_proj"].astype(y.dtype)


# -- decode (recurrent) -----------------------------------------------------------


def init_ssm_cache(cfg: ModelConfig, batch: int):
    d_in, H, P, N, G = _dims(cfg)
    return {
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, d_in + 2 * G * N), cfg.dtype),
    }


def mamba_prefill(p, x, cache, cfg: ModelConfig):
    """Full-sequence prefill of the recurrent state in ONE compiled program.

    x: [B, T, d] -> (y [B, T, d], new cache).  A ``lax.scan`` of the
    one-token recurrence over time — bitwise-equal to stepping
    :func:`mamba_decode` token by token, but fused so serving prefill
    compiles and dispatches once instead of T times.  (The chunked-SSD
    train path cannot substitute here: it does not expose the final
    recurrent state the decode loop needs.)
    """

    def step(c, xt):
        y, nc = mamba_decode(p, xt[:, None], c, cfg)
        return nc, y[:, 0]

    new_cache, ys = jax.lax.scan(step, cache, jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(ys, 0, 1), new_cache


def mamba_decode(p, x, cache, cfg: ModelConfig, *, at=(0,)):
    """One-token recurrent step.  x: [B, 1, d] -> (y [B, 1, d], new cache).
    ``at`` picks the cache rows x steps, as in ``attention.attn_decode``;
    their state and conv window are read, then written back whole."""
    B_, _, d = x.shape
    d_in, H, P, N, G = _dims(cfg)
    dt_f = cfg.dtype
    zxbcdt = x[:, 0].astype(dt_f) @ p["in_proj"].astype(dt_f)
    z, xx, Bc, Cc, dtv = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([xx, Bc, Cc], axis=-1)  # [B, C]
    conv = cache_rows(cache["conv"], at, B_)
    window = jnp.concatenate([conv, conv_in[:, None]], axis=1)  # [B, K, C]
    w = p["conv_w"].astype(dt_f)
    conv_out = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"].astype(dt_f))
    xx, Bc, Cc = jnp.split(conv_out, [d_in, d_in + G * N], axis=-1)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # [B,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    xh = xx.reshape(B_, H, P).astype(jnp.float32)
    Bh = Bc.reshape(B_, G, N).astype(jnp.float32)[:, 0]  # G=1
    Ch = Cc.reshape(B_, G, N).astype(jnp.float32)[:, 0]
    decay = jnp.exp(dtv * A)  # [B,H]
    state = cache_rows(cache["state"], at, B_) * decay[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dtv, xh, Bh
    )
    y = jnp.einsum("bhpn,bn->bhp", state, Ch) + xh * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(B_, 1, d_in).astype(dt_f)
    y = rmsnorm({"scale": p["norm_scale"]}, y * jax.nn.silu(z[:, None]))
    y = y @ p["out_proj"].astype(y.dtype)
    return y, {
        "state": set_cache_rows(cache["state"], at, state),
        "conv": set_cache_rows(cache["conv"], at, window[:, 1:]),
    }
