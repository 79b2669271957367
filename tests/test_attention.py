"""Attention layer: GQA grouping, chunked path, windows, M-RoPE, decode."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    _causal_window_mask,
    attn_decode,
    attn_init,
    attn_train,
    chunked_attention,
    init_kv_cache,
    sdpa,
)
from repro.models.common import ModelConfig


def _cfg(**kw):
    base = dict(
        name="t", family="dense", num_layers=2, d_model=64,
        num_heads=8, num_kv_heads=2, d_ff=128, vocab_size=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return ModelConfig(**base)


def _repeat_ref(q, k, v, causal=True, window=None):
    H, K = q.shape[2], k.shape[2]
    kr = jnp.repeat(k, H // K, axis=2)
    vr = jnp.repeat(v, H // K, axis=2)
    T, S = q.shape[1], k.shape[1]
    logits = jnp.einsum("bthd,bshd->bhts", q, kr) / math.sqrt(q.shape[-1])
    mask = _causal_window_mask(T, S, window, causal)
    logits = jnp.where(mask, logits, -1e30)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(logits, -1), vr)


@pytest.mark.parametrize("H,K", [(8, 2), (8, 8), (6, 3), (4, 1)])
def test_sdpa_grouped_equals_repeated(H, K):
    key = jax.random.PRNGKey(0)
    B, T, hd = 2, 32, 16
    q = jax.random.normal(key, (B, T, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, K, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, K, hd))
    out = sdpa(q, k, v, _causal_window_mask(T, T, None, True))
    ref = _repeat_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("window", [None, 8, 24])
@pytest.mark.parametrize("q_chunk", [8, 16, 32])
def test_chunked_equals_sdpa(window, q_chunk):
    key = jax.random.PRNGKey(1)
    B, T, H, K, hd = 1, 32, 4, 2, 16
    q = jax.random.normal(key, (B, T, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, K, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, K, hd))
    out = chunked_attention(q, k, v, causal=True, window=window, q_chunk=q_chunk)
    ref = _repeat_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_chunked_nondivisible_padding():
    key = jax.random.PRNGKey(2)
    B, T, H, hd = 1, 23, 2, 8
    q = jax.random.normal(key, (B, T, H, hd))
    out = chunked_attention(q, q, q, causal=True, q_chunk=8)
    ref = _repeat_ref(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_decode_matches_train_step_by_step():
    """Token-by-token decode must reproduce the full-sequence forward."""
    cfg = _cfg()
    p = attn_init(jax.random.PRNGKey(3), cfg)
    B, T = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, cfg.d_model)) * 0.1
    full = attn_train(p, x, cfg)
    cache = init_kv_cache(cfg, B, max_len=T)
    outs = []
    for i in range(T):
        o, cache = attn_decode(p, x[:, i : i + 1], cache, i, cfg)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=1e-4)


def test_windowed_decode_ring_buffer():
    """A windowed layer's ring buffer must agree with full attention under
    the same window mask."""
    cfg = _cfg()
    W = 4
    p = attn_init(jax.random.PRNGKey(5), cfg)
    B, T = 1, 10
    x = jax.random.normal(jax.random.PRNGKey(6), (B, T, cfg.d_model)) * 0.1
    full = attn_train(p, x, cfg, window=W)
    cache = init_kv_cache(cfg, B, max_len=T, window=W)
    assert cache["k"].shape[1] == W  # ring buffer allocates only the window
    outs = []
    for i in range(T):
        o, cache = attn_decode(p, x[:, i : i + 1], cache, i, cfg, window=W)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=1e-4)


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize(
    "window,max_len,index",
    [(None, 12, [0, 5, 11]), (4, 12, [2, 4, 9]), (None, 300, [3, 130, 299])],
    ids=["full", "ring", "past-one-write-tile"],
)
def test_decode_per_row_index_equals_scalar_calls(window, max_len, index, stacked):
    """``attn_decode`` with a [B] index equals B scalar-index calls, one per
    row on its own cache (to the round-off of a B-row matmul against a
    one-row one): the ring buffer at index >= L included, and, in a stacked
    cache, rows [2, 2 + B) of layer 1 with every other row kept bit for bit."""
    cfg = _cfg()
    p = attn_init(jax.random.PRNGKey(8), cfg)
    B = len(index)
    x = jax.random.normal(jax.random.PRNGKey(9), (B, 1, cfg.d_model))
    rows = {
        n: jax.random.normal(jax.random.PRNGKey(10 + i), c.shape)
        for i, (n, c) in enumerate(init_kv_cache(cfg, B, max_len, window).items())
    }
    if stacked:  # leaves [layers, B + 3, L, K, hd]; x decodes layer 1, rows 2..
        full = {n: jax.random.normal(jax.random.PRNGKey(20), (2, B + 3) + c.shape[1:])
                for n, c in rows.items()}
        cache = {n: full[n].at[1, 2 : 2 + B].set(rows[n]) for n in rows}
        at = (1, 2)
    else:
        cache, at = rows, (0,)
    decode = jax.jit(lambda x, c, i, at: attn_decode(p, x, c, i, cfg, window=window, at=at))
    out, new = decode(x, cache, jnp.asarray(index), at)
    for r, i in enumerate(index):
        row = {n: c[r : r + 1] for n, c in rows.items()}
        o, want = decode(x[r : r + 1], row, i, (0,))
        np.testing.assert_allclose(np.asarray(out[r : r + 1]), np.asarray(o), atol=1e-5)
        for n in rows:
            got = new[n][1, 2 + r] if stacked else new[n][r]
            np.testing.assert_allclose(np.asarray(got), np.asarray(want[n][0]), atol=1e-5)
    if stacked:
        for n in rows:
            kept = np.asarray(new[n]).copy()
            kept[1, 2 : 2 + B] = np.asarray(full[n][1, 2 : 2 + B])
            np.testing.assert_array_equal(kept, np.asarray(full[n]))


def test_mrope_reduces_to_rope_on_equal_streams():
    """Identical (t, h, w) position streams must equal plain 1-D RoPE."""
    from repro.models.layers import apply_mrope, apply_rope, rope_frequencies

    cfg = _cfg(mrope=True, mrope_sections=(2, 3, 3), head_dim=16)
    B, T, H = 2, 8, 4
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, H, 16))
    pos = jnp.arange(T)[None, :].repeat(B, 0)
    pos3 = jnp.broadcast_to(pos[None], (3, B, T))
    out_m = apply_mrope(cfg, x, pos3)
    cos, sin = rope_frequencies(cfg, pos)
    out_r = apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_r), atol=1e-5)
