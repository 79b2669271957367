"""Grouped-query attention with RoPE / M-RoPE, sliding windows, KV cache.

Three entry points used by the assembly code:

* ``attn_train``   — full-sequence causal (or bidirectional) attention.
* ``attn_prefill`` — full-sequence attention that ALSO fills the decode KV
  cache (one fused pass replaces T single-token steps — the serving
  prefill path).
* ``attn_decode``  — single-token decode against a pre-filled KV cache:
  one new K/V row per batch row written in place, at a shared or a per-row
  position, into one layer and a window of rows of a (stacked) cache.
* ``cross_attn``   — encoder-decoder cross attention (seamless backbone).

The prefill path routes through :mod:`repro.kernels.flash_attention.ops`
when ``use_flash`` — a compiled Pallas TPU kernel, with no fallback on other
backends.  Decode uses the jnp path (one query token: bandwidth-bound gather, no
kernel needed).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.layers import (
    apply_mrope,
    apply_rope,
    cache_rows,
    dense,
    dense_init,
    rope_frequencies,
)

__all__ = [
    "attn_init",
    "cross_attn_init",
    "attn_train",
    "attn_prefill",
    "attn_decode",
    "chunked_attention",
    "cross_attn",
    "init_kv_cache",
    "sdpa",
]


def attn_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, cfg, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, cfg, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, cfg, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, cfg),
    }


def cross_attn_init(key, cfg: ModelConfig):
    return attn_init(key, cfg.replace(qkv_bias=False))


def _split_heads(x, n_heads: int, hd: int):
    return x.reshape(*x.shape[:-1], n_heads, hd)


def _merge_heads(x):
    return x.reshape(*x.shape[:-2], -1)


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def sdpa(q, k, v, mask=None, scale: float | None = None):
    """Grouped-query scaled-dot-product attention.

    q: [B,T,H,hd]; k, v: [B,S,K,hd] with H = K·r.  The GQA repeat is folded
    into the einsum (grouped heads) instead of materialized with jnp.repeat:
    a repeated KV is r× HBM traffic in train and, under GSPMD, a broadcast
    the partitioner round-trips through entry-level all-gathers in decode
    (observed: 8 GB wire per decoded token on jamba).  f32 accumulation via
    preferred_element_type — an .astype on the inputs would materialize a 2x
    KV copy.
    """
    B, T, H, hd = q.shape
    K = k.shape[2]
    r = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, T, K, r, hd)
    logits = jnp.einsum(
        "btkrh,bskh->bkrts", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        # mask comes in as [..., T, S] broadcastable over [B,K,r,T,S]
        while mask.ndim < logits.ndim:
            mask = mask[:, None]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrts,bskh->btkrh", probs.astype(v.dtype), v)
    return out.reshape(B, T, H, hd)


def chunked_attention(
    q, k, v, *, causal: bool = True, window: int | None = None,
    scale: float | None = None, q_chunk: int = 512,
):
    """Memory-bounded attention: sequential ``lax.map`` over query chunks.

    Each chunk materializes only a [B, H, qc, S] score tile (exact softmax
    over the full key range — no online rescaling needed), so peak temp is
    T/qc times smaller than naive sdpa.  This is the lowering-honest stand-in
    for the Pallas flash kernel on paths the dry-run compiles (the kernel
    itself targets real TPU silicon); the backward differentiates through
    the map, rematerializing one chunk's scores at a time — the same working
    set as flash-backward.  q [B,T,H,hd]; k, v [B,S,H,hd] (GQA pre-repeated).
    """
    B, T, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    r = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, T)
    pad = (-T) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (T + pad) // q_chunk
    k_pos = jnp.arange(S)[None, :]

    def one_chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        qg = qs.reshape(B, q_chunk, K, r, hd)
        logits = jnp.einsum(
            "btkrh,bskh->bkrts", qg, k, preferred_element_type=jnp.float32
        ) * scale
        q_pos = i * q_chunk + jnp.arange(q_chunk)[:, None] + (S - T)
        mask = jnp.ones((q_chunk, S), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkrts,bskh->btkrh", probs.astype(v.dtype), v)
        return out.reshape(B, q_chunk, H, hd)  # [B,qc,H,hd]

    out = jax.lax.map(one_chunk, jnp.arange(nq))  # [nq, B, qc, H, hd]
    out = jnp.moveaxis(out, 0, 1).reshape(B, T + pad, H, hd)
    return out[:, :T]


# sequence length at/above which attn_train switches to the chunked path
CHUNKED_ATTN_THRESHOLD = 2048


def _causal_window_mask(T: int, S: int, window: int | None, causal: bool):
    """[1,1,T,S] boolean mask; S >= T positions are aligned at the end."""
    q_pos = jnp.arange(T)[:, None] + (S - T)
    k_pos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask[None, None]


def _project_qkv(p, x, cfg: ModelConfig, positions, mrope_positions=None):
    q = _split_heads(dense(p["wq"], x, cfg), cfg.num_heads, cfg.hd)
    k = _split_heads(dense(p["wk"], x, cfg), cfg.num_kv_heads, cfg.hd)
    v = _split_heads(dense(p["wv"], x, cfg), cfg.num_kv_heads, cfg.hd)
    if cfg.mrope and mrope_positions is not None:
        q = apply_mrope(cfg, q, mrope_positions)
        k = apply_mrope(cfg, k, mrope_positions)
    elif positions is not None:
        cos, sin = rope_frequencies(cfg, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def attn_train(
    p,
    x,
    cfg: ModelConfig,
    *,
    window: int | None = None,
    causal: bool = True,
    positions=None,
    mrope_positions=None,
    use_flash: bool = False,
):
    """Full-sequence attention.  x: [B, T, d]."""
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.arange(T)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions, mrope_positions)
    n_rep = cfg.num_heads // cfg.num_kv_heads
    if use_flash:
        from repro.kernels.flash_attention import ops as flash_ops

        out = flash_ops.flash_attention(
            q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), causal=causal, window=window
        )
    elif T >= CHUNKED_ATTN_THRESHOLD:
        out = chunked_attention(q, k, v, causal=causal, window=window)
    else:
        mask = _causal_window_mask(T, T, window, causal)
        out = sdpa(q, k, v, mask)
    return dense(p["wo"], _merge_heads(out), cfg)


# -- KV cache decode -----------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, window: int | None = None):
    """Cache for one attention layer.  Windowed layers allocate only the window."""
    L = min(max_len, window) if window else max_len
    shape = (batch, L, cfg.num_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def attn_prefill(p, x, cache, cfg: ModelConfig, *, window: int | None = None):
    """Full-sequence prefill that fills the decode KV cache in one pass.

    x: [B, T, d].  Returns (out [B, T, d], new_cache) with the cache in
    exactly the state T successive :func:`attn_decode` calls at indices
    ``0..T-1`` would leave it: slots ``i % L`` hold the last ``min(T, L)``
    tokens' projections, so the next decode call runs at ``index=T``.
    Attention itself is the fused ``attn_train`` math (one sdpa over the
    causal/windowed mask), not T bandwidth-bound single-token gathers.
    """
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions)
    mask = _causal_window_mask(T, T, window, causal=True)
    out = sdpa(q, k, v, mask)
    L = cache["k"].shape[1]
    kc = k.astype(cache["k"].dtype)
    vc = v.astype(cache["v"].dtype)
    if T <= L:
        new_k = cache["k"].at[:, :T].set(kc)
        new_v = cache["v"].at[:, :T].set(vc)
    else:
        # ring buffer: only the last L tokens survive T sequential writes
        idx = jnp.arange(T - L, T) % L
        new_k = cache["k"].at[:, idx].set(kc[:, T - L :])
        new_v = cache["v"].at[:, idx].set(vc[:, T - L :])
    return dense(p["wo"], _merge_heads(out), cfg), {"k": new_k, "v": new_v}


def attn_decode(
    p, x, cache, index, cfg: ModelConfig, *, window: int | None = None, at=(0,)
):
    """One-token decode.  x: [b, 1, d]; ``index``: the new token's position,
    a scalar or one per row ([b]).  ``cache`` leaves are [*lead, B, L, K, hd]
    and ``at`` picks the b rows x decodes (see :func:`cache_rows`; the
    default is every row of an unstacked cache).  Each row's new K/V is
    written in place at ``index % L`` (a ring buffer when windowed), then
    attention reads the rows.  Returns (out, new_cache), the whole cache."""
    b = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    positions = jnp.broadcast_to(index.reshape(-1, 1), (b, 1))
    mrope_positions = None
    if cfg.mrope:
        mrope_positions = jnp.broadcast_to(positions, (3, b, 1))
    q, k, v = _project_qkv(p, x, cfg, positions, mrope_positions)
    L = cache["k"].shape[-3]
    slot = index % L  # ring buffer when windowed; id otherwise
    new = {"k": _write_token(cache["k"], k, at, slot), "v": _write_token(cache["v"], v, at, slot)}
    # valid positions: for a ring buffer every slot < min(index+1, L) is valid
    valid = jnp.arange(L) < jnp.minimum(index + 1, L)[..., None]
    out = sdpa(
        q, cache_rows(new["k"], at, b), cache_rows(new["v"], at, b),
        mask=valid.reshape(-1, 1, 1, L),
    )
    out = dense(p["wo"], _merge_heads(out), cfg)
    return out, new


# positions a per-row decode write covers: one TPU lane-width tile
WRITE_TILE = 128


def _write_token(buf, t, at, pos):
    """Write the new token's [b, 1, K, hd] projection ``t`` of the rows at
    ``at`` into ``buf`` at position ``pos``: a scalar, or one per row.

    A shared position is one ``dynamic_update_slice``, which GSPMD splits
    over a sharded position axis.  Per-row positions are the serving
    engine's, on one TPU: it lays a cache out with the position axis minor
    (K·hd is narrower than its lanes), and a one-position write there
    would make XLA lay the whole cache out again around the decode loop,
    copying it twice a tick.  So each row rewrites, in place, the aligned
    tile of up to ``WRITE_TILE`` positions that holds its token."""
    *lead, row0 = at
    t = t.astype(buf.dtype)
    if pos.ndim == 0:
        t = t.reshape((1,) * len(lead) + t.shape)
        return jax.lax.dynamic_update_slice(buf, t, (*lead, row0, pos, 0, 0))
    L = buf.shape[-3]
    w = min(L, WRITE_TILE)
    for r in range(t.shape[0]):
        s0 = jnp.minimum(pos[r] // w * w, L - w)
        start = (*lead, row0 + r, s0, 0, 0)
        tile = jax.lax.dynamic_slice(buf, start, (1,) * (len(lead) + 1) + (w,) + t.shape[2:])
        hit = (jnp.arange(w) == pos[r] - s0)[:, None, None]
        buf = jax.lax.dynamic_update_slice(buf, jnp.where(hit, t[r], tile), start)
    return buf


# -- cross attention (enc-dec) ---------------------------------------------------


def cross_attn(p, x, memory, cfg: ModelConfig):
    """Decoder queries attend to encoder memory (no positions on k/v)."""
    q = _split_heads(dense(p["wq"], x, cfg), cfg.num_heads, cfg.hd)
    k = _split_heads(dense(p["wk"], memory, cfg), cfg.num_kv_heads, cfg.hd)
    v = _split_heads(dense(p["wv"], memory, cfg), cfg.num_kv_heads, cfg.hd)
    out = sdpa(q, k, v)
    return dense(p["wo"], _merge_heads(out), cfg)
