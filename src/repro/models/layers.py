"""Primitive layers: norms, MLPs, embeddings, rotary position embeddings,
and the row addressing of decode caches.

Everything is functional: ``init_*`` returns a param pytree, ``apply``-style
functions are pure.  Parameters are stored in ``cfg.param_dtype`` and cast to
``cfg.dtype`` at use (bf16 compute on the TPU target).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig

__all__ = [
    "constrain_hidden",
    "dense_init",
    "dense",
    "rmsnorm_init",
    "rmsnorm",
    "layernorm_init",
    "layernorm",
    "norm_init",
    "norm_apply",
    "mlp_init",
    "mlp",
    "embedding_init",
    "embed",
    "unembed",
    "rope_frequencies",
    "apply_rope",
    "apply_mrope",
    "cross_entropy_loss",
    "cache_rows",
    "set_cache_rows",
]


# -- sharding anchor -----------------------------------------------------------


def constrain_hidden(x, cfg: ModelConfig):
    """Anchor the hidden stream [B, T, d] to ``cfg.act_sharding`` (if set).

    Applied at block boundaries so GSPMD propagation cannot drop the batch
    split between sharded-weight ops.  No-op when the anchor is unset or the
    rank disagrees (e.g. flattened MoE token streams).
    """
    if cfg.act_sharding is None or x.ndim != len(cfg.act_sharding):
        return x
    from jax.sharding import PartitionSpec

    return jax.lax.with_sharding_constraint(x, PartitionSpec(*cfg.act_sharding))


# -- linear -----------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, cfg: ModelConfig, bias: bool = False):
    scale = 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), cfg.param_dtype) * scale
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), cfg.param_dtype)
    return p


def dense(p, x, cfg: ModelConfig):
    y = x.astype(cfg.dtype) @ p["w"].astype(cfg.dtype)
    if "b" in p:
        y = y + p["b"].astype(cfg.dtype)
    return y


# -- norms --------------------------------------------------------------------


def rmsnorm_init(d: int, cfg: ModelConfig):
    return {"scale": jnp.ones((d,), cfg.param_dtype)}


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, cfg: ModelConfig):
    return {"scale": jnp.ones((d,), cfg.param_dtype), "bias": jnp.zeros((d,), cfg.param_dtype)}


def layernorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


def norm_init(d: int, cfg: ModelConfig):
    return layernorm_init(d, cfg) if cfg.norm == "layernorm" else rmsnorm_init(d, cfg)


def norm_apply(p, x, cfg: ModelConfig):
    return layernorm(p, x) if cfg.norm == "layernorm" else rmsnorm(p, x)


# -- MLP ----------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    keys = jax.random.split(key, 3)
    if cfg.mlp_act == "swiglu":
        return {
            "gate": dense_init(keys[0], cfg.d_model, d_ff, cfg),
            "up": dense_init(keys[1], cfg.d_model, d_ff, cfg),
            "down": dense_init(keys[2], d_ff, cfg.d_model, cfg),
        }
    return {
        "up": dense_init(keys[0], cfg.d_model, d_ff, cfg),
        "down": dense_init(keys[1], d_ff, cfg.d_model, cfg),
    }


def mlp(p, x, cfg: ModelConfig):
    if "gate" in p:
        h = jax.nn.silu(dense(p["gate"], x, cfg)) * dense(p["up"], x, cfg)
    else:
        h = jax.nn.gelu(dense(p["up"], x, cfg))
    return dense(p["down"], h, cfg)


# -- embeddings ----------------------------------------------------------------


def embedding_init(key, cfg: ModelConfig):
    emb = jax.random.normal(key, (cfg.vocab_size, cfg.d_model), cfg.param_dtype) * 0.02
    p = {"table": emb}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["head"] = jax.random.normal(k2, (cfg.d_model, cfg.vocab_size), cfg.param_dtype) * 0.02
    return p


def embed(p, tokens, cfg: ModelConfig):
    return p["table"].astype(cfg.dtype)[tokens]


def unembed(p, h, cfg: ModelConfig):
    if "head" in p:
        return h.astype(cfg.dtype) @ p["head"].astype(cfg.dtype)
    return h.astype(cfg.dtype) @ p["table"].astype(cfg.dtype).T


# -- rotary position embeddings -------------------------------------------------


def rope_frequencies(cfg: ModelConfig, positions):
    """inv-freq outer positions → (cos, sin) of shape [..., hd/2], fp32."""
    hd = cfg.hd
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [..., T, hd/2]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    # x: [..., T, n_heads, hd]; cos/sin: [..., T, hd/2] -> broadcast over heads
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(x, cos, sin):
    return _rotate(x, cos, sin).astype(x.dtype)


def apply_mrope(cfg: ModelConfig, x, positions3):
    """Qwen2-VL M-RoPE: three position streams (temporal, height, width).

    ``positions3``: [3, ..., T].  head_dim/2 frequency slots are split into
    ``mrope_sections`` (t, h, w); each section takes its angle from its own
    stream.  Text-only inputs pass identical streams, recovering 1-D RoPE.
    """
    hd = cfg.hd
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions3.astype(jnp.float32)[..., None] * inv  # [3, ..., T, hd/2]
    sec = jnp.cumsum(jnp.asarray(cfg.mrope_sections))
    idx = jnp.searchsorted(sec, jnp.arange(hd // 2), side="right")  # 0/1/2 per slot
    sel = jax.nn.one_hot(idx, 3, dtype=jnp.float32)  # [hd/2, 3]
    ang = jnp.einsum("s...j,js->...j", ang, sel)
    return apply_rope(x, jnp.cos(ang), jnp.sin(ang))


# -- decode-cache rows -------------------------------------------------------------
#
# A decode cache leaf is [*lead, B, ...]: ``lead`` is the layer axis of a
# stacked (scanned) block cache, or nothing.  ``at = (*lead_index, row0)``
# names the rows [row0, row0 + n) of one layer, the rows a decode step of
# n tokens reads and writes.


def _rows_start(buf, at):
    return tuple(at) + (0,) * (buf.ndim - len(at))


def cache_rows(buf, at, n: int):
    """Rows ``[at[-1], at[-1] + n)`` of the layer ``at[:-1]`` of ``buf``:
    [n, ...].  Whole rows of an unstacked cache come back unchanged."""
    lead = len(at) - 1
    sizes = (1,) * lead + (n,) + buf.shape[len(at):]
    return jax.lax.dynamic_slice(buf, _rows_start(buf, at), sizes).reshape(sizes[lead:])


def set_cache_rows(buf, at, rows):
    """``buf`` with ``rows`` [n, ...] written where :func:`cache_rows` reads."""
    rows = rows.astype(buf.dtype).reshape((1,) * (len(at) - 1) + rows.shape)
    return jax.lax.dynamic_update_slice(buf, rows, _rows_start(buf, at))


# -- loss -----------------------------------------------------------------------


def cross_entropy_loss(logits, labels, mask=None, z_loss: float = 0.0):
    """Mean token cross-entropy in fp32, optional z-loss, optional mask."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(logz)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
