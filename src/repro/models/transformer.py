"""Model assembly: decoder-only (dense / MoE / SSM / hybrid) and enc-dec.

Layers are grouped by the *periodic pattern* of their specs (e.g. gemma3's
5-local:1-global window cycle, jamba's 8-layer mamba/attention interleave
with MoE every other layer) and executed with ``jax.lax.scan`` over stacked
identical blocks.  This keeps HLO size and compile time O(pattern) instead
of O(num_layers) — essential when 48–61-layer configs are lowered 80+ times
by the dry-run matrix.  A ``prefix`` of irregular leading layers (kimi-k2's
first dense layer) is unrolled in Python.

All entry points are pure functions over a params pytree.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba as mamba_mod
from repro.models.common import LayerSpec, ModelConfig, layer_specs
from repro.models.layers import (
    constrain_hidden,
    cross_entropy_loss,
    embed,
    embedding_init,
    mlp,
    mlp_init,
    norm_apply,
    norm_init,
    unembed,
)
from repro.models.moe import moe_apply, moe_apply_grouped, moe_init

__all__ = [
    "Structure",
    "structure",
    "init_layer",
    "apply_layer_train",
    "apply_layer_decode",
    "init_decoder",
    "decoder_forward",
    "decoder_loss",
    "init_decode_cache",
    "decode_step",
    "apply_layer_prefill",
    "prefill_with_cache",
    "init_encdec",
    "encdec_forward",
    "encdec_loss",
    "MOE_AUX_WEIGHT",
    "MOE_Z_WEIGHT",
]

MOE_AUX_WEIGHT = 0.01
MOE_Z_WEIGHT = 1e-4


# ---------------------------------------------------------------------------
# Periodic structure detection
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Structure:
    prefix: tuple[LayerSpec, ...]  # irregular leading layers (unrolled)
    pattern: tuple[LayerSpec, ...]  # repeating block (scanned)
    n_blocks: int

    @property
    def num_layers(self) -> int:
        return len(self.prefix) + len(self.pattern) * self.n_blocks


def _sig(s: LayerSpec) -> tuple:
    return (s.kind, s.moe, s.window)


def structure(
    cfg: ModelConfig, num_layers: int | None = None, prefix_len: int | None = None
) -> Structure:
    specs = layer_specs(cfg, num_layers)
    if prefix_len is None:
        prefix_len = getattr(cfg, "first_k_dense", 0) or 0
    body = specs[prefix_len:]
    n = len(body)
    sigs = [_sig(s) for s in body]
    for p in range(1, n + 1):
        if n % p == 0 and all(sigs[i] == sigs[i % p] for i in range(n)):
            return Structure(tuple(specs[:prefix_len]), tuple(body[:p]), n // p)
    return Structure(tuple(specs[:prefix_len]), tuple(body), 1)


# ---------------------------------------------------------------------------
# One layer
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, spec: LayerSpec, cross: bool = False):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"ln1": norm_init(cfg.d_model, cfg)}
    if spec.kind == "attn":
        p["attn"] = attn.attn_init(ks[0], cfg)
    else:
        p["mamba"] = mamba_mod.mamba_init(ks[0], cfg)
    if cross:
        p["ln_x"] = norm_init(cfg.d_model, cfg)
        p["xattn"] = attn.cross_attn_init(ks[1], cfg)
    if spec.moe:
        p["ln2"] = norm_init(cfg.d_model, cfg)
        p["moe"] = moe_init(ks[2], cfg)
    elif cfg.d_ff > 0:
        p["ln2"] = norm_init(cfg.d_model, cfg)
        p["mlp"] = mlp_init(ks[3], cfg)
    return p


@jax.named_scope("mlp")
def _ffn(p, x, cfg: ModelConfig, spec: LayerSpec, capacity_factor: float | None = None):
    """FFN sublayer; returns (delta, aux_losses)."""
    zero = jnp.zeros((), jnp.float32)
    if spec.moe:
        h = norm_apply(p["ln2"], x, cfg)
        B, T, d = h.shape
        if cfg.act_sharding is not None:
            # distributed: per-group (per-batch-row) dispatch — see
            # moe_apply_grouped for why flat dispatch is catastrophic
            # under 2-D expert sharding
            y, aux = moe_apply_grouped(p["moe"], h, cfg)
            return y, (aux["load_balance"], aux["router_z"])
        y, aux = moe_apply(p["moe"], h.reshape(B * T, d), cfg, capacity_factor)
        return y.reshape(B, T, d), (aux["load_balance"], aux["router_z"])
    if "mlp" in p:
        return mlp(p["mlp"], norm_apply(p["ln2"], x, cfg), cfg), (zero, zero)
    return jnp.zeros_like(x), (zero, zero)


def apply_layer_train(
    p, x, cfg: ModelConfig, spec: LayerSpec,
    *, causal: bool = True, memory=None, positions=None, mrope_positions=None,
    use_flash: bool = False,
):
    h = norm_apply(p["ln1"], x, cfg)
    if spec.kind == "attn":
        with jax.named_scope("attention"):
            h = attn.attn_train(
                p["attn"], h, cfg,
                window=spec.window, causal=causal,
                positions=positions, mrope_positions=mrope_positions, use_flash=use_flash,
            )
    else:
        with jax.named_scope("mamba"):
            h = mamba_mod.mamba_train(p["mamba"], h, cfg)
    x = x + h
    if memory is not None and "xattn" in p:
        x = x + attn.cross_attn(p["xattn"], norm_apply(p["ln_x"], x, cfg), memory, cfg)
    delta, aux = _ffn(p, x, cfg, spec)
    return x + delta, aux


def init_layer_cache(
    cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int, cross: bool = False
):
    if spec.kind == "attn":
        c = {"kv": attn.init_kv_cache(cfg, batch, max_len, window=spec.window)}
    else:
        c = {"ssm": mamba_mod.init_ssm_cache(cfg, batch)}
    if cross:
        c["xkv"] = None  # filled at prefill with encoder memory projections
    return c


def apply_layer_decode(
    p, x, cache, index, cfg: ModelConfig, spec: LayerSpec, *, memory=None, at=(0,),
):
    """One-token layer step on the cache rows ``at`` picks (see
    ``attention.attn_decode``); returns (x, the whole new cache).  MoE
    dispatch is dropless, so a row's token never depends on the rows
    decoded beside it."""
    h = norm_apply(p["ln1"], x, cfg)
    new_cache = dict(cache)
    if spec.kind == "attn":
        with jax.named_scope("attention"):
            h, new_kv = attn.attn_decode(
                p["attn"], h, cache["kv"], index, cfg, window=spec.window, at=at
            )
        new_cache["kv"] = new_kv
    else:
        with jax.named_scope("mamba"):
            h, new_ssm = mamba_mod.mamba_decode(p["mamba"], h, cache["ssm"], cfg, at=at)
        new_cache["ssm"] = new_ssm
    x = x + h
    if memory is not None and "xattn" in p:
        x = x + attn.cross_attn(p["xattn"], norm_apply(p["ln_x"], x, cfg), memory, cfg)
    dropless = cfg.num_experts / cfg.num_experts_per_tok if spec.moe else None
    delta, _ = _ffn(p, x, cfg, spec, capacity_factor=dropless)
    return x + delta, new_cache


# ---------------------------------------------------------------------------
# Decoder-only model
# ---------------------------------------------------------------------------


def init_decoder(key, cfg: ModelConfig):
    st = structure(cfg)
    ks = jax.random.split(key, 4 + len(st.prefix))
    params: dict[str, Any] = {"embed": embedding_init(ks[0], cfg)}
    params["prefix"] = [
        init_layer(ks[2 + i], cfg, spec) for i, spec in enumerate(st.prefix)
    ]
    if st.n_blocks:
        block_keys = jax.random.split(ks[1], st.n_blocks)

        def one_block(k):
            kk = jax.random.split(k, len(st.pattern))
            return [init_layer(kk[i], cfg, spec) for i, spec in enumerate(st.pattern)]

        params["blocks"] = jax.vmap(one_block)(block_keys)  # leaves: [n_blocks, ...]
    params["final_norm"] = norm_init(cfg.d_model, cfg)
    return params


def _hidden_from_inputs(params, cfg: ModelConfig, tokens, embeds):
    if embeds is not None:
        return embeds.astype(cfg.dtype)
    return embed(params["embed"], tokens, cfg)


def decoder_forward(
    params, cfg: ModelConfig,
    tokens=None, embeds=None,
    *, positions=None, mrope_positions=None, use_flash: bool = False,
    last_only: bool = False,
):
    """Full-sequence forward.  Returns (logits, aux_metrics).

    ``last_only=True`` unembeds only the final position — the prefill path;
    it avoids materializing [B, T, V] logits (for a 32k-token prefill of a
    163k-vocab model that tensor alone would dwarf HBM).
    """
    st = structure(cfg)
    x = constrain_hidden(_hidden_from_inputs(params, cfg, tokens, embeds), cfg)
    aux_lb = jnp.zeros((), jnp.float32)
    aux_z = jnp.zeros((), jnp.float32)
    for p, spec in zip(params["prefix"], st.prefix):
        x, (lb, z) = apply_layer_train(
            p, x, cfg, spec,
            positions=positions, mrope_positions=mrope_positions, use_flash=use_flash,
        )
        x = constrain_hidden(x, cfg)
        aux_lb, aux_z = aux_lb + lb, aux_z + z
    if st.n_blocks:
        def block_body(x, block_params):
            lb = jnp.zeros((), jnp.float32)
            z = jnp.zeros((), jnp.float32)
            for i, spec in enumerate(st.pattern):
                x, (l, zz) = apply_layer_train(
                    block_params[i], x, cfg, spec,
                    positions=positions, mrope_positions=mrope_positions,
                    use_flash=use_flash,
                )
                x = constrain_hidden(x, cfg)
                lb, z = lb + l, z + zz
            return x, lb, z

        body = jax.checkpoint(block_body) if cfg.remat_blocks else block_body

        def block_step(carry, block_params):
            x, lb, z = carry
            x, l, zz = body(x, block_params)
            return (x, lb + l, z + zz), None

        (x, aux_lb, aux_z), _ = jax.lax.scan(
            block_step, (x, aux_lb, aux_z), params["blocks"]
        )
    if last_only:
        x = x[:, -1:, :]
    x = norm_apply(params["final_norm"], x, cfg)
    logits = unembed(params["embed"], x, cfg)
    return logits, {"moe_load_balance": aux_lb, "moe_router_z": aux_z}


def decoder_loss(
    params, cfg: ModelConfig, tokens=None, labels=None, embeds=None,
    *, mask=None, positions=None, mrope_positions=None, use_flash: bool = False,
):
    logits, aux = decoder_forward(
        params, cfg, tokens, embeds,
        positions=positions, mrope_positions=mrope_positions, use_flash=use_flash,
    )
    loss = cross_entropy_loss(logits, labels, mask=mask)
    total = loss + MOE_AUX_WEIGHT * aux["moe_load_balance"] + MOE_Z_WEIGHT * aux["moe_router_z"]
    metrics = {"ce_loss": loss, **aux}
    return total, metrics


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int):
    st = structure(cfg)
    cache = {
        "prefix": [init_layer_cache(cfg, spec, batch, max_len) for spec in st.prefix],
    }
    if st.n_blocks:
        def one_block(_):
            return [init_layer_cache(cfg, spec, batch, max_len) for spec in st.pattern]

        cache["blocks"] = jax.vmap(one_block)(jnp.arange(st.n_blocks))
    return cache


def apply_layer_prefill(p, x, cache, cfg: ModelConfig, spec: LayerSpec):
    """Full-sequence layer forward that also fills the layer's decode cache
    (self-attention/SSM families only — no cross attention)."""
    h = norm_apply(p["ln1"], x, cfg)
    new_cache = dict(cache)
    if spec.kind == "attn":
        with jax.named_scope("attention"):
            h, new_kv = attn.attn_prefill(p["attn"], h, cache["kv"], cfg, window=spec.window)
        new_cache["kv"] = new_kv
    else:
        with jax.named_scope("mamba"):
            h, new_ssm = mamba_mod.mamba_prefill(p["mamba"], h, cache["ssm"], cfg)
        new_cache["ssm"] = new_ssm
    x = x + h
    delta, _ = _ffn(p, x, cfg, spec)
    return x + delta, new_cache


def prefill_with_cache(params, cfg: ModelConfig, cache, tokens=None, embeds=None):
    """Fused serving prefill: one forward pass over the whole prompt fills
    every layer's decode cache AND returns the last position's logits.

    tokens [B, T] (or embeds [B, T, d]).  Returns (logits [B, 1, V],
    new_cache); the next :func:`decode_step` runs at ``index = T``.  This
    replaces the T-step token-by-token cache warmup the serving example used
    to do — same cache contents (see ``attn_prefill`` / ``mamba_prefill``),
    one compile and one dispatch instead of T.
    """
    st = structure(cfg)
    x = constrain_hidden(_hidden_from_inputs(params, cfg, tokens, embeds), cfg)
    new_prefix = []
    for p, spec, c in zip(params["prefix"], st.prefix, cache["prefix"]):
        x, nc = apply_layer_prefill(p, x, c, cfg, spec)
        x = constrain_hidden(x, cfg)
        new_prefix.append(nc)
    new_cache = {"prefix": new_prefix}
    if st.n_blocks:
        def block_step(x, scanned):
            block_params, block_cache = scanned
            new_bc = []
            for i, spec in enumerate(st.pattern):
                x, nc = apply_layer_prefill(block_params[i], x, block_cache[i], cfg, spec)
                x = constrain_hidden(x, cfg)
                new_bc.append(nc)
            return x, new_bc

        x, new_blocks = jax.lax.scan(block_step, x, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = new_blocks
    x = norm_apply(params["final_norm"], x[:, -1:, :], cfg)
    logits = unembed(params["embed"], x, cfg)
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, cache, index, tokens=None, embeds=None, groups=1):
    """One-token decode.  tokens [B,1] or embeds [B,1,d].  Returns
    (logits [B,1,V], new_cache).

    ``index`` is the new token's position, shared (a scalar) or per row
    ([B]).  The rows decode as ``groups`` micro-batches of B/groups rows,
    one after another inside each layer.  The stacked block cache is the
    state of the one loop over (block, group) steps, and no other loop
    carries it: each step writes its group's new K/V rows into it in place
    and reads those rows back, so a donated cache is neither copied nor
    laid out again.
    """
    st = structure(cfg)
    x = constrain_hidden(_hidden_from_inputs(params, cfg, tokens, embeds), cfg)
    B = x.shape[0]
    if B % groups:
        raise ValueError(f"{groups} groups do not divide {B} rows")
    b = B // groups
    index = jnp.asarray(index, jnp.int32)

    def rows(v, g):  # group g's b rows of v
        return jax.lax.dynamic_slice_in_dim(v, g * b, b)

    def group_step(ps, specs, x, caches, g, lead):
        xg = rows(x, g)
        ig = index if index.ndim == 0 else rows(index, g)
        caches = list(caches)
        for j, spec in enumerate(specs):
            xg, caches[j] = apply_layer_decode(
                ps[j], xg, caches[j], ig, cfg, spec, at=(*lead, g * b)
            )
            xg = constrain_hidden(xg, cfg)
        return jax.lax.dynamic_update_slice_in_dim(x, xg, g * b, 0), caches

    new_prefix = []
    for p, spec, c in zip(params["prefix"], st.prefix, cache["prefix"]):
        for g in range(groups):
            x, (c,) = group_step([p], [spec], x, [c], g, ())
        new_prefix.append(c)
    new_cache = {"prefix": new_prefix}
    if st.n_blocks:
        # one step per (block, group), groups innermost
        def block_step(carry, step):
            x, blocks = carry
            i, g = step // groups, step % groups
            ps = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), params["blocks"]
            )
            return group_step(ps, st.pattern, x, blocks, g, (i,)), None

        (x, new_cache["blocks"]), _ = jax.lax.scan(
            block_step, (x, list(cache["blocks"])), jnp.arange(st.n_blocks * groups)
        )
    x = norm_apply(params["final_norm"], x, cfg)
    logits = unembed(params["embed"], x, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Encoder-decoder (seamless-m4t backbone)
# ---------------------------------------------------------------------------


def init_encdec(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    enc_cfg = cfg.replace(num_experts=0, window_pattern=(), attn_every=1, family="dense")
    enc_specs = layer_specs(enc_cfg, cfg.encoder_layers)
    dec_specs = layer_specs(cfg)
    ke = jax.random.split(ks[0], len(enc_specs))
    kd = jax.random.split(ks[1], len(dec_specs))
    return {
        "embed": embedding_init(ks[2], cfg),
        "encoder": [init_layer(ke[i], enc_cfg, s) for i, s in enumerate(enc_specs)],
        "enc_norm": norm_init(cfg.d_model, cfg),
        "decoder": [init_layer(kd[i], cfg, s, cross=True) for i, s in enumerate(dec_specs)],
        "final_norm": norm_init(cfg.d_model, cfg),
    }


def _encode(params, cfg: ModelConfig, src_embeds, use_flash: bool = False):
    enc_cfg = cfg.replace(num_experts=0, window_pattern=(), attn_every=1, family="dense")
    x = constrain_hidden(src_embeds.astype(cfg.dtype), cfg)
    for p, spec in zip(params["encoder"], layer_specs(enc_cfg, cfg.encoder_layers)):
        x, _ = apply_layer_train(p, x, enc_cfg, spec, causal=False, use_flash=use_flash)
        x = constrain_hidden(x, cfg)
    return norm_apply(params["enc_norm"], x, cfg)


def encdec_forward(
    params, cfg: ModelConfig, src_embeds, tgt_tokens,
    use_flash: bool = False, last_only: bool = False,
):
    """Returns (logits, aux).  src_embeds come from the modality frontend stub."""
    memory = _encode(params, cfg, src_embeds, use_flash)
    x = constrain_hidden(embed(params["embed"], tgt_tokens, cfg), cfg)
    aux_lb = jnp.zeros((), jnp.float32)
    aux_z = jnp.zeros((), jnp.float32)
    for p, spec in zip(params["decoder"], layer_specs(cfg)):
        x, (lb, z) = apply_layer_train(p, x, cfg, spec, memory=memory, use_flash=use_flash)
        x = constrain_hidden(x, cfg)
        aux_lb, aux_z = aux_lb + lb, aux_z + z
    if last_only:
        x = x[:, -1:, :]
    x = norm_apply(params["final_norm"], x, cfg)
    return unembed(params["embed"], x, cfg), {
        "moe_load_balance": aux_lb,
        "moe_router_z": aux_z,
    }


def encdec_loss(params, cfg: ModelConfig, src_embeds, tgt_tokens, labels, mask=None):
    logits, aux = encdec_forward(params, cfg, src_embeds, tgt_tokens)
    loss = cross_entropy_loss(logits, labels, mask=mask)
    total = loss + MOE_AUX_WEIGHT * aux["moe_load_balance"] + MOE_Z_WEIGHT * aux["moe_router_z"]
    return total, {"ce_loss": loss, **aux}


def init_encdec_cache(cfg: ModelConfig, batch: int, max_len: int):
    # encoder memory stays an explicit decode input (not part of the cache)
    # so the cache pytree structure is stable across steps
    return {
        "decoder": [
            init_layer_cache(cfg, spec, batch, max_len, cross=True)
            for spec in layer_specs(cfg)
        ],
    }


def encdec_decode_step(params, cfg: ModelConfig, cache, index, tgt_tokens, memory):
    """One decoder token against fixed encoder ``memory``."""
    x = constrain_hidden(embed(params["embed"], tgt_tokens, cfg), cfg)
    new_dec = []
    for p, spec, c in zip(params["decoder"], layer_specs(cfg), cache["decoder"]):
        x, nc = apply_layer_decode(p, x, c, index, cfg, spec, memory=memory)
        x = constrain_hidden(x, cfg)
        new_dec.append(nc)
    x = norm_apply(params["final_norm"], x, cfg)
    return unembed(params["embed"], x, cfg), {"decoder": new_dec}
