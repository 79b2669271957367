"""Uniform model API over all architecture families.

Every family exposes the same four entry points, keyed off a batch *dict*
(so jit/pjit and ShapeDtypeStruct dry-runs treat all architectures
identically):

* ``init_params(key, cfg)``
* ``loss_fn(params, cfg, batch) -> (loss, metrics)``  — train/prefill
* ``init_cache(cfg, batch, max_len)``                 — decode state
* ``decode_fn(params, cfg, cache, index, batch) -> (logits, cache)``

Batch keys by family:
  text (dense/moe/ssm/hybrid): tokens [B,T], labels [B,T]
  vlm:    embeds [B,T,d], labels [B,T], mrope_positions [3,B,T]
  encdec: src_embeds [B,S,d], tgt_tokens [B,T], labels [B,T]
Decode batches carry ``tokens`` [B,1] (all families) plus ``memory``
[B,S,d] for enc-dec; ``index`` is a scalar or, decoder-only, [B].
"""

from __future__ import annotations

from typing import Mapping

import jax

from repro.models import transformer as tf
from repro.models.common import ModelConfig

__all__ = [
    "init_params",
    "loss_fn",
    "forward_fn",
    "init_cache",
    "cache_specs",
    "decode_fn",
    "prefill_with_cache",
]


def init_params(key, cfg: ModelConfig):
    if cfg.family == "encdec":
        return tf.init_encdec(key, cfg)
    return tf.init_decoder(key, cfg)


def loss_fn(params, cfg: ModelConfig, batch: Mapping[str, jax.Array]):
    if cfg.family == "encdec":
        return tf.encdec_loss(
            params, cfg, batch["src_embeds"], batch["tgt_tokens"], batch["labels"]
        )
    if cfg.family == "vlm":
        return tf.decoder_loss(
            params,
            cfg,
            labels=batch["labels"],
            embeds=batch["embeds"],
            mrope_positions=batch.get("mrope_positions"),
        )
    return tf.decoder_loss(params, cfg, batch["tokens"], labels=batch["labels"])


def forward_fn(params, cfg: ModelConfig, batch: Mapping[str, jax.Array]):
    if cfg.family == "encdec":
        return tf.encdec_forward(params, cfg, batch["src_embeds"], batch["tgt_tokens"])
    if cfg.family == "vlm":
        return tf.decoder_forward(
            params, cfg, embeds=batch["embeds"],
            mrope_positions=batch.get("mrope_positions"),
        )
    return tf.decoder_forward(params, cfg, batch["tokens"])


def prefill_fn(params, cfg: ModelConfig, batch: Mapping[str, jax.Array]):
    """Inference prefill: full-sequence forward, last-position logits only.

    Avoids materializing [B, T, V] logits — the serving-path contract the
    ``prefill_32k`` dry-run shape lowers.
    """
    if cfg.family == "encdec":
        logits, _ = tf.encdec_forward(
            params, cfg, batch["src_embeds"], batch["tgt_tokens"], last_only=True
        )
        return logits
    if cfg.family == "vlm":
        logits, _ = tf.decoder_forward(
            params, cfg, embeds=batch["embeds"],
            mrope_positions=batch.get("mrope_positions"), last_only=True,
        )
        return logits
    logits, _ = tf.decoder_forward(params, cfg, batch["tokens"], last_only=True)
    return logits


def prefill_with_cache(
    params, cfg: ModelConfig, cache, batch: Mapping[str, jax.Array]
):
    """Fused prefill that also fills the decode cache in one pass.

    The serving entry point: ``(logits [B,1,V], cache)`` ready for
    ``decode_fn`` at ``index = T``.  Text families (dense/moe/ssm/hybrid)
    only — enc-dec threads encoder memory explicitly and vlm threads
    M-RoPE positions; neither is a serving path here.
    """
    if cfg.family in ("encdec", "vlm"):
        raise NotImplementedError(
            f"prefill_with_cache does not support family {cfg.family!r}"
        )
    return tf.prefill_with_cache(params, cfg, cache, tokens=batch["tokens"])


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int):
    if cfg.family == "encdec":
        return tf.init_encdec_cache(cfg, batch_size, max_len)
    return tf.init_decode_cache(cfg, batch_size, max_len)


def cache_specs(cfg: ModelConfig, batch_size: int, max_len: int):
    """ShapeDtypeStruct pytree of the decode cache — no allocation."""
    return jax.eval_shape(lambda: init_cache(cfg, batch_size, max_len))


def decode_fn(
    params, cfg: ModelConfig, cache, index, batch: Mapping[str, jax.Array], groups=1
):
    """One token per row.  ``index`` is a scalar position or one per row;
    decoder-only families may walk the rows as ``groups`` micro-batches
    (see ``transformer.decode_step``)."""
    if cfg.family == "encdec":
        logits, new_cache = tf.encdec_decode_step(
            params, cfg, cache, index, batch["tokens"], batch["memory"]
        )
        return logits, new_cache
    return tf.decode_step(params, cfg, cache, index, tokens=batch["tokens"], groups=groups)
