"""Stage partitioning for pipeline parallelism.

A :class:`StagedModel` cuts a decoder-only config into ``S`` contiguous
stages of equal layer count (the balance-aware uniform cut; Rhino's ILP
cutting is orthogonal to the scheduling contribution — DESIGN.md §9.3).

SPMD uniformity: every stage holds an *identical pytree structure* —
``layers`` is the repeating pattern stacked ``reps`` times, and the
embedding / final-norm parameters are present on every stage but only
*used* by the first / last stage (their copies elsewhere receive zero
gradient; the engine psums the replicated leaves over the stage axis, which
is exactly the sum of the one non-zero contribution).  The memory overhead
of the replicated embedding is accounted in the memory model.

Constraints (documented in DESIGN.md): ``num_layers % num_stages == 0`` and
``layers_per_stage % len(pattern) == 0`` — satisfied by the paper's GPT
configs and the assigned archs' regular bodies; kimi-k2's single leading
dense layer is handled by folding it into a 61=1+60 prefix carried by stage
0 only when S divides 60 (not exercised by the engine tests).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as tf
from repro.models.common import LayerSpec, ModelConfig
from repro.models.layers import (
    cross_entropy_loss,
    embed,
    embedding_init,
    norm_apply,
    norm_init,
    unembed,
)

__all__ = ["StagedModel"]


@dataclasses.dataclass(frozen=True)
class StagedModel:
    cfg: ModelConfig
    num_stages: int
    pattern: tuple[LayerSpec, ...]
    reps: int  # pattern repetitions per stage

    @classmethod
    def build(cls, cfg: ModelConfig, num_stages: int) -> "StagedModel":
        if cfg.family == "encdec":
            raise ValueError("pipeline engine covers decoder-only families")
        st = tf.structure(cfg)
        if st.prefix:
            raise ValueError(
                f"{cfg.name}: irregular prefix layers not supported by the "
                "stage partitioner (fold into cfg or use the SPMD path)"
            )
        L = cfg.num_layers
        if L % num_stages:
            raise ValueError(f"layers {L} % stages {num_stages} != 0")
        per_stage = L // num_stages
        if per_stage % len(st.pattern):
            raise ValueError(
                f"layers/stage {per_stage} must tile the layer pattern "
                f"(len {len(st.pattern)})"
            )
        return cls(cfg, num_stages, st.pattern, per_stage // len(st.pattern))

    @property
    def layers_per_stage(self) -> int:
        return self.reps * len(self.pattern)

    # -- params ---------------------------------------------------------------

    def init_stage_params(self, key, stage: int) -> dict[str, Any]:
        """Parameters of ONE stage (embed/final_norm replicated everywhere)."""
        cfg = self.cfg
        k_embed, k_layers = jax.random.split(jax.random.fold_in(key, 0))

        def one_rep(k):
            kk = jax.random.split(k, len(self.pattern))
            return [tf.init_layer(kk[i], cfg, sp) for i, sp in enumerate(self.pattern)]

        rep_keys = jax.random.split(jax.random.fold_in(k_layers, stage), self.reps)
        return {
            "embed": embedding_init(k_embed, cfg),  # same on every stage
            "final_norm": norm_init(cfg.d_model, cfg),
            "blocks": jax.vmap(one_rep)(rep_keys),  # leaves [reps, ...]
        }

    def init_all_stages(self, key):
        """Stacked [S, ...] params pytree (leading dim = stage).

        One compiled program: an eager call and a call traced inside a
        caller's ``jit`` (the runtime's sharded init) fuse alike, so both
        produce the same bits."""
        return _init_all_stages(self, key)

    # -- compute --------------------------------------------------------------

    def stage_hidden(self, params, x):
        """The stage body: hidden [b, T, d] -> hidden [b, T, d]."""
        cfg = self.cfg

        def rep_step(x, rep_params):
            for i, sp in enumerate(self.pattern):
                x, _ = tf.apply_layer_train(rep_params[i], x, cfg, sp)
            return x, None

        body = jax.checkpoint(rep_step) if cfg.remat_blocks else rep_step
        x, _ = jax.lax.scan(body, x, params["blocks"])
        return x

    @jax.named_scope("embed")
    def embed_tokens(self, params, tokens):
        return embed(params["embed"], tokens, self.cfg)

    @jax.named_scope("head_loss")
    def head_loss(self, params, h, labels):
        """Last-stage epilogue: final norm + unembed + mean token CE."""
        cfg = self.cfg
        h = norm_apply(params["final_norm"], h, cfg)
        logits = unembed(params["embed"], h, cfg)
        return cross_entropy_loss(logits, labels)

    # convenience: the mathematically-equivalent unpipelined model ------------

    def full_loss(self, all_params, tokens, labels):
        """Direct (non-pipelined) forward over all stages — the numerics
        oracle the engine is validated against."""
        x = self.embed_tokens(jax.tree_util.tree_map(lambda p: p[0], all_params), tokens)
        for s in range(self.num_stages):
            p_s = jax.tree_util.tree_map(lambda p: p[s], all_params)
            x = self.stage_hidden(p_s, x)
        p_last = jax.tree_util.tree_map(lambda p: p[-1], all_params)
        return self.head_loss(p_last, x, labels)


@functools.partial(jax.jit, static_argnums=0)
def _init_all_stages(staged: StagedModel, key):
    per_stage = [staged.init_stage_params(key, s) for s in range(staged.num_stages)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)
