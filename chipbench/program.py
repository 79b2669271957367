"""What the benchmark hands the program under test, and how it reads it back.

The program (``src/repro``) is the system under test. The benchmark gives it
a model configuration built from a configuration file, and weights that the
benchmark made from ``--seed`` in the reference's layout; it reads the
program's training state back in that layout. Nothing here computes a
result the comparison relies on: the conversions only move and reshape
arrays, and :func:`check_layout` refuses a program whose layout changed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gpt import LAYER_KEYS

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

# reference layer key -> path inside one program block
_BLOCK_PATHS = {
    "ln1_g": ("ln1", "scale"),
    "ln1_b": ("ln1", "bias"),
    "wq": ("attn", "wq", "w"),
    "wk": ("attn", "wk", "w"),
    "wv": ("attn", "wv", "w"),
    "wo": ("attn", "wo", "w"),
    "ln2_g": ("ln2", "scale"),
    "ln2_b": ("ln2", "bias"),
    "w_up": ("mlp", "up", "w"),
    "w_down": ("mlp", "down", "w"),
}


def seed_key(seed: int, purpose: int):
    """A JAX key from the whole of ``--seed`` (``jax.random.PRNGKey`` keeps
    only its low 32 bits) and a purpose number."""
    words = np.random.SeedSequence([int(seed), purpose]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def model_config(cfg: dict, remat: bool = False):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.common import ModelConfig

    if cfg["activation"] != "gelu_tanh" or cfg["norm"] != "layernorm" or cfg["position"] != "rope":
        raise ValueError(f"{cfg['name']}: the program builds GPT blocks only as gelu/layernorm/rope")
    if not cfg["tie_word_embeddings"]:
        raise ValueError(f"{cfg['name']}: the GPT configurations tie their embeddings")
    return ModelConfig(
        name=cfg["name"],
        family="dense",
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"],
        mlp_act="gelu",
        norm="layernorm",
        tie_embeddings=True,
        rope_theta=cfg["rope_theta"],
        dtype=_DTYPES[cfg["compute_dtype"]],
        param_dtype=_DTYPES[cfg["param_dtype"]],
        remat_blocks=remat,
    )


def _block(layers, lead):
    """One program block (leaves ``lead + [...]``) from reference layers."""
    out: dict = {}
    for key in LAYER_KEYS:
        node = out
        *parents, leaf = _BLOCK_PATHS[key]
        for p in parents:
            node = node.setdefault(p, {})
        x = layers[key]
        node[leaf] = x.reshape(lead + x.shape[1:])
    return out


def _unblock(block, L):
    layers = {}
    for key in LAYER_KEYS:
        x = block
        for p in _BLOCK_PATHS[key]:
            x = x[p]
        rank = 1 if key.startswith("ln") else 2  # one layer's vector or matrix
        layers[key] = x.reshape((L,) + x.shape[x.ndim - rank :])
    return layers


def to_staged(w, num_stages: int):
    """Reference weights -> the pipeline's stage-stacked parameters: block
    leaves ``[S, L/S, ...]``, the tied embedding and the final norm copied
    onto every stage."""
    L = w["layers"]["wq"].shape[0]
    S = num_stages
    rep = lambda x: jnp.broadcast_to(x, (S,) + x.shape)  # noqa: E731
    return {
        "blocks": [_block(w["layers"], (S, L // S))],
        "embed": {"table": rep(w["wte"])},
        "final_norm": {"bias": rep(w["lnf_b"]), "scale": rep(w["lnf_g"])},
    }


def from_staged(tree, num_stages: int):
    """A parameter-shaped tree of the pipeline -> the reference layout. The
    embedding is read from the first stage, which embeds the tokens; the
    final norm from the last, which applies it."""
    block = tree["blocks"][0]
    L = num_stages * block["attn"]["wq"]["w"].shape[1]
    return {
        "wte": tree["embed"]["table"][0],
        "lnf_g": tree["final_norm"]["scale"][num_stages - 1],
        "lnf_b": tree["final_norm"]["bias"][num_stages - 1],
        "layers": _unblock(block, L),
    }


def to_api(w):
    """Reference weights -> ``repro.models.api`` parameters (serving)."""
    L = w["layers"]["wq"].shape[0]
    return {
        "blocks": [_block(w["layers"], (L,))],
        "embed": {"table": w["wte"]},
        "final_norm": {"bias": w["lnf_b"], "scale": w["lnf_g"]},
        "prefix": [],
    }


def check_layout(ours, theirs, what: str) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the program's."""
    a = jax.tree_util.tree_structure(ours)
    b = jax.tree_util.tree_structure(theirs)
    if a != b:
        raise RuntimeError(f"{what}: the program's parameter tree changed:\n{b}\nexpected\n{a}")
    for x, y in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise RuntimeError(f"{what}: leaf {y.shape} {y.dtype} where {x.shape} {x.dtype} is made")
