"""Plain float32 GPT: weights, forward, loss, gradient and the AdamW step.

The yardstick that decides ``correct``. It imports nothing of the program
under test and follows the published description of a GPT decoder:
pre-LayerNorm blocks of causal multi-head self-attention and a GELU MLP,
a final LayerNorm, and an output head tied to the token embedding
(GPT-2, Radford et al. 2019; GPT-3, arXiv:2005.14165). Departures, each as
the configuration files state them:

* positions are rotary (RoPE, arXiv:2104.09864, rotating the two halves of
  each head) in place of learned position embeddings, as the configuration
  under test is built;
* the GELU is the tanh approximation (GPT-2's ``gelu_new``);
* no dense layer has a bias (the LayerNorms keep theirs);
* AdamW decays every parameter, as arXiv:2005.14165 (App. B) states it.

Every matrix product goes through :func:`mm`. In ``"f32"`` mode it runs in
float32 at ``precision="highest"`` (the TPU would otherwise round float32
inputs to bfloat16). In ``"fp8"`` mode its operands are rounded to 8-bit
floats with a per-tensor scale (e4m3 forward, e5m2 for the cotangents of
the backward pass), which is the control: the reference put one precision
below the program's bfloat16 compute.

Layout: ``{"wte": [V, d], "lnf_g": [d], "lnf_b": [d], "layers": {name:
[L, ...]}}``, with ``layers`` holding ``ln1_g ln1_b wq wk wv wo ln2_g ln2_b
w_up w_down``; ``wq/wk/wv`` are ``[L, d, H*hd]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LAYER_KEYS = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b", "w_up", "w_down")
LN_EPS = 1e-5
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def dims(cfg: dict) -> dict:
    """The widths the reference needs, read from a configuration file."""
    return {
        "L": int(cfg["num_hidden_layers"]),
        "d": int(cfg["hidden_size"]),
        "ff": int(cfg["intermediate_size"]),
        "H": int(cfg["num_attention_heads"]),
        "hd": int(cfg["head_dim"]),
        "V": int(cfg["vocab_size"]),
        "theta": float(cfg["rope_theta"]),
        "std": float(cfg["initializer_range"]),
    }


# -- weights ------------------------------------------------------------------


def init_weights(key, cfg: dict):
    """GPT-2's initialisation: N(0, std) for every matrix, residual output
    projections scaled by 1/sqrt(2L), LayerNorm gain 1 and bias 0."""
    g = dims(cfg)
    L, d, ff, q = g["L"], g["d"], g["ff"], g["H"] * g["hd"]
    std = g["std"]
    out_std = std / math.sqrt(2 * L)
    k = jax.random.split(key, 7)
    normal = lambda kk, shape, s: jax.random.normal(kk, shape, jnp.float32) * s  # noqa: E731
    return {
        "wte": normal(k[0], (g["V"], d), std),
        "lnf_g": jnp.ones((d,), jnp.float32),
        "lnf_b": jnp.zeros((d,), jnp.float32),
        "layers": {
            "ln1_g": jnp.ones((L, d), jnp.float32),
            "ln1_b": jnp.zeros((L, d), jnp.float32),
            "wq": normal(k[1], (L, d, q), std),
            "wk": normal(k[2], (L, d, q), std),
            "wv": normal(k[3], (L, d, q), std),
            "wo": normal(k[4], (L, q, d), out_std),
            "ln2_g": jnp.ones((L, d), jnp.float32),
            "ln2_b": jnp.zeros((L, d), jnp.float32),
            "w_up": normal(k[5], (L, d, ff), std),
            "w_down": normal(k[6], (L, ff, d), out_std),
        },
    }


def pieces(tree) -> list[tuple[str, jax.Array]]:
    """The units a norm is taken over: each layer's matrix or vector on its
    own, and the unstacked leaves whole."""
    out = [("wte", tree["wte"]), ("lnf_g", tree["lnf_g"]), ("lnf_b", tree["lnf_b"])]
    for name in LAYER_KEYS:
        leaf = tree["layers"][name]
        out += [(f"{name}.{i}", leaf[i]) for i in range(leaf.shape[0])]
    return out


def piece_norms(tree) -> jax.Array:
    """L2 norm of every piece, in :func:`pieces` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for _, x in pieces(tree)])


# -- matrix products in the chosen precision ------------------------------------


def _fp8(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    return jnp.einsum(spec, _fp8(a, jnp.float8_e4m3fn, E4M3_MAX), _fp8(b, jnp.float8_e4m3fn, E4M3_MAX),
                      precision="highest")


def _mm8_fwd(spec, a, b):
    qa, qb = _fp8(a, jnp.float8_e4m3fn, E4M3_MAX), _fp8(b, jnp.float8_e4m3fn, E4M3_MAX)
    return jnp.einsum(spec, qa, qb, precision="highest"), (qa, qb)


def _mm8_bwd(spec, res, ct):
    qa, qb = res
    ct = _fp8(ct, jnp.float8_e5m2, E5M2_MAX)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision="highest"), qa, qb)
    return vjp(ct)


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def mm(spec: str, a, b, mode: str):
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision="highest")
    if mode == "fp8":
        return _mm8(spec, a, b)
    raise ValueError(f"unknown precision mode {mode!r}")


# -- the model ----------------------------------------------------------------------


def _layernorm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def _rope(x, theta):
    """x [B, T, H, hd]: rotate the first and second half of each head."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(x, p, g, mode):
    B, T, d = x.shape
    H, hd = g["H"], g["hd"]
    h = _layernorm(x, p["ln1_g"], p["ln1_b"])
    q = mm("btd,de->bte", h, p["wq"], mode).reshape(B, T, H, hd)
    k = mm("btd,de->bte", h, p["wk"], mode).reshape(B, T, H, hd)
    v = mm("btd,de->bte", h, p["wv"], mode).reshape(B, T, H, hd)
    q, k = _rope(q, g["theta"]), _rope(k, g["theta"])
    s = mm("bthe,bshe->bhts", q, k, mode) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bhts,bshe->bthe", a, v, mode).reshape(B, T, H * hd)
    x = x + mm("bte,ed->btd", o, p["wo"], mode)
    h = _layernorm(x, p["ln2_g"], p["ln2_b"])
    h = _gelu(mm("btd,df->btf", h, p["w_up"], mode))
    return x + mm("btf,fd->btd", h, p["w_down"], mode)


def forward(w, tokens, cfg: dict, mode: str = "f32", remat: bool = False):
    """Logits [B, T, V] in float32 for tokens [B, T]."""
    g = dims(cfg)
    x = w["wte"][tokens]

    def body(x, p):
        return _block(x, p, g, mode), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["layers"])
    x = _layernorm(x, w["lnf_g"], w["lnf_b"])
    return mm("btd,vd->btv", x, w["wte"], mode)


def loss(w, tokens, labels, cfg: dict, mode: str = "f32"):
    """Mean token cross-entropy over [B, T]."""
    logits = forward(w, tokens, cfg, mode, remat=True)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# -- training -----------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3, 4))
def _micro_grad(w, tokens, labels, cfg_h, mode):
    return jax.value_and_grad(loss)(w, tokens, labels, cfg_h, mode)


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 1, 2))
def _adamw(w, m, v, opt_items, g, step):
    opt = dict(opt_items)
    b1, b2, eps, wd, lr = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"], opt["lr"]
    leaves = jax.tree_util.tree_leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(norm, 1e-12))
    g = jax.tree_util.tree_map(lambda x: x * scale, g)
    m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c1, c2 = 1 - b1**step, 1 - b2**step
    w = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p), w, m, v
    )
    return w, m, v, g


def train_readings(key, batches, cfg: dict, opt: dict, mode: str = "f32") -> dict:
    """Run ``len(batches)`` AdamW steps from the weights ``init_weights(key)``
    makes, each over a batch ``(tokens, labels)`` of ``[M, b, T]`` computed
    one micro-batch at a time, and return what a step of the program is held
    to: each step's mean loss, the norm of every piece of the first (clipped)
    gradient, and the norm of every piece's change over all the steps."""
    cfg_h = _hashable(cfg)
    opt_items = tuple(sorted(opt.items()))
    w = make_weights(key, cfg)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for step, (tokens, labels) in enumerate(batches, start=1):
        M = tokens.shape[0]
        total, acc = 0.0, None
        for i in range(M):
            l, gi = _micro_grad(w, tokens[i], labels[i], cfg_h, mode)
            acc = gi if acc is None else jax.tree_util.tree_map(jnp.add, acc, gi)
            total += float(l)
        acc = jax.tree_util.tree_map(lambda x: x / M, acc)
        losses.append(total / M)
        w, m, v, g = _adamw(w, m, v, opt_items, acc, jnp.float32(step))
        if first_grad is None:
            first_grad = [float(x) for x in jax.jit(piece_norms)(g)]
        del g, acc
    del m, v
    change = [float(x) for x in change_norms(w, make_weights(key, cfg))]
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change}


@jax.jit
def change_norms(w, w0):
    return piece_norms(jax.tree_util.tree_map(jnp.subtract, w, w0))


class _hashable(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def make_weights(key, cfg: dict):
    """``init_weights`` as one jitted call on the default device."""
    return jax.jit(init_weights, static_argnums=1)(key, _hashable(cfg))


def logits_at(w, seqs, cfg: dict, mode: str = "f32", chunk: int = 4):
    """Forward over padded sequences [N, T] in chunks of ``chunk`` rows."""
    cfg_h = _hashable(cfg)
    f = _forward_jit(cfg_h, mode)
    return [f(w, seqs[i : i + chunk]) for i in range(0, seqs.shape[0], chunk)]


@functools.lru_cache(maxsize=8)
def _forward_jit(cfg_h, mode):
    return jax.jit(lambda w, t: forward(w, t, cfg_h, mode))
