"""Operations and bytes a step needs, computed from the configuration's
shapes. Recomputation (remat) is not counted, and neither is work on
padding: these are what the model requires, not what a program did."""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: the blocks'
    projections and MLP, and the output head (the tied embedding)."""
    L, d, ff = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return L * (4 * d * q + 2 * d * ff) + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward: 6 per weight, plus attention's scores and
    weighted sum over the full sequence, 12 * L * (H * hd) * T (the
    PaLM accounting, arXiv:2204.02311 App. B)."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + 12.0 * cfg["num_hidden_layers"] * q * seq_len


def decode_flops(cfg: dict, occupied: int, positions: int) -> float:
    """One decode tick: 2 per weight for each occupied slot, plus the
    scores and weighted sum over each slot's cached positions."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * matmul_params(cfg) * occupied + 4.0 * cfg["num_hidden_layers"] * q * positions


def decode_bytes(cfg: dict, param_bytes: int, kv_itemsize: int, positions: int) -> float:
    """One decode tick: every parameter read once, and the keys and values
    of each occupied slot up to its position."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return float(param_bytes) + 2.0 * cfg["num_hidden_layers"] * q * kv_itemsize * positions


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time at peak and transfer time
    at peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
