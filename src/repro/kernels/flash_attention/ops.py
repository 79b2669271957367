"""Jit'd public wrapper for flash attention: head layout around the kernel."""

from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention_pallas

__all__ = ["flash_attention"]


def flash_attention(
    q, k, v,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """q [B,T,H,hd]; k, v [B,S,H,hd] (heads already GQA-repeated).

    Always runs the Pallas kernel: compiled by Mosaic (TPU only) unless the
    caller asks for the Pallas interpreter with ``interpret=True``."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    of = flash_attention_pallas(
        qf, kf, vf,
        causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return of.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
