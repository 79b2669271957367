"""Pallas TPU kernel for the chunked Mamba2 SSD scan.

TPU adaptation (vs. the paper's CUDA kernels): one grid step owns a
(batch, head, chunk) tile; the chunk axis is the *minor* grid dimension, so
TPU's sequential grid execution threads the recurrent state through a VMEM
scratch accumulator (no atomics, no inter-block sync — the TPU grid IS the
scan).  All tiles live in VMEM via BlockSpecs; the [Q, Q] intra-chunk matrix
and [P, N] state are MXU-shaped (Q, P, N multiples of 8/128 recommended).

Layout: Mosaic requires the last two dims of every block to be multiples of
(8, 128) or the array's full extent, so the wrapper moves the head axis out
of them — x/y go head-major ``[B, H, T, P]``, and the per-step decay terms
arrive as a ``[Q, 1]`` column and a ``[1, Q]`` row whose singleton axis is
the array's own.  The in-chunk cumulative decay is an XLA ``cumsum`` in the
wrapper (a lane-axis scan is not a Mosaic primitive).

VMEM working set per step ≈ Q·P + 2·Q·N + Q² + P·N floats — e.g.
Q=128, P=64, N=128: ~45 KiB in fp32, comfortably inside the ~16 MiB VMEM.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

__all__ = ["ssd_chunked_pallas"]

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _ssd_kernel(x_ref, dt_ref, cum_ref, cum_row_ref, b_ref, c_ref, y_ref, h_scratch):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    x = x_ref[0, 0].astype(jnp.float32)  # [Q, P]
    dt = dt_ref[0, 0]  # [Q, 1]
    cum = cum_ref[0, 0]  # [Q, 1] inclusive in-chunk sum of dt * A
    cum_row = cum_row_ref[0, 0, 0]  # [1, Q] the same, as a row
    Bm = b_ref[0].astype(jnp.float32)  # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)  # [Q, N]
    Q = x.shape[0]

    w = dt * x  # [Q, P]

    # intra-chunk: (C B^T ∘ L) @ w
    cb = jax.lax.dot_general(Cm, Bm, _NT, preferred_element_type=jnp.float32)  # [Q, Q]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(col <= row, jnp.exp(cum - cum_row), 0.0)
    y = jnp.dot(cb * L, w, preferred_element_type=jnp.float32)  # [Q, P]

    # inter-chunk: C_i . (exp(cum_i) h_in)
    h_in = h_scratch[...]  # [P, N]
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        Cm, h_in, _NT, preferred_element_type=jnp.float32
    )

    # carry update
    last = cum_row[:, Q - 1 :]  # [1, 1]
    inj_w = jnp.exp(last - cum)  # [Q, 1]
    h_scratch[...] = jnp.exp(last) * h_in + jax.lax.dot_general(
        w * inj_w, Bm, _TN, preferred_element_type=jnp.float32
    )
    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_pallas(x, dt, A, Bm, Cm, chunk: int = 64, interpret: bool = False):
    """x [B,T,H,P], dt [B,T,H], A [H], Bm/Cm [B,T,N] -> y [B,T,H,P].

    ``interpret=True`` runs the kernel in the Pallas interpreter (any
    backend); otherwise it is compiled by Mosaic and needs a TPU."""
    if Bm.ndim == 4:
        Bm = Bm[:, :, 0, :]
        Cm = Cm[:, :, 0, :]
    B_, T, H, P = x.shape
    N = Bm.shape[-1]
    if T % chunk != 0:
        raise ValueError(f"T={T} % chunk={chunk} != 0")
    nc = T // chunk

    xh = x.transpose(0, 2, 1, 3)  # [B, H, T, P]
    dth = dt.astype(jnp.float32).transpose(0, 2, 1)  # [B, H, T]
    a = dth * A.astype(jnp.float32)[None, :, None]
    cum = jnp.cumsum(a.reshape(B_, H, nc, chunk), axis=-1)

    y = pl.pallas_call(
        _ssd_kernel,
        grid=(B_, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),  # x
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),  # dt
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),  # cum
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),  # cum row
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),  # B
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),  # C
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B_, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(
        xh,
        dth.reshape(B_, H, T, 1),
        cum.reshape(B_, H, T, 1),
        cum.reshape(B_, H, nc, 1, chunk),
        Bm,
        Cm,
    )
    return y.transpose(0, 2, 1, 3)
