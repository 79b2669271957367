"""The comparison that decides ``correct``.

Training is held to the plain reference on three numbers, each over the
steps that set-up drives through the window's own call:

* ``loss_gap``: the largest ``|loss - reference loss|`` over those steps;
* ``grad_gap``: over every piece (one layer's matrix or vector, the
  embedding, the final norm), the gap between the norm of the first
  gradient as the optimizer got it and the reference's norm, over the
  larger of that piece's reference norm and the median piece's;
* ``change_gap``: the same for the norm of each piece's change over all
  those steps. Pieces whose reference gradient is under a thousandth of the
  median piece's are left out: they move by round-off alone.

Serving is held to one number, ``logit_gap``: over a sample of finished
requests, the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.

A number passes when it is finite and at most its limit.
"""

from __future__ import annotations

import math

import numpy as np

ROUND_OFF_SHARE = 1e-3


def _piece_gap(prog: list[float], ref: list[float], keep: np.ndarray | None = None) -> float:
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    scale = np.maximum(r, np.median(r))
    gaps = np.abs(p - r) / np.where(scale > 0, scale, 1.0)
    if keep is not None:
        gaps = gaps[keep]
    return float(np.max(gaps))


def train_gaps(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses``, ``grad_norms`` and
    ``change_norms`` as :func:`chipbench.reference.gpt.train_readings` gives
    them."""
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    keep = g_ref >= ROUND_OFF_SHARE * np.median(g_ref)
    return {
        "loss_gap": float(max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))),
        "grad_gap": _piece_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": _piece_gap(prog["change_norms"], ref["change_norms"], keep),
    }


def served_gap(ref_logits: np.ndarray, served: np.ndarray) -> float:
    """Widest ``max(ref) - ref[served]`` over positions; ``ref_logits``
    [n, V] are the reference's logits at the positions that chose
    ``served`` [n]."""
    best = ref_logits.max(axis=-1)
    got = ref_logits[np.arange(len(served)), served]
    return float(np.max(best - got))


def judge(gaps: dict, limits: dict) -> tuple[bool, list[tuple[str, float, float]]]:
    """(correct, [(name, value, limit)]) for every number the cell compares.
    A number without a limit fails."""
    checks = [(name, float(v), float(limits.get(name, math.nan))) for name, v in gaps.items()]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    return ok, checks
