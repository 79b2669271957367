"""The one traffic generator: turns a traffic file's parameters and ``--seed``
into training batches or serving requests.

Every seed gets the same work in another order. Serving: the set of
inter-arrival gaps, prompt lengths and output lengths is fixed by the
parameters (quantiles of the stated distributions) and only their order is
drawn from the seed, so two seeds differ in which request comes when, not
in how much is asked. Training: each step's batch is ``[B, T+1]`` token ids
drawn from the seed and the step number; labels are the next token.

Arrivals are an open loop: a request is due at its time whether or not the
server has kept up (the ``ArrivalProcess`` idea of ``repro.serve.arrival``,
with fixed quantiles in place of random draws).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


# -- training ---------------------------------------------------------------------


def train_batch(traffic: dict, vocab: int, seed: int, step: int):
    """Tokens and labels ``[M, b, T]`` (int32) of training step ``step``."""
    M, b, T = traffic["microbatches"], traffic["micro_batch"], traffic["seq_len"]
    seq = _rng(seed, 1, step).integers(0, vocab, size=(M * b, T + 1), dtype=np.int32)
    shape = (M, b, T)
    return seq[:, :-1].reshape(shape), seq[:, 1:].reshape(shape)


# -- serving ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of the schedule: due ``due`` seconds after the window
    opens; ``rid`` seeds the prompt the engine makes."""

    rid: int
    due: float
    prompt_len: int
    new_tokens: int


def _lognormal_quantiles(n: int, median: float, sigma: float) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return median * np.exp(sigma * z)


def _bucketed(x: np.ndarray, buckets: list[int]) -> np.ndarray:
    b = np.asarray(sorted(buckets))
    idx = np.minimum(np.searchsorted(b, np.ceil(x)), len(b) - 1)
    return b[idx]


def serve_schedule(traffic: dict, seed: int, seconds: float) -> list[Planned]:
    """The requests due in a window of ``seconds``, in order of due time."""
    rate = float(traffic["rate"])
    n = max(1, round(rate * seconds))
    rng = _rng(seed, 2)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])]) * seconds / gaps.sum()
    p, o = traffic["prompt"], traffic["output"]
    prompts = _bucketed(_lognormal_quantiles(n, p["median"], p["sigma"]), p["buckets"])
    outs = np.clip(np.rint(_lognormal_quantiles(n, o["median"], o["sigma"])), o["min"], o["max"])
    prompts, outs = rng.permutation(prompts), rng.permutation(outs)
    rids = rng.choice(2**31 - 1, size=n, replace=False)
    return [
        Planned(int(r), float(d), int(pl), int(nt))
        for r, d, pl, nt in zip(rids, due, prompts, outs)
    ]


def quantile(samples: list[float], q: float) -> float:
    """Linear-interpolated quantile over raw samples (the arithmetic of
    ``repro.serve.slo.SLOTracker._quantile``); ``inf`` counts as a miss."""
    if not samples:
        return math.nan
    xs = sorted(samples)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return math.inf if pos > lo or math.isinf(xs[lo]) else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
