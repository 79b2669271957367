"""Tiny configurations and traffic for running the drivers on the CPU."""

from __future__ import annotations

from pathlib import Path
import tempfile
import time

from chipbench.common import Context

CONFIG = {
    "name": "tiny",
    "num_hidden_layers": 4,
    "hidden_size": 32,
    "intermediate_size": 64,
    "num_attention_heads": 2,
    "head_dim": 16,
    "vocab_size": 64,
    "tie_word_embeddings": True,
    "activation": "gelu_tanh",
    "norm": "layernorm",
    "position": "rope",
    "rope_theta": 10000.0,
    "initializer_range": 0.02,
    "param_dtype": "float32",
    "compute_dtype": "float32",
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                  "max_grad_norm": 1.0},
}

TRAIN = {"driver": "train", "backend": "reference", "stages": 1, "k": 1, "microbatches": 4,
         "micro_batch": 1, "seq_len": 16, "remat": True}

SERVE = {"driver": "serve", "stages": 2, "slots": 4, "groups": 2, "k": 2, "max_len": 40,
         "rate": 4.0, "prompt": {"median": 12, "sigma": 0.5, "buckets": [8, 16, 32]},
         "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
         "check_sample": 4, "check_chunk": 2}


def context(traffic: dict, *, config=None, limits=None, seed=2**40 + 3, seconds=1.0,
            trace=False, patch=None, **traffic_overrides) -> Context:
    return Context(
        workload="tiny", config=dict(config or CONFIG), traffic=dict(traffic, **traffic_overrides),
        limits=limits or {}, seed=seed, seconds=seconds, trace=trace,
        trace_dir=Path(tempfile.mkdtemp()), chips=1, t_start=time.perf_counter(), patch=patch,
    )
