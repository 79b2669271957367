"""End-to-end training driver.

Two execution modes:

* ``--mode spmd``     — pjit data/tensor-parallel train step (any arch).
* ``--mode pipeline`` — the paper's kFkB shard_map engine with the
  Ada-Grouper auto-tuner choosing k online (GPT-style configs; requires
  at least ``--stages`` local devices — set
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for CPU runs).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
      --steps 50 --batch 8 --seq 64
  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python -m repro.launch.train --mode pipeline --gpt GPT-Medium \
      --layers 8 --stages 4 --steps 20 --batch 8 --seq 64 --k 2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import get_arch
from repro.data import SyntheticTextDataset
from repro.models import api
from repro.optim import linear_warmup_cosine, make_optimizer
from repro.runtime import enable_persistent_cache
from repro.training import create_train_state, make_train_step


def _batch_dict(cfg, batch):
    if cfg.family == "encdec":
        S = max(batch.tokens.shape[1] // 8, 1)
        B = batch.tokens.shape[0]
        return {
            "src_embeds": (batch.embeds if batch.embeds is not None
                           else jnp.zeros((B, S, cfg.d_model), jnp.float32)),
            "tgt_tokens": batch.tokens,
            "labels": batch.labels,
        }
    if cfg.family == "vlm":
        B, T = batch.tokens.shape
        return {
            "embeds": (batch.embeds if batch.embeds is not None
                       else jnp.zeros((B, T, cfg.d_model), jnp.float32)),
            "labels": batch.labels,
            "mrope_positions": jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[None, None], (3, B, T)
            ),
        }
    return {"tokens": batch.tokens, "labels": batch.labels}


def run_spmd(args):
    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke else spec.model
    params = api.init_params(jax.random.PRNGKey(args.seed), cfg)
    opt = make_optimizer(
        spec.optimizer, linear_warmup_cosine(args.lr, args.warmup, args.steps)
    )
    state = create_train_state(params, opt)
    if args.ckpt_dir and (step0 := latest_step(args.ckpt_dir)) is not None:
        state = load_checkpoint(args.ckpt_dir, step0, state)
        print(f"resumed from step {step0}")
    step_fn = jax.jit(
        make_train_step(
            lambda p, b: api.loss_fn(p, cfg, b), opt,
            num_microbatches=args.microbatches,
        )
    )
    embed_dim = cfg.d_model if cfg.family in ("vlm", "encdec") else None
    ds = SyntheticTextDataset(
        cfg.vocab_size, args.seq, args.batch, seed=args.seed,
        embed_dim=embed_dim,
        embed_len=(args.seq if cfg.family == "vlm" else max(args.seq // 8, 1)),
    )
    t0 = time.time()
    losses = []
    for i in range(int(state.step), args.steps):
        b = ds.batch_at(i)
        state, m = step_fn(state, _batch_dict(cfg, b))
        losses.append(float(m["loss"]))
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            tput = args.batch * args.seq * (len(losses)) / max(dt, 1e-9)
            print(f"step {i:5d}  loss {losses[-1]:.4f}  lr {float(m['lr']):.2e}  "
                  f"{tput:,.0f} tok/s")
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1, state)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, state)
    assert losses[-1] < losses[0], "training must reduce loss"
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


def run_pipeline(args):
    from repro.configs.gpt import GPT_CONFIGS
    from repro.core.schedule import make_plan
    from repro.pipeline.engine import make_pipeline_step, stage_mesh
    from repro.pipeline.stage import StagedModel
    from repro.training import TrainState

    cfg = GPT_CONFIGS[args.gpt].replace(
        num_layers=args.layers, vocab_size=1024, dtype=jnp.float32
    )
    S = args.stages
    assert jax.device_count() >= S, (
        f"pipeline mode needs >= {S} devices "
        "(XLA_FLAGS=--xla_force_host_platform_device_count=N)"
    )
    staged = StagedModel.build(cfg, S)
    params = staged.init_all_stages(jax.random.PRNGKey(args.seed))
    opt = make_optimizer("adamw", linear_warmup_cosine(args.lr, args.warmup, args.steps))
    state = create_train_state(params, opt)
    M = args.microbatches or max(S, args.batch // 2)
    plan = make_plan(S, M, args.k)
    mesh = stage_mesh(S)
    engine = make_pipeline_step(staged, plan, mesh)

    @jax.jit
    def step_fn(state, tokens, labels):
        loss, grads = engine(state.params, tokens, labels)
        new_p, new_o, metrics = opt.update(state.params, grads, state.opt_state)
        return TrainState(state.step + 1, new_p, new_o), {"loss": loss, **metrics}

    ds = SyntheticTextDataset(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    b_mb = args.batch // M
    losses = []
    t0 = time.time()
    with mesh:
        for i in range(args.steps):
            b = ds.batch_at(i)
            tokens = b.tokens.reshape(M, b_mb, args.seq)
            labels = b.labels.reshape(M, b_mb, args.seq)
            state, m = step_fn(state, tokens, labels)
            losses.append(float(m["loss"]))
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:5d}  loss {losses[-1]:.4f}  "
                      f"plan {plan.name}  ({time.time()-t0:.1f}s)")
    assert losses[-1] < losses[0], "training must reduce loss"
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}  [{plan.name}]")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="spmd", choices=["spmd", "pipeline"])
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--gpt", default="GPT-Medium", help="pipeline mode: GPT config")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--k", type=int, default=2, help="kFkB group count")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()
    enable_persistent_cache()
    if args.mode == "pipeline":
        run_pipeline(args)
    else:
        run_spmd(args)


if __name__ == "__main__":
    main()
