"""End-to-end kFkB pipeline training of a GPT model on local devices.

Spawns 4 pipeline stages over 4 host devices (set before jax import) and
trains a reduced GPT for a few hundred steps with the real shard_map
engine under a 2F2B plan, asserting the loss drops.  Pass ``--full`` for
the paper's GPT-Medium (350M — slow on CPU, sized for a real slice).

Run:  PYTHONPATH=src python examples/train_pipeline_e2e.py [--steps 200]
(Set REPRO_SMOKE=1 for the CI-sized run.)
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.gpt import GPT_CONFIGS
from repro.core import ScheduleSpec
from repro.core.schedule import make_plan
from repro.data import SyntheticTextDataset
from repro.optim import linear_warmup_cosine, make_optimizer
from repro.pipeline.engine import make_pipeline_step, stage_mesh
from repro.pipeline.stage import StagedModel
from repro.training import TrainState, create_train_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full", action="store_true",
                    help="paper GPT-Medium (350M); default is a reduced variant")
    args = ap.parse_args()
    smoke = bool(os.environ.get("REPRO_SMOKE"))
    if smoke:
        args.steps = min(args.steps, 20)
        args.seq = min(args.seq, 32)

    cfg = GPT_CONFIGS["GPT-Medium"]
    if smoke:
        cfg = cfg.replace(num_layers=4, d_model=64, d_ff=128, num_heads=4,
                          num_kv_heads=4, head_dim=16, vocab_size=512)
    elif not args.full:
        cfg = cfg.replace(num_layers=4, d_model=256, d_ff=1024, num_heads=8,
                          num_kv_heads=8, head_dim=32, vocab_size=1024)
    cfg = cfg.replace(dtype=jnp.float32, param_dtype=jnp.float32)
    S, M, k = args.stages, args.microbatches, args.k
    assert jax.device_count() >= S

    staged = StagedModel.build(cfg, S)
    params = staged.init_all_stages(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"model {cfg.name}: {n_params/1e6:.1f}M params (stacked over {S} stages), "
          f"plan {k}F{k}B, M={M}")

    opt = make_optimizer("adamw", linear_warmup_cosine(3e-3, 20, args.steps))
    state = create_train_state(params, opt)
    mesh = stage_mesh(S)
    engine = make_pipeline_step(staged, make_plan(S, M, spec=ScheduleSpec(k=k)), mesh)

    @jax.jit
    def step_fn(state, tokens, labels):
        loss, grads = engine(state.params, tokens, labels)
        new_p, new_o, metrics = opt.update(state.params, grads, state.opt_state)
        return TrainState(state.step + 1, new_p, new_o), {"loss": loss, **metrics}

    ds = SyntheticTextDataset(cfg.vocab_size, args.seq, args.batch, seed=0)
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
            if i % 20 == 0 or i == args.steps - 1:
                tput = args.batch * args.seq * len(losses) / (time.time() - t0)
                print(f"step {i:4d}  loss {losses[-1]:.4f}  {tput:,.0f} tok/s")
    if smoke:  # 20 steps: just prove the loop learns at all
        assert losses[-1] < losses[0], (losses[0], losses[-1])
    else:
        assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])
    print(f"\nloss {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps "
          f"under the {k}F{k}B engine — OK")


if __name__ == "__main__":
    main()
