"""§5.4 plan switching on the REAL engine: zero-cost mid-training swap.

The paper: "Switching between schedule plans does not require variable
buffers to be dumped out and restored ... the variance of micro-batch size
or group member count [has] no effect on model parameters."

Here both the 1F1B and 2F2B engines are compiled up front against the SAME
parameter pytree; training starts under 1F1B, "the tuner" switches to 2F2B
mid-run, and the loss curve continues seamlessly (same params, same
optimizer state, different schedule).  We also assert both engines produce
identical gradients for identical params — the switch is mathematically
invisible.

Run:  PYTHONPATH=src python examples/engine_plan_switch.py
(Set REPRO_SMOKE=1 for the CI-sized run.)
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ScheduleSpec
from repro.core.schedule import make_plan
from repro.data import SyntheticTextDataset
from repro.models.common import ModelConfig
from repro.optim import make_optimizer
from repro.pipeline.engine import make_pipeline_step, stage_mesh
from repro.pipeline.stage import StagedModel
from repro.training import TrainState, create_train_state

SMOKE = bool(os.environ.get("REPRO_SMOKE"))
S, M, B = 4, 4, 8
T = 16 if SMOKE else 32
STEPS = 12 if SMOKE else 60

cfg = ModelConfig("switch-demo", "dense", num_layers=4,
                  d_model=64 if SMOKE else 128, num_heads=4,
                  num_kv_heads=2, d_ff=128 if SMOKE else 256, vocab_size=512,
                  dtype=jnp.float32, param_dtype=jnp.float32)
staged = StagedModel.build(cfg, S)
params = staged.init_all_stages(jax.random.PRNGKey(0))
opt = make_optimizer("adamw", schedule=lambda s: jnp.float32(2e-3))
state = create_train_state(params, opt)
mesh = stage_mesh(S)

# ALL candidate plans compiled up front (the Ada-Grouper scheduler keeps
# every task graph alive, §3.2.1)
engines = {
    k: make_pipeline_step(staged, make_plan(S, M, spec=ScheduleSpec(k=k)), mesh)
    for k in (1, 2)
}


def step_with(k):
    engine = engines[k]

    @jax.jit
    def step(state, tokens, labels):
        loss, grads = engine(state.params, tokens, labels)
        new_p, new_o, m = opt.update(state.params, grads, state.opt_state)
        return TrainState(state.step + 1, new_p, new_o), loss

    return step


steps = {k: step_with(k) for k in engines}
ds = SyntheticTextDataset(cfg.vocab_size, T, B, seed=0)
b_mb = B // M

with mesh:
    # gradient equivalence at the switch point: both plans, same params
    b0 = ds.batch_at(0)
    tok = b0.tokens.reshape(M, b_mb, T)
    lab = b0.labels.reshape(M, b_mb, T)
    l1, g1 = engines[1](state.params, tok, lab)
    l2, g2 = engines[2](state.params, tok, lab)
    assert abs(float(l1) - float(l2)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    print("1F1B and 2F2B gradients identical for identical params ✓")

    losses, plan_of_step = [], []
    k = 1
    for i in range(STEPS):
        if i == STEPS // 2:
            k = 2  # "network preempted" -> tuner switches plans; params and
            # optimizer state carry over untouched
            print(f"-- switching plan 1F1B -> 2F2B at step {i} --")
        b = ds.batch_at(i)
        state, loss = steps[k](
            state, b.tokens.reshape(M, b_mb, T), b.labels.reshape(M, b_mb, T)
        )
        losses.append(float(loss))
        plan_of_step.append(k)
        if i % 10 == 0 or i == STEPS - 1:
            print(f"step {i:3d}  plan {k}F{k}B  loss {losses[-1]:.4f}")

pre = losses[STEPS // 2 - 1]
post = losses[STEPS // 2]
assert abs(post - pre) < 0.5, "loss must be continuous across the switch"
if not SMOKE:  # the smoke run is too short to earn a meaningful loss drop
    assert losses[-1] < losses[0] - 0.3
print(f"\nloss {losses[0]:.3f} -> {losses[-1]:.3f}; "
      f"switch discontinuity {abs(post - pre):.4f} (≈ one normal step delta). "
      "Plan switching is free — paper §5.4 reproduced on the real engine.")
