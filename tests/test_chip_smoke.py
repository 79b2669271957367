"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
step-1 gradient check is tight enough to catch a wrong schedule."""

import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gpt import GPT_CONFIGS
from repro.core.schedule import make_plan
from repro.pipeline.engine import reference_pipeline_grads
from repro.pipeline.stage import StagedModel

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT,
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def grad_case():
    """A bf16 GPT (Table 1 layout, cut to toy widths) on 4 stages, M=4: the
    unpipelined oracle's loss and grads, and the reference executor's 1F1B
    grads."""
    smoke = _load_smoke()
    cfg = GPT_CONFIGS["GPT-Medium"].replace(
        name="gpt-toy", num_layers=4, d_model=32, d_ff=128, num_heads=2,
        num_kv_heads=2, head_dim=16, vocab_size=128,
    )
    S, M, T = 4, 4, 16
    staged = StagedModel.build(cfg, S)
    params = staged.init_all_stages(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, (M, 1, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 128, (M, 1, T)), jnp.int32)
    ref = smoke.oracle_loss_and_grads(staged, params, tokens, labels)
    plan = make_plan(S, M, 1)
    pipe = jax.jit(lambda p, t, lbl: reference_pipeline_grads(staged, p, t, lbl, plan))(
        params, tokens, labels
    )
    return smoke, staged, params, tokens, labels, ref, pipe


def test_grad_check_accepts_the_pipeline(grad_case):
    smoke, _, _, _, _, (ref_loss, ref_grads), (loss, grads) = grad_case
    assert abs(float(loss) - ref_loss) <= smoke.LOSS_TOL
    assert smoke.compare_grads(grads, ref_grads, summed_replicated=False) <= smoke.GRAD_TOL


def _drop_last_microbatch(smoke, staged, params, tokens, labels, grads):
    """What a schedule that never ran the last micro-batch would return."""
    M = tokens.shape[0]
    _, kept = smoke.oracle_loss_and_grads(staged, params, tokens[:-1], labels[:-1])
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(grads), [g * (M - 1) / M for g in kept]
    )


def _swap_stage_rows(smoke, staged, params, tokens, labels, grads):
    """Stage 1's block gradients landing on stage 2 and back."""
    swap = np.array([0, 2, 1, 3])
    return {**grads, "blocks": jax.tree_util.tree_map(lambda g: g[swap], grads["blocks"])}


@pytest.mark.parametrize("wrong", [_drop_last_microbatch, _swap_stage_rows])
def test_grad_check_rejects_a_wrong_schedule(grad_case, wrong):
    smoke, staged, params, tokens, labels, (_, ref_grads), (_, grads) = grad_case
    bad = wrong(smoke, staged, params, tokens, labels, grads)
    with pytest.raises(smoke.SmokeFailure):
        smoke.compare_grads(bad, ref_grads, summed_replicated=False)
