"""``correct`` at a tiny size on the CPU: a sound run passes, and the run
comes out not correct with the timed path broken underneath (a step that
leaves its state unchanged, half of each batch left out, a served token
altered where it is produced) and with the control, the reference in fp8,
in the program's place. The harness's look for a chip is skipped: the
drivers are called directly."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare
from chipbench.drivers import serve, train
from chipbench.tests import tiny

# the program computes in float32 here, so sound runs read round-off only
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}
SERVE_LIMITS = {"logit_gap": 1e-4}

# wide enough that the fp8 control puts other tokens first
SERVE_CONFIG = dict(tiny.CONFIG, hidden_size=128, intermediate_size=512, head_dim=64,
                    vocab_size=2048, initializer_range=0.2)


def _train(patch=None):
    ctx = tiny.context(tiny.TRAIN, limits=TRAIN_LIMITS, seconds=0.3, patch=patch)
    return train.run(ctx)


def test_sound_training_run_is_correct():
    out = _train()
    assert out["correct"], out["checks"]


def test_state_left_unchanged_is_caught():
    def patch(rt):
        step = rt.run_iteration

        def stuck(tokens, labels):
            before = jax.tree_util.tree_map(jnp.copy, rt.state)
            result = step(tokens, labels)
            rt.state = before
            return result

        rt.run_iteration = stuck

    out = _train(patch)
    assert not out["correct"]
    assert dict((n, v) for n, v, _ in out["checks"])["change_gap"] == pytest.approx(1.0)


def test_half_batch_left_out_is_caught():
    def patch(rt):
        step = rt.run_iteration

        def half(tokens, labels):
            M = tokens.shape[0]
            return step(*(x[: M // 2].repeat(2, axis=0) for x in (tokens, labels)))

        rt.run_iteration = half

    assert not _train(patch)["correct"]


def test_training_control_is_caught():
    ctx = tiny.context(tiny.TRAIN, limits=TRAIN_LIMITS)
    f32 = train.reference_readings(ctx)
    ok, _ = compare.judge(compare.train_gaps(train.reference_readings(ctx, "fp8"), f32), TRAIN_LIMITS)
    assert not ok


def _serve(patch=None):
    ctx = tiny.context(tiny.SERVE, config=SERVE_CONFIG, limits=SERVE_LIMITS, seconds=2.0,
                       patch=patch)
    return ctx, serve.run(ctx)


def test_sound_serving_run_is_correct():
    _, out = _serve()
    assert out["correct"] and out["failed"] == 0, out["checks"]


def test_altered_token_is_caught():
    def patch(engine):
        tick = engine.decode_tick

        def altered(in_flight):
            tick(in_flight)
            for inf in in_flight:
                out = engine.outputs[inf.request.rid]
                out[-1] = (out[-1] + 1) % SERVE_CONFIG["vocab_size"]

        engine.decode_tick = altered

    _, out = _serve(patch)
    assert not out["correct"]


def test_serving_control_is_caught():
    ctx = tiny.context(tiny.SERVE, config=SERVE_CONFIG, seconds=2.0)
    engine, _ = serve.build(ctx)
    from chipbench.common import CompileCounter, Profile

    out = serve.window(ctx, engine, Profile(ctx), CompileCounter())
    reqs = serve.sample(ctx, out["finished"])
    assert serve.logit_gap(ctx, reqs, engine.outputs) <= SERVE_LIMITS["logit_gap"]
    control = serve.logit_gap(ctx, reqs, engine.outputs, mode="fp8", pick="own")
    assert control > SERVE_LIMITS["logit_gap"]
