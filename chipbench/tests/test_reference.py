"""The plain reference against the program's own unpipelined model and
forward pass, at a tiny size in float32 on the CPU."""

import jax
import numpy as np
import pytest

from chipbench import program
from chipbench.reference import gpt as ref
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def setup():
    cfg = tiny.CONFIG
    w = ref.make_weights(program.seed_key(2**40 + 7, 0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg["vocab_size"])
    lab = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg["vocab_size"])
    return cfg, w, tok, lab


@pytest.mark.parametrize("stages", [1, 2, 4])
def test_loss_and_grad_match_staged_full_loss(setup, stages):
    from repro.pipeline.stage import StagedModel

    cfg, w, tok, lab = setup
    staged = StagedModel.build(program.model_config(cfg), stages)
    params = program.to_staged(w, stages)
    spec = jax.eval_shape(lambda: staged.init_all_stages(jax.random.PRNGKey(0)))
    program.check_layout(params, spec, "staged")
    l_prog, g_prog = jax.value_and_grad(staged.full_loss)(params, tok, lab)
    l_ref, g_ref = jax.value_and_grad(ref.loss)(w, tok, lab, cfg)
    assert abs(float(l_prog) - float(l_ref)) < 1e-5
    back = program.from_staged(g_prog, stages)
    # the unpipelined model reads the tied table on its first stage (embedding)
    # and its last (head); the tied gradient is their sum
    back["wte"] = g_prog["embed"]["table"].sum(0)
    for (name, a), (_, b) in zip(ref.pieces(g_ref), ref.pieces(back)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-7, err_msg=name)


def test_forward_matches_api(setup):
    from repro.models import api

    cfg, w, tok, _ = setup
    mc = program.model_config(cfg)
    params = program.to_api(w)
    program.check_layout(params, jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0), mc)), "api")
    got = api.forward_fn(params, mc, {"tokens": tok})[0]
    want = ref.forward(w, tok, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_layout_round_trip(setup):
    cfg, w, _, _ = setup
    back = program.from_staged(program.to_staged(w, 2), 2)
    for (n, a), (_, b) in zip(ref.pieces(w), ref.pieces(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=n)


def test_layout_change_is_refused(setup):
    cfg, w, _, _ = setup
    params = program.to_api(w)
    params["blocks"][0]["attn"]["wq"]["w"] = params["blocks"][0]["attn"]["wq"]["w"][:, :, :8]
    with pytest.raises(RuntimeError):
        program.check_layout(params, program.to_api(w), "api")


def test_fp8_mode_differs_and_keys_use_the_whole_seed(setup):
    cfg, w, tok, lab = setup
    a = float(ref.loss(w, tok, lab, cfg, "f32"))
    b = float(ref.loss(w, tok, lab, cfg, "fp8"))
    assert a != b and abs(a - b) < 0.1
    k1, k2 = program.seed_key(5, 0), program.seed_key(2**40 + 5, 0)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
