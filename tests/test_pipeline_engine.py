"""Pipeline engine: kFkB execution == unpipelined gradients.

The reference executor runs in-process (single device).  The shard_map
engine needs one device per stage, so it runs in a subprocess with
``xla_force_host_platform_device_count=8`` (the main pytest process must
keep seeing 1 device, per the brief).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kinds import ScheduleSpec
from repro.core.schedule import Op, lower_to_table, make_plan, tick_table
from repro.models.common import ModelConfig
from repro.pipeline.engine import arrival_tables, queue_capacities, reference_pipeline_grads
from repro.pipeline.stage import StagedModel


def _cfg(**kw):
    base = dict(
        name="tiny", family="dense", num_layers=4, d_model=48,
        num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return ModelConfig(**base)


def _data(M, b, T, vocab, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, vocab, (M, b, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, vocab, (M, b, T)), jnp.int32)
    return tokens, labels


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 2, 4])
def test_reference_engine_matches_oracle(k):
    cfg = _cfg()
    S, M, b, T = 4, 4, 2, 16
    staged = StagedModel.build(cfg, S)
    params = staged.init_all_stages(jax.random.PRNGKey(0))
    tokens, labels = _data(M, b, T, cfg.vocab_size)

    def oracle(p):
        return sum(staged.full_loss(p, tokens[m], labels[m]) for m in range(M)) / M

    oloss, ograds = jax.value_and_grad(oracle)(params)
    plan = make_plan(S, M, k)
    rloss, rgrads = reference_pipeline_grads(staged, params, tokens, labels, plan)
    assert float(rloss) == pytest.approx(float(oloss), rel=1e-5)
    for a, g in zip(jax.tree_util.tree_leaves(ograds), jax.tree_util.tree_leaves(rgrads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g), atol=5e-6)


@pytest.mark.slow
def test_moe_hybrid_stage_partition():
    """A jamba-like pattern (mamba+moe / attn) also pipelines correctly."""
    cfg = _cfg(
        family="hybrid", num_layers=4, attn_every=2, attn_offset=1,
        num_experts=4, num_experts_per_tok=2, moe_every=2, moe_offset=0,
        moe_d_ff=64, ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
    )
    S, M, b, T = 2, 4, 2, 16
    staged = StagedModel.build(cfg, S)
    assert staged.layers_per_stage == 2
    params = staged.init_all_stages(jax.random.PRNGKey(1))
    tokens, labels = _data(M, b, T, cfg.vocab_size, seed=1)

    def oracle(p):
        return sum(staged.full_loss(p, tokens[m], labels[m]) for m in range(M)) / M

    oloss, ograds = jax.value_and_grad(oracle)(params)
    rloss, rgrads = reference_pipeline_grads(
        staged, params, tokens, labels, make_plan(S, M, 2)
    )
    assert float(rloss) == pytest.approx(float(oloss), rel=1e-4)
    errs = [
        float(jnp.max(jnp.abs(a - g)))
        for a, g in zip(jax.tree_util.tree_leaves(ograds), jax.tree_util.tree_leaves(rgrads))
    ]
    assert max(errs) < 1e-4


def test_queue_capacity_scales_with_k():
    S, M = 4, 8
    caps = {k: queue_capacities(tick_table(make_plan(S, M, k))) for k in (1, 2, 4)}
    assert caps[2][0] >= caps[1][0]
    assert caps[4][0] >= caps[2][0]  # more grouping -> deeper arrival queues


@pytest.mark.parametrize(
    "kind,k,v,w",
    [
        ("zb_h1", 1, 1, 0),
        ("zb_h1", 2, 1, 0),
        ("zb_h2", 1, 1, 1),
        ("zb_h2", 1, 1, 2),
        ("interleaved", 1, 2, 0),
        ("interleaved", 2, 2, 0),
        ("interleaved_zb", 1, 2, 0),
        ("interleaved_zb", 2, 2, 0),
    ],
)
def test_family_arrival_conservation(kind, k, v, w):
    """Engine-side static tables for the new plan kinds: every non-first
    virtual stage receives exactly M forward activations and every
    non-last one exactly M gradients, and queue pushes balance pops."""
    S, M = 4, 8
    plan = make_plan(S, M, spec=ScheduleSpec(kind=kind, k=k, num_virtual=v, extra_warmup=w))
    grid = lower_to_table(plan).grid
    fwd, bwd = arrival_tables(grid, v)
    V = S * v
    # device s hosts chunks {c}: it receives one fwd per non-first vstage
    for s in range(S):
        n_first = sum(1 for c in range(v) if c * S + s == 0)
        n_last = sum(1 for c in range(v) if c * S + s == V - 1)
        assert fwd[s].sum() == M * (v - n_first)
        assert bwd[s].sum() == M * (v - n_last)
    cap_f, cap_b = queue_capacities(grid, v)
    assert cap_f >= 1 and cap_b >= 1


def test_zb_grid_slots_shared_by_b_and_w():
    """BWD_INPUT reads the activation slot and BWD_WEIGHT frees it: in the
    lowered grid both carry the same slot index as their FWD."""
    plan = make_plan(4, 8, spec=ScheduleSpec(kind="zb_h1"))
    grid = lower_to_table(plan).grid
    for s in range(grid.shape[0]):
        slot_of = {}
        for t in range(grid.shape[1]):
            op, mb, _, slot = (int(x) for x in grid[s, t])
            if op == int(Op.FWD):
                slot_of[mb] = slot
            elif op in (int(Op.BWD_INPUT), int(Op.BWD_WEIGHT)):
                assert slot == slot_of[mb]


def test_arrival_tables_conservation():
    S, M, k = 4, 8, 2
    table = tick_table(make_plan(S, M, k))
    fwd, bwd = arrival_tables(table)
    # every non-first stage receives exactly M forward activations
    for s in range(1, S):
        assert fwd[s].sum() == M
    for s in range(S - 1):
        assert bwd[s].sum() == M


#: the executor-proof matrix: EVERY schedule kind must appear here with at
#: least one cell — test_every_plan_kind_has_an_executor_proof enforces it,
#: so no future kind can ship without gradient parity against jax.grad.
FAMILY_PARITY_CASES = [
    ("kfkb", 1, 1, 0),
    ("kfkb", 2, 1, 0),
    ("zb_h1", 1, 1, 0),
    ("zb_h1", 2, 1, 0),
    ("zb_h2", 1, 1, 1),
    ("zb_h2", 2, 1, 2),
    ("zb_h2", 1, 1, (2, 1)),  # heterogeneous per-stage warmup vector w[s]
    ("interleaved", 2, 2, 0),
    ("interleaved_zb", 1, 2, 0),
    ("interleaved_zb", 2, 2, 0),
    ("interleaved_zb", 1, 2, (1, 2)),  # the "interleaved H2" composition
    ("zbv", 1, 2, 0),  # ZB-V: V-shaped placement, intra-device turn
    ("zbv", 2, 2, 0),  # ...composed with grouping
    ("zbv", 1, 2, (1, 0)),  # ...with a heterogeneous warmup vector
]


def test_every_plan_kind_has_an_executor_proof():
    """Gate (runs in tier 1), auto-derived from the REGISTRY: the
    gradient-parity matrix below must cover every registered kind — adding
    a schedule kind without an engine proof fails here before it can ship.
    Every kind whose registry record claims ``supports_extra_warmup`` must
    additionally prove a NON-UNIFORM w[s] cell (the vector-w execution
    path cannot regress silently either)."""
    from repro.core.kinds import registered_kinds, warmup_kinds

    assert {kind for kind, *_ in FAMILY_PARITY_CASES} == set(registered_kinds())
    vector_proofs = {
        kind for kind, _, _, w in FAMILY_PARITY_CASES
        if isinstance(w, tuple) and len(set(w)) > 1
    }
    assert vector_proofs == set(warmup_kinds())


@pytest.mark.slow
@pytest.mark.parametrize("kind,k,v,w", FAMILY_PARITY_CASES)
def test_reference_engine_family_matches_oracle(kind, k, v, w):
    """Every schedule kind computes the unpipelined gradients exactly: the
    zero-bubble B/W split (at any warmup depth) and the interleaved chunk
    walk are semantics-preserving, not just schedule-length tricks."""
    cfg = _cfg(num_layers=4, d_model=32, d_ff=64, vocab_size=64)
    S, M, b, T = 2, 4, 2, 8
    staged = StagedModel.build(cfg, S * v)
    params = staged.init_all_stages(jax.random.PRNGKey(0))
    tokens, labels = _data(M, b, T, cfg.vocab_size)

    def oracle(p):
        return sum(staged.full_loss(p, tokens[m], labels[m]) for m in range(M)) / M

    oloss, ograds = jax.value_and_grad(oracle)(params)
    plan = make_plan(S, M, spec=ScheduleSpec(kind=kind, k=k, num_virtual=v, extra_warmup=w))
    rloss, rgrads = reference_pipeline_grads(staged, params, tokens, labels, plan)
    assert float(rloss) == pytest.approx(float(oloss), rel=1e-5)
    for a, g in zip(jax.tree_util.tree_leaves(ograds), jax.tree_util.tree_leaves(rgrads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g), atol=5e-6)


#: saved-residual executor proofs, separate from FAMILY_PARITY_CASES (those
#: rows are 4-tuples consumed by the registry gate above): every kind whose
#: registry record claims ``supports_saved_residual`` must prove gradient
#: parity for an SR plan, and the matrix must include a MIXED per-stage
#: vector (the tuner's per-stage DR/SR selection path) and a vector-w cell.
SAVED_RESIDUAL_PARITY_CASES = [
    ("zb_h1", 1, 1, 0, "saved_residual"),
    ("zb_h1", 2, 1, 0, ("saved_residual", "double_remat")),  # mixed per-stage
    ("zb_h2", 1, 1, (2, 1), "saved_residual"),  # vector-w + SR
    ("interleaved_zb", 1, 2, 0, "saved_residual"),
    ("zbv", 1, 2, 0, "saved_residual"),
]


def test_every_saved_residual_kind_has_an_executor_proof():
    """Gate (tier 1), auto-derived from the registry: flagging a kind
    ``supports_saved_residual`` without an SR engine proof fails here."""
    from repro.core.kinds import saved_residual_kinds

    assert {kind for kind, *_ in SAVED_RESIDUAL_PARITY_CASES} == set(
        saved_residual_kinds()
    )
    mixed = [
        pol for *_, pol in SAVED_RESIDUAL_PARITY_CASES
        if isinstance(pol, tuple) and len(set(pol)) > 1
    ]
    assert mixed, "the per-stage DR/SR selection path needs a mixed-vector proof"


@pytest.mark.slow
@pytest.mark.parametrize("kind,k,v,w,pol", SAVED_RESIDUAL_PARITY_CASES)
def test_reference_engine_saved_residual_matches_oracle(kind, k, v, w, pol):
    """saved_residual keeps B's combined-vjp pullback and replays it at W
    with no second rematerialization — the gradients must still be the
    unpipelined jax.grad, for every SR-capable kind and for mixed
    per-stage policy vectors."""
    cfg = _cfg(num_layers=4, d_model=32, d_ff=64, vocab_size=64)
    S, M, b, T = 2, 4, 2, 8
    staged = StagedModel.build(cfg, S * v)
    params = staged.init_all_stages(jax.random.PRNGKey(0))
    tokens, labels = _data(M, b, T, cfg.vocab_size)

    def oracle(p):
        return sum(staged.full_loss(p, tokens[m], labels[m]) for m in range(M)) / M

    oloss, ograds = jax.value_and_grad(oracle)(params)
    plan = make_plan(S, M, spec=ScheduleSpec(
        kind=kind, k=k, num_virtual=v, extra_warmup=w, zb_policy=pol,
    ))
    rloss, rgrads = reference_pipeline_grads(staged, params, tokens, labels, plan)
    assert float(rloss) == pytest.approx(float(oloss), rel=1e-5)
    for a, g in zip(jax.tree_util.tree_leaves(ograds), jax.tree_util.tree_leaves(rgrads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g), atol=5e-6)


@pytest.mark.slow
def test_reference_engine_matches_oracle_after_weight_placement():
    """A W-placement-optimized plan (the non-uniform-cost refinement of
    repro.core.placement) reorders BWD_WEIGHT tasks only — the engines must
    still reproduce the jax.grad oracle exactly."""
    from repro.core import StageCosts, optimize_weight_placement

    cfg = _cfg(num_layers=4, d_model=32, d_ff=64, vocab_size=64)
    S, M, b, T = 2, 4, 2, 8
    staged = StagedModel.build(cfg, S)
    params = staged.init_all_stages(jax.random.PRNGKey(0))
    tokens, labels = _data(M, b, T, cfg.vocab_size)

    def oracle(p):
        return sum(staged.full_loss(p, tokens[m], labels[m]) for m in range(M)) / M

    oloss, ograds = jax.value_and_grad(oracle)(params)
    plan = make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=(2, 1)))
    skew = StageCosts(
        fwd_time=[1.0, 0.8], bwd_time=[3.0, 2.0],
        fwd_bytes=[1.0] * S, bwd_bytes=[1.0] * S,
        bwd_input_time=[0.7, 1.1], bwd_weight_time=[2.3, 0.9],
    )
    opt = optimize_weight_placement(plan, skew, {(0, 1): 2.0, (1, 0): 2.0})
    rloss, rgrads = reference_pipeline_grads(staged, params, tokens, labels, opt)
    assert float(rloss) == pytest.approx(float(oloss), rel=1e-5)
    for a, g in zip(jax.tree_util.tree_leaves(ograds), jax.tree_util.tree_leaves(rgrads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g), atol=5e-6)


_SPMD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.kinds import ScheduleSpec
    from repro.core.schedule import make_plan
    from repro.models.common import ModelConfig
    from repro.pipeline.stage import StagedModel
    from repro.pipeline.engine import make_pipeline_step, stage_mesh

    cfg = ModelConfig("tiny", "dense", num_layers=4, d_model=48, num_heads=4,
                      num_kv_heads=2, d_ff=96, vocab_size=128,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    S, M, b, T = 4, 4, 2, 16
    staged = StagedModel.build(cfg, S)
    params = staged.init_all_stages(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, (M, b, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 128, (M, b, T)), jnp.int32)

    def check(plan, staged, params, oloss, ograds, dp=None):
        mesh = stage_mesh(S, 2 if dp else None)
        step = jax.jit(make_pipeline_step(staged, plan, mesh, data_axis=dp))
        with mesh:
            sloss, sgrads = step(params, tokens, labels)
        assert abs(float(sloss) - float(oloss)) < 1e-5, (plan.name, dp, float(sloss), float(oloss))
        flat_o, _ = jax.tree_util.tree_flatten_with_path(ograds)
        flat_s, _ = jax.tree_util.tree_flatten_with_path(sgrads)
        for (pa, a), (_, g) in zip(flat_o, flat_s):
            name = pa[0].key
            if name in ("embed", "final_norm"):
                a = jnp.broadcast_to(a.sum(0, keepdims=True), a.shape)
            assert float(jnp.max(jnp.abs(a - g))) < 5e-6, (plan.name, dp, name)
        print(f"plan={plan.name} dp={dp} OK")

    def oracle(p):
        return sum(staged.full_loss(p, tokens[m], labels[m]) for m in range(M)) / M
    oloss, ograds = jax.value_and_grad(oracle)(params)
    for k, dp in [(1, None), (2, None), (2, "data"), (4, None)]:
        check(make_plan(S, M, k), staged, params, oloss, ograds, dp)
    # schedule family: zero-bubble split (H1 + deeper-warmup H2) and
    # interleaved virtual stages (plain + joint interleaved-ZB)
    check(make_plan(S, M, spec=ScheduleSpec(kind="zb_h1", k=2)),
          staged, params, oloss, ograds)
    check(make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=1)),
          staged, params, oloss, ograds)
    # heterogeneous per-stage warmup vector w[s] through the REAL engine
    check(make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=(0, 1, 2, 1))),
          staged, params, oloss, ograds)
    v = 2  # S*v = 8 virtual stages -> the 8-layer sibling config
    cfg_v = ModelConfig("tiny8", "dense", num_layers=8, d_model=48, num_heads=4,
                        num_kv_heads=2, d_ff=96, vocab_size=128,
                        dtype=jnp.float32, param_dtype=jnp.float32)
    staged_v = StagedModel.build(cfg_v, S * v)
    params_v = staged_v.init_all_stages(jax.random.PRNGKey(0))
    def oracle_v(p):
        return sum(staged_v.full_loss(p, tokens[m], labels[m]) for m in range(M)) / M
    oloss_v, ograds_v = jax.value_and_grad(oracle_v)(params_v)
    check(make_plan(S, M, spec=ScheduleSpec(kind="interleaved", num_virtual=v)),
          staged_v, params_v, oloss_v, ograds_v)
    check(make_plan(S, M, spec=ScheduleSpec(kind="interleaved_zb", num_virtual=v)),
          staged_v, params_v, oloss_v, ograds_v)
    # the interleaved-H2 composition (per-stage warmup over the ring)
    check(make_plan(S, M, spec=ScheduleSpec(kind="interleaved_zb", num_virtual=v,
                                            extra_warmup=(1, 0, 2, 1))),
          staged_v, params_v, oloss_v, ograds_v)
    # ZB-V: the V-shaped (non-looped) placement through the REAL engine —
    # forwards ride BOTH ring directions and the turn is an intra-device
    # loopback, exercising every transfer channel at once
    check(make_plan(S, M, spec=ScheduleSpec(kind="zbv")),
          staged_v, params_v, oloss_v, ograds_v)
    check(make_plan(S, M, spec=ScheduleSpec(kind="zbv", extra_warmup=(1, 0, 2, 1))),
          staged_v, params_v, oloss_v, ograds_v)
    # saved_residual through the REAL engine: B's combined-vjp residuals
    # ride the per-slot f32 row and W replays the pullback with no second
    # rematerialization — uniform SR and the tuner's MIXED per-stage vector
    check(make_plan(S, M, spec=ScheduleSpec(kind="zb_h1", zb_policy="saved_residual")),
          staged, params, oloss, ograds)
    check(make_plan(S, M, spec=ScheduleSpec(
              kind="zb_h1", k=2,
              zb_policy=("saved_residual", "double_remat",
                         "saved_residual", "double_remat"))),
          staged, params, oloss, ograds)
    print("SPMD_ENGINE_ALL_OK")
    """
)


@pytest.mark.slow
def test_spmd_engine_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SPMD_SCRIPT],
        capture_output=True, text=True, env=env, timeout=1500,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SPMD_ENGINE_ALL_OK" in proc.stdout
