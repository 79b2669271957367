"""Live plan-switch runtime (repro.runtime).

Tier-1 covers the pure logic: bitwise layout re-stacking (the §5.4
"no effect on model parameters" contract extended across the interleaved
boundary), compiled-step cache mechanics (fake programs — no XLA), and the
passive-telemetry inversion.  The slow tier proves the headline behaviours
on real compiled steps: a kfkb -> zb_h2 -> interleaved_zb mid-stream switch
matching an unswitched per-segment reference to 5e-6, and the seeded
Fig-10 regime run meeting the acceptance gates (>= 2 kind switches, warm
switch latency < 5% of an iteration, oracle-parity gradients, precompile
hit rate >= 0.8).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    NetworkProfiler,
    ScheduleSpec,
    StableTrace,
    StageCosts,
    make_plan,
    simulate_plan,
    uniform_network,
)
from repro.models.common import ModelConfig
from repro.optim import make_optimizer
from repro.pipeline.stage import StagedModel
from repro.runtime import (
    CompiledStepCache,
    PassiveLinkFeed,
    PlanRuntime,
    TelemetryBus,
    invert_effective_bandwidth,
    restack_train_state,
)
from repro.training import TrainState, create_train_state


def _cfg(num_layers=4, d_model=16, **kw):
    base = dict(
        name="rt-tiny", family="dense", num_layers=num_layers, d_model=d_model,
        num_heads=2, num_kv_heads=2, d_ff=2 * d_model, vocab_size=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return ModelConfig(**base)


def _opt():
    return make_optimizer("adamw", schedule=lambda s: jnp.float32(1e-3))


def _data(B, T, seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, vocab, (B, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, vocab, (B, T)), jnp.int32)
    return tokens, labels


# ---------------------------------------------------------------------------
# Re-stacking (pure logic, tier 1)
# ---------------------------------------------------------------------------


def _flat_state(S=2, L=4, key=0):
    staged = StagedModel.build(_cfg(num_layers=L), S)
    params = staged.init_all_stages(jax.random.PRNGKey(key))
    return create_train_state(params, _opt())


def _assert_tree_bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert bool((np.asarray(x) == np.asarray(y)).all())


def test_restack_round_trip_is_bitwise():
    """flat -> v -> flat must be the identity, bit for bit, on params AND
    optimizer moments (the carried-over state is never re-derived)."""
    S = 2
    state = _flat_state(S)
    there = restack_train_state(state, S, 1, 2)
    back = restack_train_state(there, S, 2, 1)
    _assert_tree_bitwise(state, back)


def test_restack_block_layout_matches_virtual_stage_model():
    """Expanded block leaves must line up exactly with what the S*v-stage
    sibling model would stack: global virtual stage j owns the flat
    model's layers [j*reps/v, (j+1)*reps/v)."""
    S, v, L = 2, 2, 4
    state = _flat_state(S, L)
    expanded = restack_train_state(state, S, 1, v)
    flat_blocks = jax.tree_util.tree_leaves(state.params["blocks"])
    exp_blocks = jax.tree_util.tree_leaves(expanded.params["blocks"])
    for fl, ex in zip(flat_blocks, exp_blocks):
        reps = fl.shape[1]
        assert ex.shape[:2] == (S * v, reps // v)
        want = np.asarray(fl).reshape((S * v, reps // v) + fl.shape[2:])
        assert bool((np.asarray(ex) == want).all())


def test_restack_collapse_keeps_authoritative_replicated_rows():
    """Replicated leaves (embed / final_norm) diverge during training: only
    virtual stage 0 (token embedding) and the LAST virtual stage (final
    norm + unembed head) receive gradients.  Collapse must keep exactly
    those two authoritative copies — dropping the last virtual row would
    throw away the trained unembed head."""
    S, v = 2, 2
    state = _flat_state(S)
    expanded = restack_train_state(state, S, 1, v)

    # simulate divergence: mark each virtual row of embed with its index
    def mark(path, x):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        if "embed" in keys or "final_norm" in keys:
            rows = jnp.arange(x.shape[0], dtype=x.dtype).reshape(
                (-1,) + (1,) * (x.ndim - 1)
            )
            return x + rows
        return x

    marked = jax.tree_util.tree_map_with_path(mark, expanded)
    collapsed = restack_train_state(marked, S, v, 1)
    unmarked = restack_train_state(expanded, S, v, 1)
    for name in ("embed", "final_norm"):
        got = jax.tree_util.tree_leaves(collapsed.params[name])[0]
        base = jax.tree_util.tree_leaves(unmarked.params[name])[0]
        markers = np.asarray(got) - np.asarray(base)
        # flat stage 0 carries virtual row 0; flat stage S-1 carries virtual
        # row S*v - 1 (NOT its first chunk's row)
        assert float(markers[0].ravel()[0]) == 0.0
        assert float(markers[-1].ravel()[0]) == float(S * v - 1)


def test_restack_rejects_unsplittable_reps():
    S = 2
    state = _flat_state(S, L=2)  # 1 layer/stage: cannot split over v=2
    with pytest.raises(ValueError, match="reps"):
        restack_train_state(state, S, 1, 2)


# ---------------------------------------------------------------------------
# Compiled-step cache (fake programs, tier 1)
# ---------------------------------------------------------------------------


class _FakeJitted:
    """Stands in for jax.jit(fn): .lower(*args).compile() -> callable."""

    def __init__(self, table, log, delay=0.0):
        self.table, self.log, self.delay = table, log, delay

    def lower(self, *args):
        return self

    def compile(self):
        if self.delay:
            time.sleep(self.delay)
        self.log.append(self.table.plan.name)
        return lambda *a: ("ran", self.table.plan.name)


def _fake_cache(log, delay=0.0):
    return CompiledStepCache(lambda table: (_FakeJitted(table, log, delay), ()))


def test_cache_warm_hit_and_cold_miss_accounting():
    log = []
    cache = _fake_cache(log)
    t1 = make_plan(2, 4, 1).lower()
    t2 = make_plan(2, 4, 2).lower()
    cache.precompile([t1])
    cache.wait_idle()
    e1 = cache.get(t1)
    assert e1.source == "precompile" and cache.stats.warm_hits == 1
    e2 = cache.get(t2)  # never announced: synchronous cold compile
    assert e2.source == "demand" and cache.stats.cold_misses == 1
    assert cache.get(t2).compiled is e2.compiled  # now cached
    assert log.count(t1.plan.name) == 1 and log.count(t2.plan.name) == 1
    assert cache.stats.hit_rate == pytest.approx(2 / 3)
    cache.shutdown()


def test_cache_get_joins_inflight_background_compile():
    log = []
    cache = _fake_cache(log, delay=0.2)
    t1 = make_plan(2, 4, 1).lower()
    cache.precompile([t1])
    entry = cache.get(t1)  # must join the in-flight compile, not duplicate it
    assert entry.source == "precompile"
    assert cache.stats.inflight_hits == 1 and cache.stats.cold_misses == 0
    assert log == [t1.plan.name]  # compiled exactly once
    cache.shutdown()


def test_cache_key_distinguishes_refined_lowerings():
    """A +Wopt-refined lowering shares every schedule coordinate with its
    base plan but has a different grid — it must be a distinct entry (the
    unrolled tick program IS the grid)."""
    from repro.core import optimize_weight_placement

    plan = make_plan(2, 4, spec=ScheduleSpec(kind="zb_h2", extra_warmup=1))
    costs = StageCosts(
        fwd_time=[1.0, 0.8], bwd_time=[3.0, 2.0],
        fwd_bytes=[1.0, 1.0], bwd_bytes=[1.0, 1.0],
        bwd_input_time=[0.7, 1.1], bwd_weight_time=[2.3, 0.9],
    )
    refined = optimize_weight_placement(plan, costs, {(0, 1): 2.0, (1, 0): 2.0})
    k_base = CompiledStepCache.plan_key(plan.lower())
    k_ref = CompiledStepCache.plan_key(refined.lower())
    if refined.orders != plan.orders:  # search found a move on these costs
        assert k_base != k_ref
    assert CompiledStepCache.plan_key(plan.lower()) == k_base  # stable


def test_cache_precompile_thread_safety_under_concurrent_gets():
    log = []
    cache = _fake_cache(log, delay=0.01)
    tables = [make_plan(2, 8, k).lower() for k in (1, 2, 4, 8)]
    cache.precompile(tables)
    results = []

    def worker(t):
        results.append(cache.get(t).compiled()[1])

    threads = [threading.Thread(target=worker, args=(t,)) for t in tables * 2]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    cache.wait_idle()
    assert sorted(log) == sorted(t.plan.name for t in tables)  # once each
    assert cache.stats.cold_misses == 0
    cache.shutdown()


# ---------------------------------------------------------------------------
# Passive telemetry (simulation only, tier 1)
# ---------------------------------------------------------------------------


def test_invert_effective_bandwidth_recovers_ground_truth():
    """Observed length simulated under a known uniform bandwidth must invert
    back to that bandwidth (the scalar inverse problem is well-posed where
    the schedule is communication-sensitive)."""
    S, M = 4, 8
    plan = make_plan(S, M, 2)
    costs = StageCosts.uniform(S, 1.0, act_bytes=4.0)
    for bw_true in (0.5, 2.0, 8.0):
        net = uniform_network(S, lambda: StableTrace(bw_true))
        observed = simulate_plan(plan, costs, net).pipeline_length
        bw = invert_effective_bandwidth(plan, costs, observed)
        assert bw == pytest.approx(bw_true, rel=0.05)


def test_invert_effective_bandwidth_saturates_cleanly():
    S, M = 4, 8
    plan = make_plan(S, M, 2)
    costs = StageCosts.uniform(S, 1.0, act_bytes=4.0)
    compute_bound = simulate_plan(
        plan, costs, uniform_network(S, lambda: StableTrace(1e30))
    ).pipeline_length
    assert invert_effective_bandwidth(plan, costs, compute_bound * 0.5) == 1e15
    assert invert_effective_bandwidth(plan, costs, 1e12) == 1e-6


def test_passive_feed_keeps_profiler_windows_fresh():
    S, M = 4, 8
    bw_true = 2.0
    plan = make_plan(S, M, 2)
    costs = StageCosts.uniform(S, 1.0, act_bytes=4.0)
    net = uniform_network(S, lambda: StableTrace(bw_true))
    profiler = NetworkProfiler(net, window=4)
    bus = TelemetryBus()
    bus.subscribe(PassiveLinkFeed(profiler))
    length = simulate_plan(plan, costs, net).pipeline_length
    assert profiler.last_update(0, 1) is None
    bus.publish_iteration(
        index=0, plan=plan, costs=costs, seconds=length, end_time=100.0, source="sim"
    )
    for s in range(S - 1):
        assert profiler.is_fresh(s, s + 1, now=110.0, max_age=20.0)
        assert not profiler.is_fresh(s, s + 1, now=200.0, max_age=20.0)
        assert profiler.link_bandwidth(s, s + 1) == pytest.approx(bw_true, rel=0.05)
    # engine-clock timings must NOT leak into the sim-clock windows
    before = profiler.last_update(0, 1)
    bus.publish_iteration(
        index=1, plan=plan, costs=costs, seconds=0.01, end_time=999.0, source="engine"
    )
    assert profiler.last_update(0, 1) == before


# ---------------------------------------------------------------------------
# Switch equivalence + Fig-10 acceptance (real compiled steps, slow tier)
# ---------------------------------------------------------------------------


def _reference_step(staged, plan, optimizer):
    from repro.pipeline.engine import reference_pipeline_grads

    @jax.jit
    def step(state, tokens, labels):
        loss, grads = reference_pipeline_grads(
            staged, state.params, tokens, labels, plan
        )
        new_p, new_o, _ = optimizer.update(state.params, grads, state.opt_state)
        return TrainState(state.step + 1, new_p, new_o), loss, grads

    return step


@pytest.mark.slow
def test_switch_equivalence_kfkb_zb_interleaved():
    """The satellite acceptance: a run that switches kfkb -> zb_h2 ->
    interleaved_zb mid-stream on fixed data must match an unswitched
    per-segment reference (same segments executed by directly-built
    engines, state handed over manually) to 5e-6 on params AND grads."""
    S, M, b, T = 2, 4, 2, 8
    B = M * b
    cfg = _cfg(num_layers=4)
    opt = _opt()
    plans = [
        make_plan(S, M, 1, micro_batch_size=b),
        make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=1, micro_batch_size=b)),
        make_plan(S, M, spec=ScheduleSpec(kind="interleaved_zb", num_virtual=2, micro_batch_size=b)),
    ]
    batches = [_data(B, T, seed=10 + i) for i in range(6)]

    rt = PlanRuntime(cfg, S, opt, global_batch=B, seq_len=T, backend="reference")
    step_idx = 0
    for plan in plans:
        rt.switch_to(plan.lower())
        for _ in range(2):
            rt.run_iteration(*batches[step_idx])
            step_idx += 1
    rt.cache.shutdown()

    # unswitched per-segment reference: same init, same data, no runtime
    staged1 = StagedModel.build(cfg, S)
    staged2 = StagedModel.build(cfg, 2 * S)
    state = create_train_state(staged1.init_all_stages(jax.random.PRNGKey(0)), opt)
    step_idx = 0
    last_grads = None
    for plan in plans:
        v = plan.num_virtual
        staged = staged2 if v == 2 else staged1
        if v == 2:
            state = restack_train_state(state, S, 1, 2)
        step = _reference_step(staged, plan, opt)
        for _ in range(2):
            tok, lab = batches[step_idx]
            bb = B // M
            state, _, last_grads = step(
                state, tok.reshape(M, bb, T), lab.reshape(M, bb, T)
            )
            step_idx += 1

    for a, c in zip(
        jax.tree_util.tree_leaves(rt.state.params),
        jax.tree_util.tree_leaves(state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=5e-6)
    for a, c in zip(
        jax.tree_util.tree_leaves(rt.last_grads),
        jax.tree_util.tree_leaves(last_grads),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=5e-6)
    # optimizer moments carried bitwise through two layout changes
    for a, c in zip(
        jax.tree_util.tree_leaves(rt.state.opt_state),
        jax.tree_util.tree_leaves(state.opt_state),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=5e-6)


_SPMD_RUNTIME_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core.kinds import ScheduleSpec
from repro.core.schedule import make_plan
from repro.models.common import ModelConfig
from repro.optim import make_optimizer
from repro.pipeline import stage_mesh
from repro.runtime import PlanRuntime

cfg = ModelConfig("rt-spmd", "dense", num_layers=4, d_model=16, num_heads=2,
                  num_kv_heads=2, d_ff=32, vocab_size=64,
                  dtype=jnp.float32, param_dtype=jnp.float32)
S, M, b, T = 2, 4, 2, 8
B = M * b
opt = make_optimizer("adamw", schedule=lambda s: jnp.float32(1e-3))
mesh = stage_mesh(S)
rt = PlanRuntime(cfg, S, opt, global_batch=B, seq_len=T, backend="spmd", mesh=mesh)
plans = [
    make_plan(S, M, 1, micro_batch_size=b),
    make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=1, micro_batch_size=b)),
    make_plan(
        S, M,
        spec=ScheduleSpec(kind="interleaved_zb", num_virtual=2, micro_batch_size=b),
    ),
]
rng = np.random.default_rng(0)
tok = jnp.asarray(rng.integers(0, 64, (B, T)), jnp.int32)
lab = jnp.asarray(rng.integers(0, 64, (B, T)), jnp.int32)
losses = []
for plan in plans:
    ev = rt.switch_to(plan.lower())
    r = rt.run_iteration(tok, lab)
    losses.append(r.loss)
    print(f"plan={plan.name} restacked={ev.restacked} loss={r.loss:.5f}")
# the loss trajectory must be continuous across kind switches (same data,
# small lr): each switch changes only the schedule, never the state
deltas = [abs(a - c) for a, c in zip(losses, losses[1:])]
assert max(deltas) < 0.1, (losses, deltas)
# and the final interleaved state collapses back to a well-formed flat model
flat = rt.state_in_flat_layout()
from repro.pipeline.stage import StagedModel
staged = StagedModel.build(cfg, S)
mb = B // M
loss = sum(
    staged.full_loss(flat.params, tok.reshape(M, mb, T)[m], lab.reshape(M, mb, T)[m])
    for m in range(M)
) / M
assert abs(float(loss) - losses[-1]) < 0.1
rt.cache.shutdown()
print("SPMD_RUNTIME_OK")
"""


@pytest.mark.slow
def test_spmd_runtime_switch_subprocess():
    """PlanRuntime's real shard_map backend: warm kind switches (incl. the
    interleaved re-stack) on an actual stage-axis mesh, in a subprocess so
    the main pytest process keeps seeing one device."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SPMD_RUNTIME_SCRIPT],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SPMD_RUNTIME_OK" in proc.stdout


_SPMD_PLACEMENT_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.models.common import ModelConfig
from repro.optim import make_optimizer
from repro.pipeline import StagedModel, stage_mesh
from repro.runtime import PlanRuntime
from repro.training import create_train_state

cfg = ModelConfig("rt-place", "dense", num_layers=8, d_model=16, num_heads=2,
                  num_kv_heads=2, d_ff=32, vocab_size=64,
                  dtype=jnp.float32, param_dtype=jnp.float32)
S = 4
opt = make_optimizer("adamw", schedule=lambda s: jnp.float32(1e-3))
rt = PlanRuntime(cfg, S, opt, global_batch=8, seq_len=8, backend="spmd", mesh=stage_mesh(S))


def assert_placed(tree, v, what):
    want = jax.tree_util.tree_leaves(rt._state_sharding(v))
    for (path, x), sh in zip(jax.tree_util.tree_leaves_with_path(tree), want):
        name = jax.tree_util.keystr(path)
        assert x.sharding.is_equivalent_to(sh, x.ndim), (what, name, x.sharding, sh)
        if x.ndim and x.shape[0] == S * v:  # stage-stacked: v rows on each device
            assert {d.data.shape[0] for d in x.addressable_shards} == {v}, (what, name)


def assert_bitwise(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


assert_placed(rt.state, 1, "init")
# born sharded, yet the same bits as a single-device init
staged = StagedModel.build(cfg, S)
assert_bitwise(rt.state, create_train_state(staged.init_all_stages(jax.random.PRNGKey(0)), opt))
there = rt._restack_program(1, 2)(rt.state)
assert_placed(there, 2, "re-stacked to v=2")
back = rt._restack_program(2, 1)(there)
assert_placed(back, 1, "re-stacked back to v=1")
assert_bitwise(back, rt.state)
rt.cache.shutdown()
print("SPMD_PLACEMENT_OK")
"""


def test_spmd_state_placement_subprocess():
    """On a 4-device stage mesh the runtime's state is born sharded (no
    device ever holds it whole) and re-stacking lands each leaf in the
    target layout's sharding — all with the bits of the single-device
    path."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SPMD_PLACEMENT_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SPMD_PLACEMENT_OK" in proc.stdout


_CACHE_SCRIPT = """
import os, sys
import jax, jax.numpy as jnp
from repro.runtime import enable_persistent_cache
from repro.runtime.compile_cache import PERSISTENT_CACHE_DIR

before = set(os.listdir(PERSISTENT_CACHE_DIR)) if PERSISTENT_CACHE_DIR.exists() else set()
path = enable_persistent_cache()
assert path == os.environ["JAX_COMPILATION_CACHE_DIR"], path
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3).lower(jnp.ones(7)).compile()
assert os.listdir(path), "no cache entry written"
after = set(os.listdir(PERSISTENT_CACHE_DIR)) if PERSISTENT_CACHE_DIR.exists() else set()
assert after == before, "wrote outside JAX_COMPILATION_CACHE_DIR"
print("CACHE_OK")
"""


def test_persistent_cache_follows_jax_compilation_cache_dir(tmp_path):
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set, compiled programs land
    there and nowhere else; unset, the cache is one fixed directory of the
    checkout."""
    import os
    import subprocess
    import sys

    from repro.runtime import compile_cache

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CACHE_OK" in proc.stdout
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert str(compile_cache.PERSISTENT_CACHE_DIR) == os.path.join(root, ".jax_cache")


@pytest.mark.slow
def test_fig10_regime_run_meets_acceptance_gates():
    """The PR acceptance: a seeded Fig-10 RegimeTrace run through
    PlanRuntime performs >= 2 kind switches, warm-cache switch latency
    < 5% of one iteration, matches the oracle gradients (atol 5e-6), and
    the precompile hit rate on the tuner's candidate stream is >= 0.8."""
    from repro.launch.train_adaptive import (
        build_fig10_scenario,
        grad_parity_max_err,
        summarize,
    )

    sc = build_fig10_scenario()
    summary = sc.coordinator.run(14)
    # the same canonical aggregation the entry point's JSON and the bench
    # trajectory report — the gates here gate exactly those numbers
    s = summarize(sc, summary)

    assert s["kind_switches"] >= 2, s["decision_trail"]
    assert s["warm_switch_seconds"], "no warm switches recorded"
    assert s["warm_switch_latency_frac"] < 0.05
    assert s["precompile_hit_rate"] >= 0.8
    assert s["cache"]["cold_misses"] == 0

    # the switched-and-restacked state still produces oracle gradients
    assert grad_parity_max_err(sc) < 5e-6

    # passive telemetry cut the suspend-probe cost on the same run
    assert s["probe_overhead_saved_frac"] > 0.75
    sc.runtime.cache.shutdown()
