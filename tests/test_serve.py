"""Adaptive decode serving (repro.serve).

Tier-1 covers the deterministic logic: seeded arrival reproducibility,
continuous-batcher invariants (retire-before-admit, bounded occupancy,
FIFO no-starvation), SLO accounting exactness on hand-built traces, the
serving objective math, the fused-prefill/token-stepping equivalence at
model level, the decode-vs-prefill workload asymmetry through
``derive_stage_costs``, the stateless ``PlanRuntime`` serving mode, and —
on the seeded Fig-10 serving scenario — the acceptance observables: the
tuner's serve trail crossing schedule kinds, regime-divergent choices, and
serving trace tracks passing the existing no-overlap gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.devicespec import (
    derive_stage_costs,
    load_device_spec,
    load_workload_profile,
    spec_root,
)
from repro.models import api
from repro.obs import Observability
from repro.obs.trace import quantize_sim_span, spans_by_track, validate_no_overlap
from repro.serve import (
    ArrivalProcess,
    ContinuousBatcher,
    InFlight,
    Request,
    RequestQueue,
    SLOTracker,
    make_slo_objective,
)

# ---------------------------------------------------------------------------
# Arrival process
# ---------------------------------------------------------------------------


def test_arrivals_seeded_reproducible():
    a = ArrivalProcess(5.0, seed=7, burst_factor=3.0)
    b = ArrivalProcess(5.0, seed=7, burst_factor=3.0)
    ra = a.drain(20.0)
    rb = b.drain(20.0)
    assert ra == rb
    assert len(ra) > 0
    # different seed -> different stream
    rc = ArrivalProcess(5.0, seed=8, burst_factor=3.0).drain(20.0)
    assert [r.arrival_time for r in rc] != [r.arrival_time for r in ra]


def test_arrivals_poisson_rate():
    reqs = ArrivalProcess(10.0, seed=0).drain(200.0)
    # ~2000 expected; 5 sigma ~ 220
    assert 1700 <= len(reqs) <= 2300
    times = [r.arrival_time for r in reqs]
    assert times == sorted(times)
    assert all(0.0 < t <= 200.0 for t in times)


def test_arrivals_burst_factor_raises_rate():
    calm = len(ArrivalProcess(5.0, seed=3).drain(100.0))
    bursty = len(
        ArrivalProcess(
            5.0, seed=3, burst_factor=4.0, mean_calm=1.0, mean_burst=1.0
        ).drain(100.0)
    )
    # ~half the time at 4x rate -> ~2.5x the arrivals
    assert bursty > 1.5 * calm


def test_arrivals_drain_monotone_and_disjoint():
    a = ArrivalProcess(8.0, seed=1, burst_factor=2.0)
    first = a.drain(5.0)
    second = a.drain(10.0)
    assert all(r.arrival_time <= 5.0 for r in first)
    assert all(5.0 < r.arrival_time <= 10.0 for r in second)
    assert a.drain(10.0) == []  # already drained
    rids = [r.rid for r in first + second]
    assert rids == sorted(set(rids))


def test_arrivals_next_arrival_after():
    a = ArrivalProcess(2.0, seed=5)
    t = a.next_arrival_after(3.0)
    assert t is not None and t > 3.0
    assert a.drain(t) != []  # skipping to t lands on a real arrival
    assert ArrivalProcess(0.0).next_arrival_after(0.0) is None


def test_arrivals_sampled_ranges():
    reqs = ArrivalProcess(
        20.0, seed=2, prompt_len=(4, 9), new_tokens=(2, 5)
    ).drain(20.0)
    assert reqs
    assert all(4 <= r.prompt_len <= 9 for r in reqs)
    assert all(2 <= r.max_new_tokens <= 5 for r in reqs)


def test_arrivals_validation():
    with pytest.raises(ValueError):
        ArrivalProcess(-1.0)
    with pytest.raises(ValueError):
        ArrivalProcess(1.0, burst_factor=0.5)


# ---------------------------------------------------------------------------
# Queue + continuous batcher invariants
# ---------------------------------------------------------------------------


def _req(rid, t=0.0, budget=2):
    return Request(rid=rid, arrival_time=t, prompt_len=4, max_new_tokens=budget)


def test_batcher_fifo_and_occupancy_bound():
    q = RequestQueue()
    for i in range(10):
        q.push(_req(i))
    b = ContinuousBatcher(4)
    admitted = b.admit(q, now=0.0)
    assert [inf.request.rid for inf in admitted] == [0, 1, 2, 3]  # FIFO
    assert b.occupancy == 4 and len(q) == 6
    assert b.admit(q, now=1.0) == []  # full: admits nothing, raises nothing
    # finish two, retire, re-admit: strictly the next two in line
    for inf in admitted[:2]:
        inf.tokens_emitted = inf.request.max_new_tokens
    done = b.retire_finished(now=2.0)
    assert [inf.request.rid for inf in done] == [0, 1]
    again = b.admit(q, now=2.0)
    assert [inf.request.rid for inf in again] == [4, 5]
    assert b.occupancy == 4
    assert b.total_admitted == 6 and b.total_retired == 2


def test_batcher_admit_before_retire_raises():
    q = RequestQueue()
    q.push(_req(0))
    q.push(_req(1))
    b = ContinuousBatcher(1)
    (inf,) = b.admit(q, now=0.0)
    inf.tokens_emitted = inf.request.max_new_tokens
    with pytest.raises(RuntimeError, match="retire_finished"):
        b.admit(q, now=1.0)
    b.retire_finished(now=1.0)
    assert [i.request.rid for i in b.admit(q, now=1.0)] == [1]


def test_batcher_no_starvation():
    """Any queued request is admitted after at most the requests ahead of it:
    admission order equals enqueue order, regardless of retire pattern."""
    q = RequestQueue()
    rng = np.random.default_rng(0)
    for i in range(30):
        q.push(_req(i, budget=int(rng.integers(1, 4))))
    b = ContinuousBatcher(3)
    order = []
    now = 0.0
    while len(order) < 30:
        b.retire_finished(now)
        order += [inf.request.rid for inf in b.admit(q, now)]
        for inf in b.in_flight:  # one tick: everyone emits one token
            inf.tokens_emitted += 1
        now += 1.0
    assert order == list(range(30))


# ---------------------------------------------------------------------------
# SLO accounting exactness (hand-built trace)
# ---------------------------------------------------------------------------


def test_slo_tracker_exact_ttft_tpot():
    obs = Observability.create()
    slo = SLOTracker(obs.metrics, trace=obs.trace, ttft_slo=0.5, tpot_slo=0.15)
    # request arrives t=1, admitted t=2, first token t=3, tokens at 4, 5, done 5
    inf = InFlight(request=_req(0, t=1.0, budget=3), slot=0, admit_time=2.0)
    slo.on_admit(inf, 2.0)
    slo.on_first_token(inf, 3.0)
    slo.on_token(inf, 4.0)
    slo.on_token(inf, 5.0)
    slo.on_complete(inf, 5.0)
    s = slo.summary()
    assert s["completed"] == 1 and s["tokens"] == 3.0
    assert s["ttft_p50"] == pytest.approx(2.0)  # arrival 1 -> first token 3
    assert s["tpot_p50"] == pytest.approx(1.0)  # (5-3)/(3-1)
    assert s["token_latency_p50"] == pytest.approx(1.0)
    assert s["slo_attainment"] == 0.0  # both targets missed


def test_slo_tracker_attainment_mixed():
    obs = Observability.create()
    slo = SLOTracker(obs.metrics, ttft_slo=1.0, tpot_slo=1.0)
    for rid, (admit, first) in enumerate([(0.0, 0.5), (0.0, 2.0)]):
        inf = InFlight(request=_req(rid, t=0.0, budget=1), slot=0, admit_time=admit)
        slo.on_admit(inf, admit)
        slo.on_first_token(inf, first)
        slo.on_complete(inf, first)
    assert slo.attainment() == 0.5
    # budget-1 request has no TPOT sample: only the TTFT target judges it
    assert slo.summary()["tpot_p50"] == 0.0


def test_slo_tracker_quantiles_match_numpy():
    obs = Observability.create()
    slo = SLOTracker(obs.metrics)
    rng = np.random.default_rng(0)
    gaps = rng.exponential(0.05, size=500)
    inf = InFlight(request=_req(0, budget=10**9), slot=0, admit_time=0.0)
    t = 0.0
    slo.on_first_token(inf, t)
    for g in gaps:
        t += g
        slo.on_token(inf, t)
    s = slo.summary()
    assert s["token_latency_p50"] == pytest.approx(np.quantile(gaps, 0.5), rel=1e-9)
    assert s["token_latency_p99"] == pytest.approx(np.quantile(gaps, 0.99), rel=1e-9)


def test_slo_request_spans_disjoint_per_slot():
    """One slot serves requests back-to-back: the per-slot track passes the
    existing no-overlap gate even when spans touch exactly."""
    obs = Observability.create()
    slo = SLOTracker(obs.metrics, trace=obs.trace, track="host0/requests")
    t = 1000.0  # large base stresses the µs-rounding path
    for rid in range(20):
        inf = InFlight(request=_req(rid, t=t, budget=1), slot=0, admit_time=t)
        slo.on_first_token(inf, t + 0.0333)
        t += 0.0333  # next admit at exactly the previous completion
        slo.on_complete(inf, t)
    payload = obs.trace.to_chrome_trace()
    validate_no_overlap(payload, track_prefix="host0/requests")
    assert len(spans_by_track(payload)["host0/requests/slot0"]) == 20


def test_quantize_sim_span_touching_stays_touching():
    start, dur = 18.079207209, 0.000466667
    s1, d1 = quantize_sim_span(start, dur)
    s2, _ = quantize_sim_span(start + dur, dur)
    assert s1 + d1 <= s2 + 1e-12
    assert s1 == pytest.approx(start, abs=1e-9)
    assert d1 == pytest.approx(dur, abs=1e-9)


# ---------------------------------------------------------------------------
# Serving objective
# ---------------------------------------------------------------------------


def test_slo_objective_pressure_gating():
    from repro.launch.train_adaptive import fig10_parts

    _, _, cands, _ = fig10_parts(4)
    k1 = next(c for c in cands if c.k == 1)
    k2 = next(c for c in cands if c.k == 2)
    pressure = {"v": 0.0}
    obj = make_slo_objective(lambda: pressure["v"], latency_weight=2.0)
    # slack queue: grouped plans pay the emission-delay penalty
    assert obj(k1, 1.0, 0.0) == pytest.approx(1.0)
    assert obj(k2, 1.0, 0.0) == pytest.approx(1.0 + 2.0 * (2 - 1) / k2.num_microbatches)
    # saturated queue: pure makespan, no penalty
    pressure["v"] = 1.0
    assert obj(k2, 1.0, 0.0) == pytest.approx(1.0)
    # over-saturated clamps the same way
    pressure["v"] = 7.0
    assert obj(k2, 1.0, 0.0) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Fused prefill == token-stepping (model level)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "arch", ["qwen2.5-14b", "jamba-v0.1-52b", "gemma3-12b"]
)  # dense, attn/ssm hybrid, windowed attention
def test_prefill_with_cache_matches_token_stepping(arch):
    cfg = get_arch(arch).smoke
    B, P, L = 2, 6, 10
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)

    cache = api.init_cache(cfg, B, L)
    logits, cache = api.prefill_with_cache(params, cfg, cache, {"tokens": prompts})

    ref = api.init_cache(cfg, B, L)
    for i in range(P):
        ref_logits, ref = api.decode_fn(params, cfg, ref, i, {"tokens": prompts[:, i : i + 1]})
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        cache,
        ref,
    )
    # and the next decode step from both caches agrees
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    nl, _ = api.decode_fn(params, cfg, cache, P, {"tokens": tok})
    rl, _ = api.decode_fn(params, cfg, ref, P, {"tokens": tok})
    np.testing.assert_array_equal(np.asarray(nl), np.asarray(rl))


@pytest.mark.parametrize(
    "arch", ["qwen2.5-14b", "jamba-v0.1-52b", "gemma3-12b"]
)  # dense, attn/ssm hybrid with MoE, windowed attention
def test_engine_grouped_decode_matches_per_slot_stepping(arch):
    """``ServeEngine``'s grouped, in-place decode program against each slot
    stepped alone through the scalar-index ``decode_fn`` on its own batch-1
    cache: slots at different positions (one past gemma3's 64-token window)
    and empty ones, a few ticks under M=2 and then M=4."""
    from repro.core.schedule import make_plan
    from repro.serve import ServeEngine

    cfg = get_arch(arch).smoke
    slots, max_len = 8, 80
    engine = ServeEngine(cfg, 2, max_slots=slots, max_len=max_len)
    queue, batcher = RequestQueue(), ContinuousBatcher(slots)
    for rid, n in enumerate((3, 7, 66, 12, 5)):  # slots 5..7 stay empty
        queue.push(Request(rid, 0.0, n, 8))
    admitted = batcher.admit(queue, 0.0)
    engine.prefill(admitted)

    step = jax.jit(lambda p, c, i, t: api.decode_fn(p, cfg, c, i, {"tokens": t}))
    grouped = jax.jit(
        lambda p, c, i, t, M: api.decode_fn(p, cfg, c, i, {"tokens": t}, groups=M),
        static_argnums=4,
    )
    refs = [engine.slot_cache(s) for s in range(slots)]
    for tick, M in enumerate((2, 2, 4, 4)):
        engine.switch_to(make_plan(2, M, 2, micro_batch_size=slots // M).lower())
        assert engine.kv_aliased_bytes == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(engine.kv)
        )
        pos, tok = engine.positions, engine.tokens
        logits, _ = grouped(engine.params, engine.kv, pos, tok, M)
        engine.decode_tick(batcher.in_flight)
        for s in range(slots):
            ref_logits, refs[s] = step(engine.params, refs[s], pos[s], tok[s][None])
            np.testing.assert_allclose(
                np.asarray(logits[s], np.float32), np.asarray(ref_logits[0], np.float32),
                atol=2e-2, rtol=2e-2,
            )
            want = int(jnp.argmax(ref_logits[0, -1]))
            assert int(engine.tokens[s, 0]) == want, (tick, s)
            # the rows written: equal up to a bf16 rounding of matmuls of b
            # rows against matmuls of one (a misplaced or missing write is O(1))
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2**-6, atol=2**-6
                ),
                engine.slot_cache(s), refs[s],
            )
        for inf in admitted:
            assert engine.outputs[inf.request.rid][-1] == int(engine.tokens[inf.slot, 0])
    assert [int(p) for p in engine.positions] == [7, 11, 70, 16, 9, 0, 0, 0]
    engine.runtime.cache.shutdown()


def test_engine_tick_and_release_make_no_implicit_transfers():
    """A decode tick moves its per-slot state with one explicit
    ``device_put`` and one ``device_get``, and ``release`` stays on the
    host: under ``transfer_guard("disallow")`` an eager per-slot op or an
    ``int()`` of a device array raises.  The guarded engine serves the
    tokens an unguarded one serves, and only occupied slots advance."""
    from repro.core.schedule import make_plan
    from repro.serve import ServeEngine

    cfg = get_arch("qwen2.5-14b").smoke
    slots = 6

    def serve(guard: bool):
        engine = ServeEngine(cfg, 2, max_slots=slots, max_len=32)
        engine.switch_to(make_plan(2, 2, 2, micro_batch_size=slots // 2).lower())
        queue, batcher = RequestQueue(), ContinuousBatcher(slots)
        for rid, n in enumerate((5, 9, 4)):  # slots 3..5 stay empty
            queue.push(Request(rid, 0.0, n, 8))
        admitted = batcher.admit(queue, 0.0)
        engine.prefill(admitted)  # unguarded: the prompt draw is eager
        occupied = [inf.slot for inf in admitted]
        with jax.transfer_guard("disallow" if guard else "allow"):
            for _ in range(2):
                before = engine.positions
                engine.decode_tick(batcher.in_flight)
                assert isinstance(engine.positions, np.ndarray)
                want = before + np.isin(np.arange(slots), occupied)
                np.testing.assert_array_equal(engine.positions, want)
            engine.release([occupied[1]])
        assert engine.positions[occupied[1]] == 0 and engine.tokens[occupied[1], 0] == 0
        assert list(engine.positions) == [7, 0, 6, 0, 0, 0]
        engine.runtime.cache.shutdown()
        return engine.outputs

    guarded = serve(True)
    assert all(len(out) == 3 for out in guarded.values())
    assert guarded == serve(False)


def test_prefill_with_cache_rejects_unsupported_families():
    cfg = get_arch("seamless-m4t-medium").smoke
    with pytest.raises(NotImplementedError):
        api.prefill_with_cache({}, cfg, {}, {"tokens": jnp.zeros((1, 4), jnp.int32)})


# ---------------------------------------------------------------------------
# Decode workload asymmetry through derive_stage_costs
# ---------------------------------------------------------------------------


def test_decode_prefill_workload_asymmetry():
    import os

    spec = load_device_spec(os.path.join(spec_root(), "tpu-v5e.json"))
    root = os.path.join(spec_root(), "workloads")
    wl_dec = load_workload_profile(os.path.join(root, "pinned-4stage-decode.json"))
    wl_pre = load_workload_profile(os.path.join(root, "pinned-4stage-prefill.json"))
    dec = derive_stage_costs(wl_dec, spec)
    pre = derive_stage_costs(wl_pre, spec)
    assert len(dec.fwd_time) == 4 == len(pre.fwd_time)
    # decode is memory-bound: arithmetic intensity way below prefill's
    for s in range(4):
        fwd_dec, fwd_pre = wl_dec.counts[s]["fwd"], wl_pre.counts[s]["fwd"]
        ai_dec = fwd_dec.flops / fwd_dec.hbm_bytes
        ai_pre = fwd_pre.flops / fwd_pre.hbm_bytes
        assert ai_dec < 5.0 < ai_pre
        # per-token decode moves ~the same HBM traffic as the 16-token
        # prefill (weights dominate), so fwd times are within ~2x while
        # prefill carries 16x the FLOPs
        assert pre.fwd_time[s] < 2.0 * dec.fwd_time[s]
        assert fwd_pre.flops > 10.0 * fwd_dec.flops
    # activation handoffs: full-sequence prefill ships seq_len x decode's
    assert pre.fwd_bytes[0] == 16.0 * dec.fwd_bytes[0]


# ---------------------------------------------------------------------------
# Stateless PlanRuntime serving mode
# ---------------------------------------------------------------------------


def _tiny_cfg():
    from repro.models.common import ModelConfig

    return ModelConfig(
        name="serve-tiny", family="dense", num_layers=2, d_model=8,
        num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=32,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )


def test_plan_runtime_stateless_requires_factory():
    from repro.runtime import PlanRuntime

    with pytest.raises(ValueError, match="program_factory"):
        PlanRuntime(_tiny_cfg(), 2, optimizer=None, global_batch=4, seq_len=8)


def test_plan_runtime_stateless_run_program():
    from repro.core import make_plan
    from repro.runtime import PlanRuntime

    def factory(table):
        scale = float(table.plan.num_microbatches)

        def fn(x):
            return x * scale

        return jax.jit(fn), (jax.ShapeDtypeStruct((4,), jnp.float32),)

    rt = PlanRuntime(
        _tiny_cfg(), 2, optimizer=None, global_batch=4, seq_len=8,
        program_factory=factory,
    )
    assert rt.state is None
    with pytest.raises(RuntimeError, match="switch_to"):
        rt.run_program(jnp.ones((4,), jnp.float32))
    with pytest.raises(RuntimeError, match="run_program"):
        rt.run_iteration(jnp.zeros((4, 8), jnp.int32), jnp.zeros((4, 8), jnp.int32))
    p1 = make_plan(2, 2, 1).lower()
    p2 = make_plan(2, 4, 1).lower()
    rt.switch_to(p1)
    out, seconds = rt.run_program(jnp.ones((4,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(4))
    assert seconds >= 0.0
    # warm switch to a different plan re-dispatches the cached program and
    # never touches (nonexistent) train state
    rt.switch_to(p2)
    out, _ = rt.run_program(jnp.ones((4,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 4.0 * np.ones(4))
    rt.cache.shutdown()


# ---------------------------------------------------------------------------
# The serving scenario: acceptance observables (seeded, simulated clock)
# ---------------------------------------------------------------------------


def _small_run(adaptive: bool, regime: str = "fig10", requests: int = 24, seed: int = 0):
    from repro.launch.serve_adaptive import build_serve_scenario

    sc = build_serve_scenario(regime=regime, seed=seed, adaptive=adaptive)
    summary = sc.runtime.run(requests)
    return sc, summary


def test_serve_runtime_completes_and_accounts():
    sc, s = _small_run(adaptive=True)
    assert s["requests_completed"] == 24
    assert s["requests_admitted"] >= s["requests_completed"]
    done = sc.runtime.completed
    assert all(inf.tokens_emitted == inf.request.max_new_tokens for inf in done)
    assert s["ticks"] == s["decode_ticks"] + s["prefill_ticks"]
    assert s["prefill_ticks"] >= 1 and s["decode_ticks"] >= 1
    assert s["sim_time"] > 0 and s["tokens_per_second"] > 0
    # deterministic under the simulated clock
    _, s2 = _small_run(adaptive=True)
    assert s2 == s


def test_serve_tuner_crosses_kinds_and_uses_serve_telemetry():
    sc, s = _small_run(adaptive=True, requests=40)
    assert len(s["kinds_chosen"]) >= 2, s["kinds_chosen"]
    assert len(s["decision_trail"]) >= 2
    # the profiler windows were fed by this loop's own serve-sourced ticks
    assert len(sc.bus.history) > 0
    assert all(t.source == "serve" for t in sc.bus.history)
    assert s["tuning_overhead_charged"] < 0.05 * s["sim_time"]


def test_serve_static_baseline_never_switches():
    sc, s = _small_run(adaptive=False)
    assert s["decision_trail"] == []
    assert s["kinds_chosen"] == []
    assert all(t.kind == "kfkb" and t.k == 1 for t in sc.runtime.ticks)


def test_serve_chosen_spec_diverges_across_regimes():
    _, bursty = _small_run(adaptive=True, regime="bursty", requests=24)
    _, excl = _small_run(adaptive=True, regime="exclusive", requests=24)
    b_final = bursty["decision_trail"][-1]
    e_final = excl["decision_trail"][-1]
    assert b_final["chosen"] != e_final["chosen"]
    # preempted network favors the deep-warmup zero-bubble member;
    # an exclusive network frees the tuner to pick the interleaved member
    assert b_final["kind"] == "zb_h2"
    assert e_final["kind"] == "interleaved_zb"


def test_serve_trace_tracks_pass_no_overlap_gate():
    sc, _ = _small_run(adaptive=True)
    payload = sc.obs.trace.to_chrome_trace()
    validate_no_overlap(payload, track_prefix="host0")
    tracks = spans_by_track(payload)
    assert any(t.startswith("host0/requests/slot") for t in tracks)
    assert "host0/ticks" in tracks
