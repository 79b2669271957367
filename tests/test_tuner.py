"""Cost model + online tuner (§4.3, §5.4) behaviour."""

import pytest

from repro.core import (
    AutoTuner,
    Coordinator,
    CostModel,
    MemoryModel,
    Network,
    NetworkProfiler,
    RegimeTrace,
    ScheduleSpec,
    SearchSpace,
    StableTrace,
    StageCosts,
    enumerate_candidates,
    simulate_plan,
    uniform_network,
)


def _setup(S=4, B=32, bw=2.0):
    mm = MemoryModel.uniform(
        num_stages=S, seq_len=64, param_bytes=1e6, optimizer_bytes=2e6,
        grad_bytes=1e6, stage_input_bytes_per_token=512.0,
        layer_act_bytes_per_token=64.0, num_layers_per_stage=2,
    )
    cands = enumerate_candidates(
        S, B, mm, 1e8,
        space=SearchSpace(max_k=4),
    )
    costs_by_b = {}

    def stage_costs_for(cand):
        if cand.micro_batch_size not in costs_by_b:
            costs_by_b[cand.micro_batch_size] = StageCosts.uniform(
                S, 0.1 * cand.micro_batch_size, act_bytes=float(cand.micro_batch_size)
            )
        return costs_by_b[cand.micro_batch_size]

    return cands, stage_costs_for


def test_cost_model_equals_simulator_under_frozen_bw():
    cands, costs_for = _setup()
    cand = cands[0]
    bw = {k: 2.0 for s in range(3) for k in [(s, s + 1), (s + 1, s)]}
    cm = CostModel()
    est = cm.estimate(cand.plan, costs_for(cand), bw)
    net = uniform_network(4, lambda: StableTrace(2.0))
    sim = simulate_plan(cand.plan, costs_for(cand), net).pipeline_length
    assert est == pytest.approx(sim, rel=1e-9)


def test_tuner_prefers_larger_k_when_network_slow():
    cands, costs_for = _setup()
    slow = uniform_network(4, lambda: StableTrace(1.0))
    tuner = AutoTuner(cands, costs_for, NetworkProfiler(slow))
    rec = tuner.tune(now=0.0)
    assert rec.chosen_spec.k > 1


def test_tuner_tracks_regime_change():
    """Fig 10: when preemption eases, the tuner may step k back down; when
    it returns, k goes back up.  We assert the chosen plan is always the
    argmin of its own estimates, and that estimates differ across regimes."""
    cands, costs_for = _setup()
    regime = RegimeTrace(
        breakpoints=[100.0, 200.0],
        traces=[StableTrace(0.5), StableTrace(1e9), StableTrace(0.5)],
    )
    net = Network(default=regime)
    tuner = AutoTuner(cands, costs_for, NetworkProfiler(net, window=1))
    recs = [tuner.tune(t) for t in (0.0, 150.0, 250.0)]
    for rec in recs:
        assert rec.estimates[rec.chosen] == min(rec.estimates.values())
    # preempted regimes must prefer grouping (k > 1); the re-preempted
    # regime's estimates must be strictly worse than the exclusive one's
    # (the paper notes improvement is NOT monotone in k, so we do not
    # assert k ordering between regimes — only that tuning tracks them)
    assert recs[0].chosen_spec.k > 1 and recs[2].chosen_spec.k > 1
    assert min(recs[2].estimates.values()) > min(recs[1].estimates.values())


def test_coordinator_switches_and_improves():
    cands, costs_for = _setup()
    net = uniform_network(4, lambda: StableTrace(1.0))
    tuner = AutoTuner(cands, costs_for, NetworkProfiler(net))
    coord = Coordinator(tuner, net, global_batch=32, tuning_interval=1e9)
    summary = coord.run(5)
    assert len(summary.iterations) == 5
    assert summary.tuning and summary.tuning[0].chosen_spec.k > 1
    # compare against never tuning (fixed 1F1B)
    fixed = simulate_plan(cands[0].plan, costs_for(cands[0]), net).pipeline_length
    assert summary.iterations[0].length <= fixed


def test_profiler_moving_average_window():
    net = uniform_network(2, lambda: StableTrace(10.0))
    prof = NetworkProfiler(net, window=4)
    for _ in range(8):
        prof.measure(0, 1, 100.0, now=0.0, probes=1)
    assert prof.effective_time(0, 1, 100.0) == pytest.approx(10.0)
    assert prof.effective_bandwidth(0, 1, 100.0) == pytest.approx(10.0)


def test_tuner_selects_schedule_kind_not_just_k():
    """Acceptance: with a kind-diverse candidate set the tuner's argmin can
    switch the schedule *kind*.  On a fast dedicated network the
    zero-bubble / interleaved plans win (shorter fill/drain); under heavy
    preemption the chosen estimate still tracks the argmin and the record
    carries the kind."""
    S, B = 4, 32
    mm = MemoryModel.uniform(
        num_stages=S, seq_len=64, param_bytes=1e6, optimizer_bytes=2e6,
        grad_bytes=1e6, stage_input_bytes_per_token=512.0,
        layer_act_bytes_per_token=64.0, num_layers_per_stage=2,
    )
    cands = enumerate_candidates(
        S, B, mm, 1e8,
        space=SearchSpace(kinds=("kfkb", "zb_h1", "interleaved"), max_k=4),
    )
    kinds = {c.kind for c in cands}
    assert kinds == {"kfkb", "zb_h1", "interleaved"}
    assert len({c.name for c in cands}) == len(cands)  # names stay unique

    costs_by_b = {}

    def costs_for(cand):
        if cand.micro_batch_size not in costs_by_b:
            costs_by_b[cand.micro_batch_size] = StageCosts.uniform(
                S, 0.1 * cand.micro_batch_size, act_bytes=float(cand.micro_batch_size)
            )
        return costs_by_b[cand.micro_batch_size]

    fast = uniform_network(S, lambda: StableTrace(1e12))
    rec = AutoTuner(cands, costs_for, NetworkProfiler(fast)).tune(0.0)
    assert rec.chosen_spec.kind in ("zb_h1", "interleaved")  # beats every kFkB plan
    assert rec.estimates[rec.chosen] == min(rec.estimates.values())

    slow = uniform_network(S, lambda: StableTrace(0.5))
    rec2 = AutoTuner(cands, costs_for, NetworkProfiler(slow)).tune(0.0)
    assert rec2.estimates[rec2.chosen] == min(rec2.estimates.values())
    assert rec2.chosen_spec.kind in ("kfkb", "zb_h1", "interleaved")


def _mm(S=4):
    return MemoryModel.uniform(
        num_stages=S, seq_len=64, param_bytes=1e6, optimizer_bytes=2e6,
        grad_bytes=1e6, stage_input_bytes_per_token=512.0,
        layer_act_bytes_per_token=64.0, num_layers_per_stage=2,
    )


def _uniform_costs_for(S):
    costs_by_b = {}

    def costs_for(cand):
        if cand.micro_batch_size not in costs_by_b:
            costs_by_b[cand.micro_batch_size] = StageCosts.uniform(
                S, 0.1 * cand.micro_batch_size, act_bytes=float(cand.micro_batch_size)
            )
        return costs_by_b[cand.micro_batch_size]

    return costs_for


def _preempted_network(S):
    """A genuinely preempted fabric (the ISSUE's acceptance scenario): links
    periodically collapse to 1/100th bandwidth, as in Fig 2's preempted
    rows — not merely a uniformly slow StableTrace."""
    from repro.core import PeriodicPreemptionTrace

    return uniform_network(
        S, lambda: PeriodicPreemptionTrace(high=50.0, low=0.5, period=20.0, duty=0.3)
    )


def test_tuner_selects_zb_h2_when_memory_admits_extra_warmup():
    """Acceptance: with a generous memory limit the H2 candidate exists
    (largest admissible w[s] per stage, greedy on the limit curve) and
    under a preempted network the tuner picks it over H1 — its extra warmup
    forwards absorb the stalls.  The record carries the chosen warmup
    vector."""
    S, B = 4, 32
    cands = enumerate_candidates(
        S, B, _mm(S), 1e8,
        space=SearchSpace(kinds=("zb_h1", "zb_h2"), max_k=1, min_microbatches=16),
    )
    assert {c.kind for c in cands} == {"zb_h1", "zb_h2"}
    h2 = next(c for c in cands if c.kind == "zb_h2")
    assert max(h2.extra_warmup) >= 1 and h2.est_peak_bytes <= 1e8

    rec = AutoTuner(cands, _uniform_costs_for(S), NetworkProfiler(_preempted_network(S))).tune(0.0)
    assert rec.chosen_spec.kind == "zb_h2"
    assert rec.chosen_spec.extra_warmup == h2.extra_warmup
    assert max(rec.chosen_spec.extra_warmup) >= 1
    assert rec.estimates[rec.chosen] == min(rec.estimates.values())


def test_tuner_refuses_zb_h2_when_memory_forbids_it():
    """Acceptance: a limit CURVE that admits ZB-H1 but not even w[s]=1 at
    any stage (the H2 surcharge is the extra live slots) must yield NO H2
    candidate, so the tuner falls back to H1 even under the preemption that
    favours H2.  A scalar limit can never force this (some later stage
    always has slot headroom under a uniform ceiling) — per-stage refusal
    is exactly what the limit curve exists to express."""
    from repro.core import make_plan

    S, B = 4, 32
    mm = _mm(S)
    # at the smallest feasible b (=1): each stage's limit sits between its
    # own H1 peak and the cost of one extra zb slot — H1 fits everywhere,
    # w[s]=1 fits nowhere
    h1_peaks = mm.peak_bytes_per_stage(make_plan(S, B, spec=ScheduleSpec(kind="zb_h1")))
    tight = [p + 0.5 * mm.slot_bytes(s, 1, True) for s, p in enumerate(h1_peaks)]
    cands = enumerate_candidates(
        S, B, mm, tight,
        space=SearchSpace(kinds=("zb_h1", "zb_h2"), max_k=1, min_microbatches=B),
    )
    assert [c.kind for c in cands] == ["zb_h1"]  # H2 refused entirely

    rec = AutoTuner(cands, _uniform_costs_for(S), NetworkProfiler(_preempted_network(S))).tune(0.0)
    assert rec.chosen_spec.kind == "zb_h1"
    assert max(rec.chosen_spec.extra_warmup) == 0


def test_vector_warmup_beats_every_scalar_on_memory_skewed_pipeline():
    """THE acceptance gate of the heterogeneity PR: on a memory-skewed
    4-stage pipeline under ``PeriodicPreemptionTrace``, the per-stage
    greedy recovers a vector w[s] candidate whose simulated pipeline length
    is strictly shorter than EVERY scalar-w (uniform H2) candidate that is
    admissible under the same per-stage limit curve — and the tuner picks
    it."""
    from repro.core import make_plan

    S, B = 4, 32
    M, b = 32, 1
    mm = _mm(S)
    # the skew: stage s's limit admits exactly target[s] extra slots — early
    # stages are memory-rich, the last stage nearly full
    target = (3, 3, 2, 1)
    plan_v = make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=target, micro_batch_size=b))
    limits = [p + 1.0 for p in mm.peak_bytes_per_stage(plan_v)]

    cands = enumerate_candidates(
        S, B, mm, limits,
        space=SearchSpace(kinds=("zb_h1", "zb_h2"), max_k=1, min_microbatches=B, max_extra_warmup=8),
    )
    h2 = next(c for c in cands if c.kind == "zb_h2")
    assert h2.extra_warmup == target  # greedy recovers the full skew

    # Fig-2-scale costs: fwd 1s, bwd 2s, transfer = F/50 when free — the
    # preemption windows (period 20s, duty 0.3) bite mid-pipeline
    costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)

    def costs_for(_cand):
        return costs

    net = _preempted_network(S)
    len_vector = simulate_plan(h2.plan, costs, net).pipeline_length

    # every scalar w admissible under the SAME curve (w=0 is H1)
    scalar_lengths = {}
    for w in range(0, max(target) + 2):
        kind = "zb_h1" if w == 0 else "zb_h2"
        plan_s = make_plan(S, M, spec=ScheduleSpec(kind=kind, extra_warmup=w, micro_batch_size=b))
        if mm.fits(plan_s, limits):
            scalar_lengths[w] = simulate_plan(plan_s, costs, net).pipeline_length
    assert set(scalar_lengths) == {0, 1}  # the tight stage pins scalars at w<=1
    for w, length in scalar_lengths.items():
        assert len_vector < length, (w, len_vector, length)

    # and the tuner, handed vector + scalar candidates, picks the vector
    from repro.core import Candidate

    scalar_cands = [
        Candidate(1, b, M, make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=1, micro_batch_size=b)), 0.0)
    ]
    tuner = AutoTuner(
        cands + scalar_cands, costs_for, NetworkProfiler(_preempted_network(S))
    )
    rec = tuner.tune(0.0)
    assert rec.chosen == h2.name
    assert rec.chosen_spec.extra_warmup == target


def test_tuner_lowers_each_candidate_at_most_once():
    """Regression for the ROADMAP caching item: candidates are static, so
    across many tuning intervals plus engine-style dispatches the tabular
    lowering runs at most once per candidate (cached on the plan)."""
    import repro.core.schedule as schedule_mod

    S = 4
    cands, costs_for = _setup(S)
    net = uniform_network(S, lambda: StableTrace(1.0))
    tuner = AutoTuner(cands, costs_for, NetworkProfiler(net))

    calls = []
    real = schedule_mod.lower_to_table
    schedule_mod.lower_to_table = lambda plan: (calls.append(plan.name), real(plan))[1]
    try:
        for t in (0.0, 10.0, 20.0):
            tuner.tune(t)
            # engine dispatch path: the chosen plan's table is re-requested
            assert tuner.current_table is tuner.current.plan.lower()
        for cand in cands:  # a full-family dispatch sweep
            cand.table
            cand.plan.lower()
    finally:
        schedule_mod.lower_to_table = real
    assert len(calls) == len(set(calls)), f"re-lowered candidates: {sorted(calls)}"
    assert len(calls) <= len(cands)


def test_tuner_selects_saved_residual_on_admitting_stages():
    """The saved-residual acceptance: a limit curve tight on stage 0 and
    generous elsewhere yields the DR baseline plus the MIXED per-stage
    vector (saved_residual exactly where memory admits it); on a W-heavy
    pipeline under preemption the tuner picks the mixed candidate — its
    no-remat W bodies drain the bubble-filling weight passes faster — and
    the record carries the per-stage policy trail."""
    from repro.core import make_plan

    S, B = 4, 32
    mm = _mm(S)
    h1 = make_plan(S, B, spec=ScheduleSpec(kind="zb_h1"))
    base = mm.peak_bytes_per_stage(h1)
    limits = [p + (1.0 if s == 0 else 1e9) for s, p in enumerate(base)]
    cands = enumerate_candidates(
        S, B, mm, limits,
        space=SearchSpace(
            kinds=("zb_h1",), max_k=1,
            zb_policies=("double_remat", "saved_residual"),
        ),
    )
    by_policy = {tuple(c.plan.zb_policy): c for c in cands}
    mixed = [p for p in by_policy if set(p) == {"double_remat", "saved_residual"}]
    assert mixed, f"no mixed vector enumerated: {set(by_policy)}"

    # W-heavy profile: double-remat W = 3 (remat forward + pullback),
    # saved-residual W = 1.2 (pure pullback).  Tiny wire bytes keep the
    # estimate compute-bound so the W drain sets the pipeline length.
    costs = StageCosts(
        fwd_time=[1.0] * S, bwd_time=[4.0] * S,
        fwd_bytes=[0.01] * S, bwd_bytes=[0.01] * S,
        bwd_input_time=[1.0] * S, bwd_weight_time=[3.0] * S,
        bwd_weight_saved_time=[1.2] * S,
    )
    tuner = AutoTuner(cands, lambda _c: costs, NetworkProfiler(_preempted_network(S)))
    rec = tuner.tune(0.0)
    assert rec.estimates[rec.chosen] == min(rec.estimates.values())
    assert "+SR" in rec.chosen
    assert rec.chosen_spec.zb_policy in mixed
    assert rec.chosen_spec.zb_policy[0] == "double_remat"  # the tight stage
    assert rec.chosen_spec.zb_policy[1:] == ("saved_residual",) * (S - 1)
    # and the SR pick genuinely beats the DR baseline's estimate
    dr_name = by_policy[("double_remat",) * S].name
    assert rec.estimates[rec.chosen] < rec.estimates[dr_name]
