"""Benchmark-trajectory gate: pinned, seeded metrics committed per PR.

The repo had no performance history: nothing in CI would notice a PR that
halved simulator throughput or regressed schedule quality.  This suite runs
a PINNED, fully seeded subset of the paper benchmarks —

* **Fig-2 pipeline-length ratios** — 1F1B vs kFkB gains in the preempted
  network (deterministic discrete-event simulation),
* **tuner-switch counts** on a seeded Fig-10-style regime trace (the
  adaptive loop's decision trajectory, deterministic given the trace
  seeds),
* **vector-w gain** — the heterogeneous-warmup golden scenario's
  best-scalar / vector length ratio (this PR's tentpole, now a tracked
  number),
* **simulator events/sec** — wall-clock throughput of the discrete-event
  core on a fixed workload (reported for trend-watching but NOT gated
  since PR 8 — the cost-dependent simulation behaviour it used to proxy is
  now gated deterministically by the device-spec metrics below),
* **device-spec matrix** (PR 8) — the offline hardware-matrix slice
  (``benchmarks/hardware_matrix.py``) on three parts: the pinned
  workload's spec-derived seconds drive candidate enumeration + tuner +
  makespan simulation for ``h100-sxm`` and the two synthetic stress specs,
  gating that the extreme-skew regime deterministically flips the chosen
  ``ScheduleSpec`` away from the H100's pick, plus the H100 makespan and
  the slow-interconnect/H100 makespan ratio — all spec-derived seconds,
  zero wall-clock,
* **live plan-switch runtime** — the seeded Fig-10 regime run through
  ``PlanRuntime`` (real compiled steps, reference backend): kind-switch
  count, precompile hit rate on the tuner's candidate stream, warm-cache
  switch latency as a fraction of one iteration (wall-clock, median over
  trace spans since PR 9), the probe overhead passive telemetry saves vs
  suspend-and-probe, and (PR 9) the ``model_drift_ratio``
  predicted-vs-observed gauge plus the flight-recorder decision count,
* **coordinator fabric** — a two-host ``LocalTransport`` fleet driven
  through a scripted refusal (fleet-wide abort) and a committed warm
  switch: barrier verdict counts, the committed epoch's ready-vote count
  (deterministic, gated — replaces the old wall-clock commit-latency gate;
  the latency itself is still reported), and the worst per-host
  precompile hit rate,
* **saved-residual zero-bubble** — the no-remat ``BWD_WEIGHT`` body:
  simulated makespan gain of ``zb_policy="saved_residual"`` over
  double-remat on a W-heavy pipeline under preemption, the tuner's
  per-stage policy trail on a stage-0-tight limit curve, and (runtime
  suite) the compiled-HLO FLOP ratio of the two W bodies on real stage
  kernels — all deterministic,
* **adaptive decode serving** (PR 10) — the seeded Fig-10 serving
  scenario (``repro.launch.serve_adaptive``) head-to-head against the
  static 1F1B decode baseline on identical seeds: p99 token-latency
  ratio, the serve tuner's kind diversity, SLO attainment, and the
  bursty-vs-exclusive regime-divergent ``ScheduleSpec`` choice — all on
  the simulated clock, deterministic,

— and writes them as schema-versioned ``BENCH_<tag>.json`` at the repo
root.  The CI ``bench`` job (main only) runs ``--check``: against the most
recent previously committed ``BENCH_*.json`` (when one exists), any gated
metric that regresses beyond its tolerance fails the job.  Each PR that
touches performance commits its own ``BENCH_<tag>.json``, growing the
trajectory.

Usage:
  python benchmarks/trajectory.py                 # print metrics
  python benchmarks/trajectory.py --out BENCH_PR3.json [--check]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import re
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.core import (  # noqa: E402
    AutoTuner,
    BurstyTrace,
    MemoryModel,
    Network,
    NetworkProfiler,
    RegimeTrace,
    ScheduleSpec,
    SearchSpace,
    StableTrace,
    StageCosts,
    enumerate_candidates,
    make_plan,
    simulate_plan,
    uniform_network,
)
from repro.core.network import PeriodicPreemptionTrace  # noqa: E402

SCHEMA_VERSION = 1
REL_TOL = 0.10  # >10% regression on a gated deterministic metric fails the job

#: metric -> (direction, rel_tol); "higher" means bigger is better and the
#: gate requires ``new >= old * (1 - tol)`` (resp. <= for "lower").  Every
#: gate is deterministic at the tight default band except
#: ``runtime_warm_switch_frac`` — the ONE remaining wall-clock gate (a
#: real compiled-step latency fraction with no spec-derived equivalent:
#: it measures host re-stacking work, not schedule cost), which keeps the
#: wide band + fingerprint guard.  ``sim_events_per_sec`` and
#: ``fabric_barrier_latency_commit`` were demoted in PR 8 from wall-clock
#: gates to reported-only metrics; their cost-dependent content is gated
#: deterministically by the spec_* and fabric_commit_ready_votes gates.
GATES = {
    "fig2_gain_k2": ("higher", REL_TOL),
    "fig2_gain_k4": ("higher", REL_TOL),
    "vector_w_gain": ("higher", REL_TOL),
    "tuner_preempted_hours_beat_1f1b": ("higher", REL_TOL),
    # ZB-V (PR 5): the registry-only member's controllable-memory trade —
    # makespan parity with 1F1B under preemption at ~half the plain
    # interleaved peak-live count (both deterministic simulation)
    "zbv_preempted_gain_vs_1f1b": ("higher", REL_TOL),
    "zbv_peak_live_ratio_vs_interleaved": ("higher", REL_TOL),
    # device-spec matrix (PR 8): offline spec-derived seconds, deterministic
    "spec_divergent_choice": ("higher", 0.0),
    "spec_h100_makespan_s": ("lower", REL_TOL),
    "spec_slow_link_makespan_ratio": ("higher", REL_TOL),
    # live plan-switch runtime (PR 4): the adaptive loop on the real engine
    "runtime_kind_switches": ("higher", 0.0),
    "runtime_precompile_hit_rate": ("higher", REL_TOL),
    "runtime_probe_overhead_saved_frac": ("higher", REL_TOL),
    "runtime_warm_switch_frac": ("lower", 0.5),
    # observability (PR 9): the cost model must keep predicting iteration
    # time — model_drift_ratio joins simulated iteration durations against
    # the tuner's estimates (rolling median, source="sim" only, so it is
    # deterministic: both sides are spec/cost arithmetic, no wall clock) —
    # and the tuner decision trail must keep landing in the flight ring
    "model_drift_ratio": ("lower", REL_TOL),
    "tuner_decision_logged": ("higher", 0.0),
    # tuner trajectory (PR 6): the decision trail must keep crossing kinds
    "tuner_kind_diversity": ("higher", 0.0),
    # coordinator fabric (PR 6): the scripted two-host trail must keep its
    # one refused epoch (fleet-wide abort) and one committed warm switch,
    # and precompilation must keep the boundary switch on the warm path
    "fabric_committed_switches": ("higher", 0.0),
    "fabric_aborted_switches": ("higher", 0.0),
    "fabric_precompile_hit_rate_min": ("higher", REL_TOL),
    "fabric_commit_ready_votes": ("higher", 0.0),
    # saved-residual zero-bubble (PR 7): the no-remat W body must keep
    # beating double-remat on the W-heavy preemption cell, the tuner must
    # keep choosing saved_residual exactly on the admitting stages, and the
    # real compiled W kernels must keep the FLOP gap (the eliminated
    # rematerialized forward) on every stage
    "saved_residual_gain_vs_double_remat": ("higher", REL_TOL),
    "sr_tuner_mixed_selected": ("higher", 0.0),
    "sr_w_flops_ratio_min": ("higher", REL_TOL),
    # adaptive decode serving (PR 10): adaptive must keep beating the
    # static 1F1B decode pipeline on p99 token latency under the seeded
    # Fig-10 preemption regimes, the serve tuner's trail must keep
    # crossing schedule kinds, SLO attainment must not regress, and the
    # preempted-vs-exclusive regimes must keep choosing different specs —
    # all simulated-clock deterministic
    "serve_p99_ratio_vs_static_1f1b": ("higher", REL_TOL),
    "serve_tuner_kind_diversity": ("higher", 0.0),
    "serve_slo_attainment": ("higher", REL_TOL),
    "serve_regime_divergent_choice": ("higher", 0.0),
}

#: wall-clock metrics only gate against a baseline recorded on a comparable
#: machine — a BENCH committed from a dev laptop must not fail the CI
#: runner (or vice versa) on hardware difference alone; on a fingerprint
#: mismatch they are reported but not gated.  Since PR 8 this guard covers
#: exactly one gate (see the GATES note); ``sim_events_per_sec`` and
#: ``fabric_barrier_latency_commit`` remain in the report but not in GATES.
#: PR 9 de-flaked the surviving gate's *definition*: the fraction is now
#: ``median(switch span) / median(iteration span)`` over the runtime's
#: trace spans (``train_adaptive.warm_switch_frac_from_trace``) instead of
#: a single max-switch / mean-iteration quotient — one slow outlier
#: iteration or GC pause no longer swings the ratio.  The *spans* are
#: still real host re-stacking time, so it stays fingerprint-guarded.
WALL_CLOCK_METRICS = {
    "runtime_warm_switch_frac",
}


def machine_fingerprint() -> dict:
    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
    }


def fig2_ratios() -> dict:
    """Fig-2 pinned cell: S=4, M=8, bwd=2·fwd, transfer = F/2."""
    S, M = 4, 8
    costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)
    net = uniform_network(S, lambda: StableTrace(2.0))
    lengths = {
        k: simulate_plan(make_plan(S, M, k), costs, net).pipeline_length
        for k in (1, 2, 4)
    }
    return {
        "fig2_len_1f1b": lengths[1],
        "fig2_gain_k2": lengths[1] / lengths[2],
        "fig2_gain_k4": lengths[1] / lengths[4],
    }


def vector_w_gain() -> dict:
    """The heterogeneity golden scenario: memory-skewed 4-stage pipeline
    under periodic preemption; gain = best-admissible-scalar / vector."""
    S, M = 4, 32
    costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)
    net = uniform_network(
        S, lambda: PeriodicPreemptionTrace(high=50.0, low=0.5, period=20.0, duty=0.3)
    )
    vec = make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=(3, 3, 2, 1)))
    scal = make_plan(S, M, spec=ScheduleSpec(kind="zb_h2", extra_warmup=1))
    len_v = simulate_plan(vec, costs, net).pipeline_length
    len_s = simulate_plan(scal, costs, net).pipeline_length
    return {
        "vector_w_len": len_v,
        "scalar_w_len": len_s,
        "vector_w_gain": len_s / len_v,
    }


def zbv_ratios() -> dict:
    """ZB-V (the registry-only family member) on the pinned preemption
    cell: simulated makespan vs 1F1B (>= 1.0 means the V is no worse
    despite its capped memory) and worst-device peak live vs the
    equal-(S, M, k) plain interleaved plan (> 1.0 means cheaper)."""
    from repro.core.schedule import peak_live_activations

    S, M = 4, 16
    costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)

    def trace():
        return PeriodicPreemptionTrace(high=50.0, low=0.5, period=20.0, duty=0.3)

    len_1f1b = simulate_plan(
        make_plan(S, M, 1), costs, uniform_network(S, trace)
    ).pipeline_length
    zbv = make_plan(S, M, spec=ScheduleSpec(kind="zbv"))
    len_zbv = simulate_plan(zbv, costs, uniform_network(S, trace)).pipeline_length
    peak_zbv = max(peak_live_activations(zbv))
    peak_il = max(
        peak_live_activations(make_plan(S, M, spec=ScheduleSpec(kind="interleaved", num_virtual=2)))
    )
    return {
        "zbv_preempted_len": len_zbv,
        "zbv_preempted_gain_vs_1f1b": len_1f1b / len_zbv,
        "zbv_peak_live": peak_zbv,
        "zbv_peak_live_ratio_vs_interleaved": peak_il / peak_zbv,
    }


def saved_residual_metrics() -> dict:
    """Saved-residual zero-bubble on the pinned W-heavy preemption cell.

    * **simulator gain** — identical zb_h1 schedule shape, W-heavy costs
      (double-remat W = remat forward + pullback at 2.0, saved-residual W
      = pure pullback at 1.0); gain = DR length / SR length.  The drain of
      ``M`` W bodies per stage sets the tail, so eliminating the remat
      shortens the makespan deterministically.
    * **tuner policy trail** — the acceptance shape: a limit curve tight
      on stage 0 and generous elsewhere; the enumeration emits the DR
      baseline plus the mixed vector and the tuner must select
      saved_residual exactly on the admitting stages (``sr_tuner_mixed_
      selected`` gates the deterministic pick).
    """
    S, M = 4, 16
    costs = StageCosts(
        fwd_time=[1.0] * S, bwd_time=[3.0] * S,
        fwd_bytes=[1.0] * S, bwd_bytes=[1.0] * S,
        bwd_input_time=[1.0] * S, bwd_weight_time=[2.0] * S,
        bwd_weight_saved_time=[1.0] * S,
    )

    def trace():
        return PeriodicPreemptionTrace(high=50.0, low=0.5, period=20.0, duty=0.3)

    dr = make_plan(S, M, spec=ScheduleSpec(kind="zb_h1"))
    sr = make_plan(S, M, spec=ScheduleSpec(kind="zb_h1", zb_policy="saved_residual"))
    len_dr = simulate_plan(dr, costs, uniform_network(S, trace)).pipeline_length
    len_sr = simulate_plan(sr, costs, uniform_network(S, trace)).pipeline_length

    # the tuner's per-stage policy trail (mirrors the acceptance test)
    B = 32
    mm = MemoryModel.uniform(
        num_stages=S, seq_len=64, param_bytes=1e6, optimizer_bytes=2e6,
        grad_bytes=1e6, stage_input_bytes_per_token=512.0,
        layer_act_bytes_per_token=64.0, num_layers_per_stage=2,
    )
    base = mm.peak_bytes_per_stage(make_plan(S, B, spec=ScheduleSpec(kind="zb_h1")))
    limits = [p + (1.0 if s == 0 else 1e9) for s, p in enumerate(base)]
    cands = enumerate_candidates(
        S, B, mm, limits,
        space=SearchSpace(
            kinds=("zb_h1",), max_k=1,
            zb_policies=("double_remat", "saved_residual"),
        ),
    )
    w_heavy = StageCosts(
        fwd_time=[1.0] * S, bwd_time=[4.0] * S,
        fwd_bytes=[0.01] * S, bwd_bytes=[0.01] * S,
        bwd_input_time=[1.0] * S, bwd_weight_time=[3.0] * S,
        bwd_weight_saved_time=[1.2] * S,
    )
    rec = AutoTuner(
        cands, lambda _c: w_heavy, NetworkProfiler(uniform_network(S, trace))
    ).tune(0.0)
    trail = list(rec.chosen_spec.zb_policy)
    mixed = (
        trail
        and trail[0] == "double_remat"
        and trail[1:] == ["saved_residual"] * (S - 1)
    )
    return {
        "saved_residual_len_dr": len_dr,
        "saved_residual_len_sr": len_sr,
        "saved_residual_gain_vs_double_remat": len_dr / len_sr,
        "sr_tuner_policy_trail": trail,
        "sr_tuner_mixed_selected": int(bool(mixed)),
        "sr_tuner_chosen": rec.chosen,
    }


def saved_residual_kernel_metrics() -> dict:
    """The real-engine proof that SR's W body is genuinely cheaper: compile
    both W kernels of every stage of a tiny real model and compare their
    optimized-HLO FLOP counts.  The ratio is exactly the rematerialized
    forward double-remat pays per W task; FLOPs (not roofline seconds) are
    the honest gate — at bench-tiny shapes the residual-row read can make
    SR memory-bound even though the compiled work strictly shrinks.
    Deterministic given the model config.  Imports are local: this is part
    of the runtime suite (compiles jax programs) and ``--skip-runtime``
    must stay light."""
    import jax.numpy as jnp

    from repro.core.calibrate import calibrate_stage_costs
    from repro.models.common import ModelConfig
    from repro.pipeline.stage import StagedModel

    cfg = ModelConfig(
        name="bench-sr", family="dense", num_layers=2, d_model=32,
        num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=256,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    staged = StagedModel.build(cfg, 2)
    cal = calibrate_stage_costs(staged, micro_batch_size=2, seq_len=8)
    ratios = [
        p["bwd_weight"].flops / p["bwd_weight_saved"].flops for p in cal.profiles
    ]
    return {
        "sr_w_flops_ratio_min": min(ratios),
        "sr_w_flops_ratios": ratios,
        "sr_w_seconds": [p["bwd_weight_saved"].seconds for p in cal.profiles],
        "dr_w_seconds": [p["bwd_weight"].seconds for p in cal.profiles],
    }


def tuner_switch_trace() -> dict:
    """Seeded Fig-10-style regime trace (4 'hours': preemption crush ->
    contended mid-bandwidth -> eased -> crush); all decisions deterministic
    given the trace seeds.

    The candidate space spans five schedule kinds at ``max_k=2`` — at
    ``max_k=4`` a single family's deepest-k member dominates every regime
    and the trajectory never leaves it (the ROADMAP-flagged degeneracy).
    With the capped space each regime has a different winner: the crush
    hours reward zero-bubble splitting (``zb_h2``), the eased hour's cheap
    links reward ZB-V's bubble-free V placement (``zbv``), and contended
    mid-bandwidth windows reward interleaving compute over the stalls
    (``interleaved``) — the per-link bursty realizations at the decision
    instants pick which of the last two regimes each non-crush hour lands
    in, and ``tuner_kind_diversity`` gates that the trajectory keeps
    crossing >= 3 kinds."""
    S, B, hour = 4, 32, 600.0
    mm = MemoryModel.uniform(
        num_stages=S, seq_len=64, param_bytes=1e6, optimizer_bytes=2e6,
        grad_bytes=1e6, stage_input_bytes_per_token=512.0,
        layer_act_bytes_per_token=64.0, num_layers_per_stage=2,
    )
    cands = enumerate_candidates(
        S, B, mm, 1e8,
        space=SearchSpace(
            kinds=("kfkb", "zb_h1", "zb_h2", "zbv", "interleaved"),
            virtual_degrees=(2,), max_k=2,
        ),
    )

    costs_by_b = {}

    def costs_for(cand):
        if cand.micro_batch_size not in costs_by_b:
            costs_by_b[cand.micro_batch_size] = StageCosts.uniform(
                S, 0.1 * cand.micro_batch_size, act_bytes=float(cand.micro_batch_size)
            )
        return costs_by_b[cand.micro_batch_size]

    def crush(seed):
        return BurstyTrace(8.0, contended_frac=0.3, mean_free=0.1,
                           mean_contended=1.0, seed=seed)

    def contended_mid(seed):
        return BurstyTrace(100.0, contended_frac=0.3, mean_free=0.1,
                           mean_contended=1.0, seed=seed)

    def link_trace(a, b):
        seed = a * 17 + b
        return RegimeTrace(
            breakpoints=[hour, 2 * hour, 3 * hour],
            traces=[crush(seed), contended_mid(seed + 7), StableTrace(200.0),
                    crush(seed + 23)],
        )

    net = Network.build(S, link_trace)
    # window == probes-per-round: each decision reads exactly the current
    # regime's samples (a wider window leaks stale-regime samples across
    # hour boundaries and blurs the regime winners)
    tuner = AutoTuner(cands, costs_for, NetworkProfiler(net, window=3))
    recs = [tuner.tune(h * hour + 30.0) for h in range(4)]
    switches = sum(1 for r in recs[1:] if r.switched)
    beat = 0
    one_f1b = next(c.name for c in cands if c.kind == "kfkb" and c.k == 1)
    for h in (0, 1, 3):  # the preempted hours
        r = recs[h]
        if r.estimates[r.chosen] < r.estimates[one_f1b]:
            beat += 1
    kinds = [r.chosen_spec.kind for r in recs]
    return {
        "tuner_switch_count": switches,
        "tuner_chosen_kinds": kinds,
        "tuner_chosen_ks": [r.chosen_spec.k for r in recs],
        "tuner_kind_diversity": len(set(kinds)),
        "tuner_preempted_hours_beat_1f1b": beat,
        "tuner_candidates": len(cands),
    }


def device_spec_metrics() -> dict:
    """The offline hardware-matrix slice on three committed device specs.

    Everything here is spec-derived arithmetic over the pinned workload's
    committed HLO counts — no accelerator, no wall clock — so the gates
    run at the tight deterministic band:

    * ``spec_divergent_choice`` — the synthetic extreme-skew part (memory-
      starved: every program goes memory-bound, so saved-residual's
      residual-row reads cost more than double-remat's recompute FLOPs,
      and the 6 GB capacity rejects deep warmup) must keep choosing a
      DIFFERENT ``ScheduleSpec`` than the compute-bound H100 on the same
      scenario — the acceptance proof that device data steers the tuner,
    * ``spec_h100_makespan_s`` — the chosen schedule's simulated makespan
      on H100-derived seconds (the deterministic cost-regression gate that
      replaces the old wall-clock events/sec band),
    * ``spec_slow_link_makespan_ratio`` — how much the 1 GB/s synthetic
      interconnect inflates the (re-tuned) makespan vs H100: the preempted-
      network operating point as a steady-state cost ratio.
    """
    from benchmarks.hardware_matrix import conformance_slice

    spec_dir = os.path.join(_ROOT, "specs")
    slices = {
        name: conformance_slice(os.path.join(spec_dir, f"{name}.json"))
        for name in ("h100-sxm", "synthetic-extreme-skew",
                     "synthetic-slow-interconnect")
    }
    h100 = slices["h100-sxm"]
    skew = slices["synthetic-extreme-skew"]
    slow = slices["synthetic-slow-interconnect"]
    return {
        "spec_chosen": {name: s["chosen"]["name"] for name, s in slices.items()},
        "spec_divergent_choice": int(h100["chosen"] != skew["chosen"]),
        "spec_h100_makespan_s": h100["makespan_s"]["chosen"],
        "spec_slow_link_makespan_ratio": (
            slow["makespan_s"]["chosen"] / h100["makespan_s"]["chosen"]
        ),
        "spec_h100_ratio_vs_1f1b": h100["makespan_ratio_vs_1f1b"],
    }


def simulator_throughput(repeats: int = 5) -> dict:
    """Discrete-event core speed on a fixed workload (events = executed
    tasks + completed transfers).  Wall-clock, hence gated loosely."""
    S, M, k = 8, 32, 2
    costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)
    plan = make_plan(S, M, spec=ScheduleSpec(kind="zb_h1", k=k))
    net = uniform_network(S, lambda: BurstyTrace(4.0, seed=11))
    graph_tasks = sum(len(o) for o in plan.orders)
    transfers = 2 * M * (S - 1)
    events = graph_tasks + transfers
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        simulate_plan(plan, costs, net)
        best = min(best, time.perf_counter() - t0)
    return {
        "sim_events": events,
        "sim_events_per_sec": events / best,
    }


def runtime_metrics(iterations: int = 14) -> dict:
    """The live plan-switch runtime on the seeded Fig-10 scenario: real
    compiled steps (reference backend), warm kind switches across the
    interleaved re-stacking boundary, background precompilation, passive
    telemetry.  Deterministic except the wall-clock latency fractions.

    Metric definitions live in ``train_adaptive.summarize`` /
    ``grad_parity_max_err`` (shared with the entry point's JSON and the
    acceptance test); this function only renames them into the bench
    namespace.  Imports are local: this is the only benchmark that pulls
    in jax and compiles programs (~minutes), and ``--skip-runtime`` must
    stay light.
    """
    from repro.launch.train_adaptive import (
        build_fig10_scenario,
        grad_parity_max_err,
        summarize,
    )

    sc = build_fig10_scenario()
    summary = sc.coordinator.run(iterations)
    s = summarize(sc, summary)
    grad_err = grad_parity_max_err(sc)
    sc.runtime.cache.shutdown()
    return {
        "runtime_kind_switches": s["kind_switches"],
        "runtime_chosen_kinds": [d["kind"] for d in s["decision_trail"]],
        "runtime_precompile_hit_rate": s["precompile_hit_rate"],
        "runtime_cold_misses": s["cache"]["cold_misses"],
        "runtime_warm_switch_seconds": max(s["warm_switch_seconds"], default=0.0),
        "runtime_cold_switch_seconds": s["cold_switch_seconds"],
        "runtime_warm_switch_frac": s["warm_switch_latency_frac"] or 0.0,
        "runtime_mean_iteration_seconds": s["mean_iteration_seconds"],
        "runtime_probes_run": s["probe_rounds_run"],
        "runtime_probes_total": s["probe_rounds_total"],
        "runtime_probe_overhead_saved_frac": s["probe_overhead_saved_frac"],
        "runtime_grad_parity_max_err": grad_err,
        # observability (PR 9): predicted-vs-observed model health + the
        # flight-recorder decision trail (see the GATES note — both
        # deterministic: the drift join is sim-sourced on both sides)
        "model_drift_ratio": s["model_drift_ratio"],
        "model_drift_samples": s["drift_samples"],
        "tuner_decision_logged": s["tuner_decisions_logged"],
    }


def fabric_metrics(iterations: int = 8) -> dict:
    """The coordinator fabric's own health numbers on a two-host
    ``LocalTransport`` fleet (tiny 2-stage model, reference backend).

    A scripted decision trail drives the two-phase barrier through both
    verdicts, deterministically: epoch 1 proposes a spec no host can lower
    (instant fleet-wide refusal -> the aborted-switch path), epoch 2
    proposes a real candidate (precompile-vote-commit -> the warm-switch
    path, both hosts at the same boundary).  Counts and hit rates are
    deterministic; the commit's barrier latency is wall-clock (it spans
    each host's precompile), hence fingerprint-gated.  Imports are local
    for the same reason as ``runtime_metrics`` — this compiles real steps
    and ``--skip-runtime`` must stay light."""
    from repro.core import ScheduleSpec as Spec
    from repro.launch.train_adaptive import (
        build_fabric_fleet,
        fig10_parts,
        run_fabric_rounds,
    )

    _, _, cands, _ = fig10_parts(2, d_model=8)
    target = cands[1].spec

    def scripted(server):
        hist = server.barrier.history
        if not hist:
            # no host can lower this: every prepare() votes ready=False
            return Spec(kind="bogus", micro_batch_size=2)
        if len(hist) == 1:
            return target
        return None

    server, workers = build_fabric_fleet(
        num_hosts=2, num_stages=2, d_model=8, seq_len=16,
        vote_timeout=600.0, decision_fn=scripted,
    )
    try:
        out = run_fabric_rounds(server, workers, iterations)
    finally:
        for w in workers:
            w.runtime.cache.shutdown()
    fab = out["fabric"]
    commits = [r for r in server.barrier.history if r.committed]
    return {
        "fabric_hosts": fab["hosts"],
        "fabric_telemetry_windows": fab["telemetry_windows"],
        "fabric_committed_switches": fab["committed_switches"],
        "fabric_aborted_switches": fab["aborted_switches"],
        # reported only (wall-clock, not gated — see WALL_CLOCK_METRICS note)
        "fabric_barrier_latency_commit": max(
            (r.latency for r in commits), default=0.0
        ),
        # deterministic replacement gate: every committed epoch must have
        # collected a ready vote from the FULL fleet (a commit on partial
        # votes would be a barrier-protocol regression)
        "fabric_commit_ready_votes": min(
            (sum(1 for v in r.votes.values() if v.ready) for r in commits),
            default=0,
        ),
        "fabric_precompile_hit_rate_min": min(
            h["precompile_hit_rate"] for h in out["hosts"].values()
        ),
    }


def serve_metrics() -> dict:
    """Adaptive decode serving on the seeded Fig-10 serving scenario.

    Definitions live in ``repro.launch.serve_adaptive`` (shared with the
    entry point's JSON and the acceptance tests); everything runs on the
    simulated clock — arrivals, network traces, and tick pricing are all
    seeded — so every number is deterministic.  The import is local: the
    serve package pulls in the model stack, and ``--skip-runtime`` must
    stay light, but nothing here compiles a program (no engine attached).
    """
    from repro.launch.serve_adaptive import (
        chosen_specs_by_regime,
        compare_adaptive_static,
    )

    cmp = compare_adaptive_static(max_requests=60, regime="fig10", seed=0)
    div = chosen_specs_by_regime(max_requests=24, seed=0)
    a = cmp["adaptive"]
    return {
        "serve_p99_ratio_vs_static_1f1b": cmp["p99_ratio_vs_static"],
        "serve_tuner_kind_diversity": cmp["kind_diversity"],
        "serve_kinds_chosen": a["kinds_chosen"],
        "serve_slo_attainment": cmp["slo_attainment"],
        "serve_regime_divergent_choice": int(
            div["bursty"]["majority"] != div["exclusive"]["majority"]
        ),
        "serve_regime_majorities": {
            r: info["majority"] for r, info in div.items()
        },
        "serve_token_latency_p99_s": a["token_latency_p99"],
        "serve_static_token_latency_p99_s": cmp["static"]["token_latency_p99"],
        "serve_ttft_p99_s": a["ttft_p99"],
        "serve_requests_completed": a["requests_completed"],
        "serve_tokens_per_second": a["tokens_per_second"],
        "serve_validated_tracks": cmp["no_overlap_tracks"],
    }


def collect(skip_runtime: bool = False) -> dict:
    metrics = {}
    metrics.update(fig2_ratios())
    metrics.update(vector_w_gain())
    metrics.update(zbv_ratios())
    metrics.update(saved_residual_metrics())
    metrics.update(tuner_switch_trace())
    metrics.update(device_spec_metrics())
    metrics.update(simulator_throughput())
    metrics.update(serve_metrics())
    if not skip_runtime:
        metrics.update(runtime_metrics())
        metrics.update(fabric_metrics())
        metrics.update(saved_residual_kernel_metrics())
    return metrics


def previous_bench(root: str, out_name: str) -> tuple[str, dict] | None:
    """The most recent other committed BENCH_*.json (by PR number suffix)."""
    pat = re.compile(r"BENCH_PR(\d+)\.json$")
    found = []
    for f in os.listdir(root):
        m = pat.match(f)
        if m and f != out_name:
            found.append((int(m.group(1)), f))
    if not found:
        return None
    _, name = max(found)
    with open(os.path.join(root, name)) as fh:
        return name, json.load(fh)


def check_regression(metrics: dict, prev_name: str, prev: dict) -> list[str]:
    failures = []
    prev_metrics = prev.get("metrics", {})
    same_machine = prev.get("machine") == machine_fingerprint()
    for key, (direction, tol) in GATES.items():
        if key not in metrics or key not in prev_metrics:
            continue
        if key in WALL_CLOCK_METRICS and not same_machine:
            print(f"[trajectory] {key} not gated: baseline from a different "
                  f"machine ({prev_name})")
            continue
        new, old = float(metrics[key]), float(prev_metrics[key])
        if old == 0:
            continue
        if direction == "higher" and new < old * (1.0 - tol):
            failures.append(f"{key}: {new:.4g} < {old:.4g} * {1 - tol} ({prev_name})")
        if direction == "lower" and new > old * (1.0 + tol):
            failures.append(f"{key}: {new:.4g} > {old:.4g} * {1 + tol} ({prev_name})")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="write schema-versioned JSON here (e.g. BENCH_PR3.json)")
    ap.add_argument("--check", action="store_true",
                    help="fail on >10%% regression vs the previous committed BENCH_*.json")
    ap.add_argument("--skip-runtime", action="store_true",
                    help="skip the live plan-switch runtime suite (the only "
                         "one that compiles real steps; ~minutes)")
    args = ap.parse_args(argv)

    t0 = time.time()
    metrics = collect(skip_runtime=args.skip_runtime)
    payload = {
        "schema_version": SCHEMA_VERSION,
        "source": "benchmarks/trajectory.py",
        "rel_tol": REL_TOL,
        "gated": sorted(GATES),
        "machine": machine_fingerprint(),
        "metrics": metrics,
        "wall_seconds": round(time.time() - t0, 2),
    }
    print(json.dumps(payload, indent=1, default=str))
    if args.out:
        parent = os.path.dirname(os.path.abspath(args.out))
        os.makedirs(parent, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1, default=str)
            f.write("\n")
        print(f"[trajectory] wrote {os.path.abspath(args.out)}")
    if args.check:
        out_name = os.path.basename(args.out) if args.out else ""
        prev = previous_bench(_ROOT, out_name)
        if prev is None:
            print("[trajectory] no previous BENCH_*.json — gate passes trivially")
            return 0
        failures = check_regression(metrics, *prev)
        if failures:
            print("[trajectory] REGRESSION vs committed baseline:")
            for f in failures:
                print("  -", f)
            return 1
        print(f"[trajectory] no gated metric regressed vs {prev[0]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
