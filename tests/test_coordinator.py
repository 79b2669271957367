"""Coordinator / Network plumbing (repro.core.coordinator).

Three behaviours the adaptive-tuning harness silently depends on:
``tuning_overhead`` must be charged to the run's wall clock, the
``_ShiftedTrace`` view must preserve the absolute phase of periodic
preemption across iterations (a plan switch mid-regime sees the shifted
world, not t=0), and ``RunSummary.throughput`` must survive the zero-time
edge."""

import pytest

from repro.core import (
    AutoTuner,
    Candidate,
    Coordinator,
    NetworkProfiler,
    PeriodicPreemptionTrace,
    RunSummary,
    StableTrace,
    StageCosts,
    make_plan,
    shifted_network,
    simulate_plan,
    uniform_network,
)
from repro.core import coordinator
from repro.core.network import Network

_ShiftedTrace = coordinator._ShiftedTrace


def _costs_for(S=4):
    costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)
    return lambda cand: costs


def _cands(S=4, M=8):
    return [Candidate(k, 1, M, make_plan(S, M, k), 0.0) for k in (1, 2)]


def test_tuning_overhead_charged_to_total_time():
    """Each tuner invocation suspends the pipeline for ``tuning_overhead``
    seconds; total_time (and hence throughput) must include every one."""
    S = 4
    net = uniform_network(S, lambda: StableTrace(2.0))

    def run_with(overhead):
        tuner = AutoTuner(_cands(S), _costs_for(S), NetworkProfiler(net))
        coord = Coordinator(
            tuner, net, global_batch=8, tuning_interval=1e9, tuning_overhead=overhead
        )
        return coord.run(3)

    free = run_with(0.0)
    taxed = run_with(7.5)
    # one tune happens (tune_first, interval never re-fires): exactly 7.5s
    assert len(taxed.tuning) == len(free.tuning) == 1
    assert taxed.total_time == pytest.approx(free.total_time + 7.5)
    assert taxed.throughput < free.throughput


def test_shifted_trace_preserves_periodic_phase():
    """The _ShiftedTrace view at absolute time t0 must report exactly what
    the base trace reports at t0 + t — bandwidth, segment boundary, and
    integrated transfer finish times."""
    base = PeriodicPreemptionTrace(high=10.0, low=1.0, period=2.0, duty=0.5)
    for t0 in (0.0, 0.7, 1.0, 3.3):
        shifted = _ShiftedTrace(base, t0)
        for t in (0.0, 0.25, 0.5, 1.5, 2.0):
            bw_s, until_s = shifted.bw_at(t)
            bw_b, until_b = base.bw_at(t0 + t)
            assert bw_s == bw_b
            assert until_s == pytest.approx(until_b - t0)
            assert shifted.finish_time(t, 6.0) == pytest.approx(
                base.finish_time(t0 + t, 6.0) - t0
            )
        assert shifted.mean_bw(0.0, 2.0) == pytest.approx(base.mean_bw(t0, t0 + 2.0))


def test_coordinator_iterations_see_the_shifted_world():
    """Fig-10 correctness: iteration i starting mid-preemption must run
    against the preempted window, not a fresh t=0 trace.  With a period-
    aligned pipeline the simulated lengths at phase 0 and mid-phase differ,
    and the coordinator's successive iterations reproduce exactly the
    lengths of manually-shifted simulations."""
    S, M = 2, 4
    costs = StageCosts.uniform(S, 1.0, act_bytes=4.0)
    trace = PeriodicPreemptionTrace(high=8.0, low=0.25, period=16.0, duty=0.5)
    net = Network(default=StableTrace(1e15), links={(0, 1): trace, (1, 0): trace})
    plan = make_plan(S, M, 1)

    # the trace is genuinely phase-sensitive at this workload
    l0 = simulate_plan(plan, costs, shifted_network(net, 0.0)).pipeline_length
    l_mid = simulate_plan(plan, costs, shifted_network(net, 8.0)).pipeline_length
    assert l0 != pytest.approx(l_mid)

    cand = Candidate(1, 1, M, plan, 0.0)
    tuner = AutoTuner([cand], lambda c: costs, NetworkProfiler(net))
    coord = Coordinator(tuner, net, global_batch=4, tuning_interval=1e9)
    summary = coord.run(3)
    now = summary.iterations[0].start
    for rec in summary.iterations:
        assert rec.start == pytest.approx(now)
        expected = simulate_plan(
            plan, costs, shifted_network(net, rec.start)
        ).pipeline_length
        assert rec.length == pytest.approx(expected)
        now += rec.length


def test_run_summary_throughput_zero_time_edge():
    empty = RunSummary(iterations=[], tuning=[], total_time=0.0, total_samples=0)
    assert empty.throughput == 0.0  # no division by zero
    some = RunSummary(iterations=[], tuning=[], total_time=2.0, total_samples=8)
    assert some.throughput == pytest.approx(4.0)


def test_typed_hooks_receive_iteration_records():
    """The modern path: a typed IterationHook object on hooks= sees every
    IterationRecord, warning-free."""
    import warnings as _w

    S = 4
    net = uniform_network(S, lambda: StableTrace(2.0))

    class Hook:
        def __init__(self):
            self.recs = []

        def on_iteration(self, rec):
            self.recs.append(rec)

    hook = Hook()
    tuner = AutoTuner(_cands(S), _costs_for(S), NetworkProfiler(net))
    with _w.catch_warnings():
        _w.simplefilter("error")
        coord = Coordinator(
            tuner, net, global_batch=8, tuning_interval=1e9, hooks=(hook,)
        )
        summary = coord.run(3)
    assert [r.index for r in hook.recs] == [r.index for r in summary.iterations]


def test_passive_telemetry_drives_tuning_overhead_to_zero():
    """With the runtime telemetry bus feeding the profiler windows, a
    passive tuner stops suspending the pipeline: after the first round
    (cold windows), every probe is skipped and the charged tuning_overhead
    of later rounds is exactly 0 — while the legacy (non-passive) run
    keeps paying the full suspension at every interval."""
    from repro.runtime import PassiveLinkFeed, TelemetryBus

    S = 4
    net = uniform_network(S, lambda: StableTrace(2.0))
    overhead = 7.5

    def run(passive):
        prof = NetworkProfiler(net, window=4)
        tuner = AutoTuner(
            _cands(S), _costs_for(S), prof,
            passive_staleness=1e9 if passive else None,
        )
        bus = None
        if passive:
            bus = TelemetryBus()
            bus.subscribe(PassiveLinkFeed(prof))
        coord = Coordinator(
            tuner, net, global_batch=8, tuning_interval=0.0,  # tune every iter
            tuning_overhead=overhead, telemetry_sink=bus,
        )
        return coord.run(4)

    legacy = run(passive=False)
    passive = run(passive=True)
    assert len(legacy.tuning) == len(passive.tuning) == 4

    # legacy: every round probes every link and pays the full suspension
    for rec in legacy.tuning:
        assert rec.probes_skipped == 0 and rec.probe_fraction == 1.0
    assert legacy.total_tuning_overhead == pytest.approx(overhead * 4)

    # passive: the first round probes once per link (cold windows), then the
    # per-iteration feed keeps every window fresh -> zero probes, zero charge
    first, rest = passive.tuning[0], passive.tuning[1:]
    assert first.probes_run > 0  # the fallback still works when stale
    for rec in rest:
        assert rec.probes_run == 0 and rec.probe_fraction == 0.0
    assert passive.total_tuning_overhead == pytest.approx(
        overhead * first.probe_fraction
    )
    assert passive.total_tuning_overhead < 0.2 * legacy.total_tuning_overhead
