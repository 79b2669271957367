"""The unified observability layer (``repro.obs``): trace, metrics, flight,
drift — and its instrumentation contract with the runtime/tuner/fabric.

Six suites:

* :class:`TraceRecorder` — span/instant/counter recording, first-use track
  order, Chrome trace-event schema round-trip, nesting/overlap validators,
  and the headline determinism property: under an injected tick clock two
  recordings of the same event sequence export **byte-identical** JSON;
* :func:`render_simulated_trace` — the registered-kinds gate: every registered
  schedule kind's simulated timeline must render with pairwise-disjoint
  spans on every device and link track (an overlap is a renderer or
  simulator bug), plus the committed golden fixture staying bit-for-bit
  reproducible (CI's lint job re-validates the fixture's schema);
* :class:`MetricsRegistry` — counter/gauge/histogram semantics, labeled
  series, one-name-one-kind, deterministic ``snapshot``/``delta``;
* :class:`FlightRecorder` — ring bound + drop accounting, monotonic ``seq``,
  kind filters, deterministic dumps, never-raising ``auto_dump``;
* :class:`DriftMonitor` + ``TelemetryBus`` self-reporting + the de-flaked
  ``warm_switch_frac_from_trace`` bench definition;
* integration — ``CoordinatorServer.fabric_metrics()``'s frozen dict shape
  over the registry, ``TuningRecord`` back-compat, and a scripted two-host
  fleet whose shared trace carries the acceptance contract: both hosts'
  iteration spans, the tuner's per-candidate decision trail, and a full
  PREPARE -> COMMIT barrier epoch.
"""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from repro.obs import Observability
from repro.obs.drift import DriftMonitor
from repro.obs.flight_recorder import FlightRecorder
from repro.obs.metrics import HistogramValue, MetricsRegistry
from repro.obs.trace import (
    TraceRecorder,
    TraceValidationError,
    merge_traces,
    render_simulated_trace,
    spans_by_track,
    validate_chrome_trace,
    validate_no_overlap,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


class Tick:
    """Deterministic injected clock: each reading advances by ``step``."""

    def __init__(self, step=0.001):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# TraceRecorder
# ---------------------------------------------------------------------------


def _record_sample(rec: TraceRecorder) -> None:
    with rec.span("host0/iterations", "iter 0", plan="p"):
        rec.instant("host0/fabric", "PREPARE epoch 1", spec="s")
    sp = rec.span("host0/switches", "switch q", warm=True)
    rec.end_span(sp, restacked=False)
    rec.counter("host0/fabric", "windows", 3)
    rec.add_span("predicted/stage0", "F mb0", 0.5, 1.0, op="F")
    rec.add_instant("coordinator/tuner", "decision q", 2.5, chosen="q")


def test_recorder_chrome_export_round_trip():
    rec = TraceRecorder(clock=Tick())
    _record_sample(rec)
    payload = rec.to_chrome_trace()
    validate_chrome_trace(payload)
    # one process row per track segment, one thread lane per track
    procs = {e["args"]["name"] for e in payload["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"host0", "predicted", "coordinator"}
    tracks = {e["args"]["name"] for e in payload["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tracks == {"host0/iterations", "host0/fabric", "host0/switches",
                      "predicted/stage0", "coordinator/tuner"}
    # the span args survive; instants carry scope "t"
    spans = spans_by_track(payload)
    assert spans["host0/iterations"][0]["args"] == {"plan": "p"}
    assert spans["host0/switches"][0]["args"] == {"warm": True, "restacked": False}
    instants = [e for e in payload["traceEvents"] if e["ph"] == "i"]
    assert all(e["s"] == "t" for e in instants)
    # explicit-timestamp events land at their stated times (microseconds)
    assert spans["predicted/stage0"][0]["ts"] == pytest.approx(0.5e6)
    assert spans["predicted/stage0"][0]["dur"] == pytest.approx(1.0e6)


def test_export_byte_identical_under_tick_clock():
    a, b = TraceRecorder(clock=Tick()), TraceRecorder(clock=Tick())
    _record_sample(a)
    _record_sample(b)
    assert a.to_json() == b.to_json()
    # and export is idempotent (formatting never mutates state)
    assert a.to_json() == a.to_json()


def test_track_ids_assigned_in_first_use_order():
    rec = TraceRecorder(clock=Tick())
    rec.instant("b/x", "1")
    rec.instant("a/y", "2")
    rec.instant("b/x", "3")
    payload = rec.to_chrome_trace()
    meta = [(e["args"]["name"], e["tid"]) for e in payload["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"]
    assert meta == [("b/x", 1), ("a/y", 2)]  # first use wins, stable


def test_nested_spans_validate_partial_overlap_rejected():
    rec = TraceRecorder(clock=Tick())
    rec.add_span("t/a", "outer", 0.0, 10.0)
    rec.add_span("t/a", "inner", 2.0, 3.0)
    rec.add_span("t/a", "after", 10.0, 1.0)
    validate_chrome_trace(rec.to_chrome_trace())  # nested + adjacent: fine

    bad = TraceRecorder(clock=Tick())
    bad.add_span("t/a", "one", 0.0, 10.0)
    bad.add_span("t/a", "straddle", 5.0, 10.0)
    with pytest.raises(TraceValidationError, match="partially overlaps"):
        validate_chrome_trace(bad.to_chrome_trace())


def test_validate_no_overlap_is_stricter_and_prefix_scoped():
    rec = TraceRecorder(clock=Tick())
    rec.add_span("predicted/stage0", "outer", 0.0, 10.0)
    rec.add_span("predicted/stage0", "inner", 2.0, 3.0)  # nested: schema-legal
    payload = rec.to_chrome_trace()
    validate_chrome_trace(payload)
    with pytest.raises(TraceValidationError, match="overlaps"):
        validate_no_overlap(payload, "predicted/")
    validate_no_overlap(payload, "host")  # out-of-prefix tracks not checked


def test_validate_schema_rejects_malformed_events():
    with pytest.raises(TraceValidationError, match="traceEvents"):
        validate_chrome_trace({"events": []})
    with pytest.raises(TraceValidationError, match="missing 'ts'"):
        validate_chrome_trace(
            {"traceEvents": [{"ph": "i", "name": "x", "pid": 1, "tid": 1}]}
        )
    with pytest.raises(TraceValidationError, match="non-negative 'dur'"):
        validate_chrome_trace(
            {"traceEvents": [
                {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0, "dur": -1}
            ]}
        )


def test_merge_traces_keeps_every_lane_disjoint():
    payloads = []
    for host in ("host0", "host1"):
        rec = TraceRecorder(clock=Tick())
        with rec.span(f"{host}/iterations", "iter 0"):
            pass
        payloads.append(rec.to_chrome_trace())
    merged = merge_traces(payloads)
    validate_chrome_trace(merged)
    lanes = [(e["pid"], e["tid"]) for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert len(lanes) == len(set(lanes)) == 2
    assert set(spans_by_track(merged)) == {"host0/iterations", "host1/iterations"}


def test_save_writes_loadable_json(tmp_path):
    rec = TraceRecorder(clock=Tick())
    _record_sample(rec)
    path = tmp_path / "trace.json"
    rec.save(str(path))
    validate_chrome_trace(json.loads(path.read_text()))


# ---------------------------------------------------------------------------
# render_simulated_trace: the registered-kinds no-overlap gate + golden fixture
# ---------------------------------------------------------------------------


def _spec_for(kind: str):
    from repro.core.kinds import ScheduleSpec, get_kind

    ks = get_kind(kind)
    return ScheduleSpec(
        kind=kind,
        num_virtual=2 if ks.supports_virtual else 1,
        extra_warmup=1 if ks.requires_warmup else 0,
    )


def test_every_plan_kind_renders_without_overlap():
    """Tier-1 gate: each registered kind's simulated timeline must be a
    legal schedule rendering — pairwise-disjoint spans on every device and
    link track, and the last span ending exactly at the simulated makespan."""
    from repro.core import (
        StableTrace, StageCosts, make_plan, registered_kinds, uniform_network,
    )

    S, M = 4, 8
    costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)
    for kind in registered_kinds():
        plan = make_plan(S, M, spec=_spec_for(kind))
        rec, result = render_simulated_trace(
            plan, costs, uniform_network(S, lambda: StableTrace(2.0))
        )
        payload = rec.to_chrome_trace()
        validate_chrome_trace(payload)
        validate_no_overlap(payload, "predicted/")
        spans = [e for evs in spans_by_track(payload).values() for e in evs]
        assert spans, kind
        last_end = max(e["ts"] + e["dur"] for e in spans)
        assert last_end == pytest.approx(result.pipeline_length * 1e6), kind


def test_golden_fixture_bit_for_bit_reproducible():
    """The committed fixture (CI lint re-validates its schema via
    ``python -m repro.obs.trace --validate``) must stay exactly what
    rendering produces — explicit-timestamp rendering touches no clock,
    so the export is deterministic down to the byte."""
    from repro.core import StableTrace, StageCosts, make_plan, uniform_network
    from repro.core.kinds import ScheduleSpec

    S, M = 4, 4
    rec, _ = render_simulated_trace(
        make_plan(S, M, spec=ScheduleSpec(kind="zb_h1")),
        StageCosts.uniform(S, 1.0, act_bytes=1.0),
        uniform_network(S, lambda: StableTrace(2.0)),
    )
    with open(os.path.join(GOLDEN, "predicted_zb_h1_trace.json")) as f:
        committed = f.read()
    assert rec.to_json() + "\n" == committed


def test_render_into_existing_recorder_alongside_observed_tracks():
    from repro.core import StableTrace, StageCosts, make_plan, uniform_network

    rec = TraceRecorder(clock=Tick())
    with rec.span("host0/iterations", "iter 0"):
        pass
    out, _ = render_simulated_trace(
        make_plan(2, 4, 1), StageCosts.uniform(2, 1.0, act_bytes=1.0),
        uniform_network(2, lambda: StableTrace(2.0)), recorder=rec,
    )
    assert out is rec
    tracks = set(spans_by_track(rec.to_chrome_trace()))
    assert "host0/iterations" in tracks and "predicted/stage0" in tracks


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total")
    c.inc()
    c.inc(2, host="a")
    assert c.value() == 1 and c.value(host="a") == 2
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)

    g = reg.gauge("windows")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value() == 4

    h = reg.histogram("latency_seconds")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    hv = h.value()
    assert isinstance(hv, HistogramValue)
    assert (hv.count, hv.sum, hv.min, hv.max) == (3, 6.0, 1.0, 3.0)
    assert hv.mean == pytest.approx(2.0)
    assert h.value(host="missing").count == 0  # absent series reads empty


def test_one_name_one_kind():
    reg = MetricsRegistry()
    reg.counter("x")
    reg.counter("x")  # idempotent
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("x")


def test_snapshot_flat_deterministic_and_delta():
    reg = MetricsRegistry()
    reg.counter("c").inc(3, host="a")
    reg.gauge("g").set(7)
    reg.histogram("h").observe(2.0)
    snap = reg.snapshot()
    assert snap == {
        "c{host=a}": 3,
        "g": 7,
        "h_count": 1,
        "h_sum": 2.0,
        "h_min": 2.0,
        "h_max": 2.0,
    }
    # key ORDER is deterministic (sorted names; histograms expand in a
    # fixed suffix order), so snapshots diff cleanly run-to-run
    assert list(snap) == list(reg.snapshot())

    reg.counter("c").inc(2, host="a")
    reg.gauge("g").set(4)  # gauges take the NEWER value in a delta
    reg.histogram("h").observe(6.0)
    d = reg.delta(snap)
    assert d["c{host=a}"] == 2
    assert d["g"] == 4
    assert d["h_count"] == 1 and d["h_sum"] == 6.0


# ---------------------------------------------------------------------------
# FlightRecorder
# ---------------------------------------------------------------------------


def test_ring_bound_drop_accounting_and_kind_filter():
    fr = FlightRecorder(capacity=3, clock=Tick())
    for i in range(5):
        fr.record("tick", i=i)
    fr.record("other")
    assert len(fr) == 3
    assert fr.dropped == 3
    assert [e["i"] for e in fr.events("tick")] == [3, 4]
    # seq is monotonic and survives eviction (total order over the run)
    assert [e["seq"] for e in fr.events()] == [3, 4, 5]
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_dump_schema_and_auto_dump_never_raises(tmp_path):
    path = str(tmp_path / "flight.json")
    fr = FlightRecorder(capacity=8, dump_path=path, clock=Tick())
    fr.record("tuner_decision", chosen="q")
    assert fr.auto_dump("barrier_abort epoch 1") == path
    with open(path) as f:
        payload = json.load(f)
    assert payload["schema"] == "repro.flight_recorder/1"
    assert payload["reason"] == "barrier_abort epoch 1"
    assert payload["recorded_total"] == 1 and payload["dropped"] == 0
    assert payload["events"][0]["kind"] == "tuner_decision"
    assert fr.dumps_written == 1

    # a broken disk must not mask the original failure
    fr.dump_path = str(tmp_path / "no" / "such" / "dir" / "f.json")
    assert fr.auto_dump("worker failure") is None
    assert FlightRecorder(clock=Tick()).auto_dump("no path configured") is None


# ---------------------------------------------------------------------------
# DriftMonitor + TelemetryBus self-reporting + the de-flaked bench fraction
# ---------------------------------------------------------------------------


def _timing(plan="p", seconds=2.0, source="sim", index=0):
    return SimpleNamespace(
        plan=SimpleNamespace(name=plan), seconds=seconds, source=source,
        index=index, end_time=float(index),
    )


def test_drift_monitor_median_join_and_skips():
    reg = MetricsRegistry()
    preds = {"p": 2.0}
    mon = DriftMonitor(lambda name: preds.get(name), registry=reg, window=4,
                       source="sim")
    assert mon.ratio() == 1.0  # before any sample
    mon.on_iteration(_timing(seconds=2.0))   # ratio 1.0
    mon.on_iteration(_timing(seconds=3.0))   # ratio 1.5
    mon.on_iteration(_timing(seconds=4.0))   # ratio 2.0 -> median 1.5
    assert mon.ratio() == pytest.approx(1.5)
    assert reg.gauge("model_drift_ratio").value() == pytest.approx(1.5)
    assert mon.samples == 3

    mon.on_iteration(_timing(plan="unknown"))          # no prediction
    mon.on_iteration(_timing(source="engine"))         # filtered source
    mon.on_iteration(_timing(seconds=0.0))             # degenerate sample
    assert mon.samples == 3
    assert reg.counter("drift_samples_joined_total").value() == 3
    assert reg.counter("drift_samples_skipped_total").value() == 2  # filter ≠ skip


def test_drift_alert_rising_edge_records_one_flight_event():
    fr = FlightRecorder(clock=Tick())
    mon = DriftMonitor(lambda name: 1.0, window=2, alert_threshold=0.5,
                       flight=fr)
    mon.on_iteration(_timing(seconds=1.1))
    assert not mon.drifting and not fr.events("drift_alert")
    mon.on_iteration(_timing(seconds=3.0))  # median(1.1, 3.0) = 2.05 > 1.5
    assert mon.drifting
    mon.on_iteration(_timing(seconds=3.0))  # still drifting: no second event
    (alert,) = fr.events("drift_alert")
    assert alert["ratio"] == pytest.approx(2.05)


def test_telemetry_bus_self_reports_per_source():
    from repro.runtime.telemetry import TelemetryBus

    reg = MetricsRegistry()
    bus = TelemetryBus(metrics=reg)
    seen = []
    bus.subscribe(seen.append)
    bus.publish(_timing(seconds=2.0, source="sim"))
    bus.publish(_timing(seconds=4.0, source="sim"))
    bus.publish(_timing(seconds=1.0, source="engine"))
    assert len(seen) == 3
    assert reg.counter("telemetry_published_total").value(source="sim") == 2
    assert reg.counter("telemetry_published_total").value(source="engine") == 1
    assert reg.histogram("telemetry_iteration_seconds").value(source="sim").sum == 6.0


def test_warm_switch_frac_from_trace_median_definition():
    from repro.launch.train_adaptive import warm_switch_frac_from_trace

    rec = TraceRecorder(clock=Tick())
    for i, dur in enumerate((1.0, 2.0, 9.0)):  # median 2.0 absorbs the outlier
        rec.add_span("host0/iterations", f"iter {i}", float(i * 10), dur)
    rec.add_span("host0/switches", "switch a", 0.5, 0.1, warm=True)
    rec.add_span("host0/switches", "switch b", 10.5, 0.3, warm=True)
    rec.add_span("host0/switches", "cold", 20.5, 5.0, warm=False)  # excluded
    frac = warm_switch_frac_from_trace(rec.to_chrome_trace())
    assert frac == pytest.approx(0.2 / 2.0)

    empty = TraceRecorder(clock=Tick())
    assert warm_switch_frac_from_trace(empty.to_chrome_trace()) is None


# ---------------------------------------------------------------------------
# integration: fabric dict shapes, TuningRecord back-compat, fleet trace
# ---------------------------------------------------------------------------

FABRIC_METRICS_SHAPE = {
    "hosts", "telemetry_windows", "telemetry_rounds_dropped",
    "telemetry_retention", "barrier_epochs", "committed_switches",
    "aborted_switches", "barrier_latency_max", "incumbent",
}


def test_fabric_metrics_dict_shape_frozen_over_registry():
    """The regression contract for satellite consumers
    (``benchmarks/trajectory.py``, the distributed CI artifact): migrating
    the values onto the registry must not move a single key."""
    from repro.core.kinds import ScheduleSpec
    from repro.runtime.fabric import CoordinatorServer

    server = CoordinatorServer(
        ("a", "b"), initial_spec=ScheduleSpec(kind="kfkb", k=1, micro_batch_size=2)
    )
    fab = server.fabric_metrics()
    assert set(fab) == FABRIC_METRICS_SHAPE
    assert fab["hosts"] == 2 and fab["barrier_epochs"] == 0
    assert isinstance(fab["incumbent"], dict)
    # the registry snapshot rides along additively on the trace export
    trace = server.telemetry_trace()
    assert trace["registry"]["fabric_hosts"] == 2
    assert trace["metrics"] == fab  # legacy alias stays the same dict


def test_tuning_record_rejected_candidates_back_compat():
    from repro.core import ScheduleSpec
    from repro.core.tuner import AutoTuner, TuningRecord

    rec = TuningRecord(time=0.0, estimates={"a": 1.0}, chosen="a",
                       chosen_spec=ScheduleSpec(), switched=False)
    assert rec.rejected_candidates == ()  # pre-PR-9 construction still valid

    rejections = AutoTuner._rejections(
        {"win": 10.0, "slow": 12.0, "tie": 10.0}, "win"
    )
    assert [n for n, _, _ in rejections] == ["tie", "slow"]  # best-first
    assert "wins deterministic order" in rejections[0][2]
    assert "20.0% slower" in rejections[1][2]


def test_fleet_trace_carries_acceptance_contract():
    """One scripted two-host fleet, one shared Observability bundle: the
    exported trace must hold both hosts' iteration spans, the tuner's
    per-candidate decision trail, and a full PREPARE -> COMMIT epoch; the
    flight ring must hold the structured trail behind it; and the
    ``CacheStats`` view must agree with the registry it reads from."""
    from repro.launch.train_adaptive import (
        build_fabric_fleet,
        fig10_parts,
        run_fabric_rounds,
    )

    _, _, cands, _ = fig10_parts(2, d_model=8)
    target = cands[1].spec

    def one_shot(server):
        return target if not server.barrier.history else None

    obs = Observability.create(clock=Tick())
    server, workers = build_fabric_fleet(
        num_hosts=2, num_stages=2, d_model=8, seq_len=16,
        vote_timeout=600.0, decision_fn=one_shot, obs=obs,
    )
    try:
        out = run_fabric_rounds(server, workers, 5)
    finally:
        for w in workers:
            w.runtime.cache.shutdown()

    payload = obs.trace.to_chrome_trace()
    validate_chrome_trace(payload)
    tracks = set(spans_by_track(payload))
    assert {"host0/iterations", "host1/iterations", "coordinator/barrier"} <= tracks

    instants = [e["name"] for e in payload["traceEvents"] if e["ph"] == "i"]
    assert any(n.startswith("PREPARE epoch") for n in instants)
    assert any(n.startswith("COMMIT epoch") for n in instants)
    assert any(n.startswith("decision ") for n in instants)  # tuner trail

    # the structured trail behind the verdict
    (decision,) = [fr for fr in obs.flight.events("tuner_decision")[:1]]
    assert set(decision) >= {"chosen", "estimates", "rejected", "switched"}
    (verdict,) = obs.flight.events("barrier_verdict")
    assert verdict["committed"] and len(obs.flight.events("barrier_vote")) == 2
    assert out["fabric"]["committed_switches"] == 1

    # CacheStats back-compat: still a dataclass view, but its values are the
    # shared registry's per-track series (one registry, per-host stats)
    stats = workers[0].runtime.cache.stats
    assert dataclasses.asdict(stats)  # legacy consumers still asdict() it
    assert stats.gets > 0 and 0.0 <= stats.hit_rate <= 1.0
    assert stats.gets == int(
        obs.metrics.counter("cache_gets_total").value(track="host0")
    )
