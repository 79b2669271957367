"""Fig-10 end-to-end on the REAL engine: the live plan-switch runtime.

The paper's regime experiment — preemption appears, eases, returns; the
tuner re-decides at intervals; the coordinator swaps plans with minimal
overhead — previously ran simulation-only.  This entry point closes the
loop with real gradients:

* the network world stays a seeded :class:`RegimeTrace` (the one thing a
  CPU container cannot make real) driving the discrete-event simulator and
  the tuner's decisions;
* every coordinator iteration is mirrored onto a live
  :class:`~repro.runtime.executor.PlanRuntime` step — a real compiled
  training iteration of the chosen plan, with warm kind switches (AOT
  cache + background precompilation of the tuner's favourites) and bitwise
  parameter re-stacking across the interleaved boundary;
* iteration timings flow back through the telemetry bus into the
  profiler's windows, so the tuner only suspends-and-probes links whose
  windows went stale.

The default scenario (4 stages, bursty -> exclusive -> bursty) flips the
chosen schedule kind at least twice: ``zb_h2`` under contention,
``interleaved_zb`` on the quiet network, back again — exercising the
compile cache, the layout re-stacking, and the passive-telemetry path in
one run.

Usage:
  PYTHONPATH=src python -m repro.launch.train_adaptive \
      [--iterations 14] [--backend reference] [--out runtime_fig10.json]

``REPRO_SMOKE=1`` shrinks iterations for CI smoke runs.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import json
import statistics
import time

import jax.numpy as jnp

from repro.core import (
    AutoTuner,
    BurstyTrace,
    Candidate,
    Coordinator,
    Network,
    NetworkProfiler,
    RegimeTrace,
    ScheduleSpec,
    StableTrace,
    StageCosts,
    make_plan,
    uniform_network,
)
from repro.data import SyntheticTextDataset
from repro.models.common import ModelConfig
from repro.obs import (
    DriftMonitor,
    Observability,
    render_simulated_trace,
    spans_by_track,
)
from repro.optim import make_optimizer
from repro.runtime import (
    PassiveLinkFeed,
    PlanRuntime,
    RealEngineHarness,
    TelemetryBus,
    enable_persistent_cache,
)

ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "experiments", "train_adaptive"
)


def fig10_parts(
    num_stages: int = 4, d_model: int = 16
) -> tuple[ModelConfig, StageCosts, list[Candidate], int]:
    """The Fig-10 scenario's shared static parts: model config, calibrated
    stage costs, the candidate set (1F1B, 2F2B, ZB-H1, ZB-H2(w=2),
    interleaved-ZB(v=2)) and the global batch.

    Factored out so the single-process harness AND every fabric host (in
    or out of process — see ``repro.launch.fabric_worker``) construct the
    identical candidate universe: a :class:`ScheduleSpec` on the wire must
    resolve to the same logical plan on every host."""
    S, M, b = num_stages, num_stages, 2
    B = M * b
    cfg = ModelConfig(
        "runtime-tiny", "dense", num_layers=2 * S, d_model=d_model, num_heads=2,
        num_kv_heads=2, d_ff=2 * d_model, vocab_size=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    costs = StageCosts.uniform(S, 1.0, act_bytes=2.0)
    cands = [
        Candidate(1, b, M, make_plan(S, M, 1, micro_batch_size=b), 0.0),
        Candidate(2, b, M, make_plan(S, M, 2, micro_batch_size=b), 0.0),
        Candidate(
            1, b, M,
            make_plan(S, M, spec=ScheduleSpec(kind="zb_h1", micro_batch_size=b)),
            0.0,
        ),
        Candidate(
            1, b, M,
            make_plan(
                S, M,
                spec=ScheduleSpec(kind="zb_h2", extra_warmup=2, micro_batch_size=b),
            ),
            0.0,
        ),
        Candidate(
            1, b, M,
            make_plan(
                S, M,
                spec=ScheduleSpec(
                    kind="interleaved_zb", num_virtual=2, micro_batch_size=b
                ),
            ),
            0.0,
        ),
    ]
    return cfg, costs, cands, B


@dataclasses.dataclass
class Fig10Scenario:
    """Everything a runtime Fig-10 run needs, wired together."""

    cfg: ModelConfig
    candidates: list[Candidate]
    costs: StageCosts
    network: Network
    coordinator: Coordinator
    tuner: AutoTuner
    runtime: PlanRuntime
    harness: RealEngineHarness
    bus: TelemetryBus
    dataset: SyntheticTextDataset
    global_batch: int
    obs: Observability
    drift: DriftMonitor


def build_fig10_scenario(
    num_stages: int = 4,
    hour: float = 120.0,
    tuning_interval: float = 55.0,
    tuning_overhead: float = 5.0,
    passive_staleness: float | None = 40.0,
    backend: str = "reference",
    mesh=None,
    d_model: int = 16,
    seq_len: int = 64,
    seed: int = 0,
    precompile_top_n: int = 5,
    obs: Observability | None = None,
) -> Fig10Scenario:
    """The seeded regime scenario shared by this entry point, the benchmark
    trajectory, and the acceptance tests.

    Candidate kinds: 1F1B, 2F2B, ZB-H1, ZB-H2(w=2) and interleaved-ZB
    (v=2).  Under the bursty regimes the deep-warmup zero-bubble plan wins;
    on the exclusive network the interleaved composition's shorter
    fill/drain takes over — so the decision trail flips kinds at least
    twice, crossing the parameter re-stacking boundary both ways.
    """
    cfg, costs, cands, B = fig10_parts(num_stages, d_model=d_model)
    S = num_stages

    def link(a: int, c: int):
        s = 17 * a + c + 100 * seed
        bursty = lambda ss: BurstyTrace(
            8.0, contended_frac=0.05, mean_free=0.5, mean_contended=2.0, seed=ss
        )
        return RegimeTrace([hour, 2 * hour], [bursty(s), StableTrace(50.0), bursty(s + 7)])

    net = Network.build(S, link)
    profiler = NetworkProfiler(net, window=4)
    obs = obs or Observability.create()
    tuner = AutoTuner(
        cands, lambda c: costs, profiler, passive_staleness=passive_staleness,
        flight=obs.flight, metrics=obs.metrics,
    )
    bus = TelemetryBus(metrics=obs.metrics)
    bus.subscribe(PassiveLinkFeed(profiler))
    # predicted-vs-observed drift on the deterministic clock: observed =
    # the coordinator's simulated iteration lengths (source="sim"), predicted
    # = the tuner's own latest cost-model estimate for the plan that ran —
    # i.e. how far the analytic cost model has drifted from the
    # discrete-event simulator's ground truth, seeded and reproducible
    drift = DriftMonitor(
        predict_fn=lambda name: (
            tuner.history[-1].estimates.get(name) if tuner.history else None
        ),
        registry=obs.metrics,
        source="sim",
        flight=obs.flight,
    )
    bus.subscribe(drift.on_iteration)
    opt = make_optimizer("adamw", schedule=lambda s: jnp.float32(1e-3))
    runtime = PlanRuntime(
        cfg, S, opt, global_batch=B, seq_len=seq_len, backend=backend, mesh=mesh,
        telemetry=bus, init_key=seed, obs=obs,
    )
    dataset = SyntheticTextDataset(cfg.vocab_size, seq_len, B, seed=seed)

    def batch_fn(i: int):
        batch = dataset.batch_at(i)
        return batch.tokens, batch.labels

    harness = RealEngineHarness(
        runtime, tuner, batch_fn, precompile_top_n=precompile_top_n
    )
    coord = Coordinator(
        tuner, net, global_batch=B, tuning_interval=tuning_interval,
        tuning_overhead=tuning_overhead, hooks=(harness,),
        telemetry_sink=bus,
    )
    return Fig10Scenario(
        cfg=cfg, candidates=cands, costs=costs, network=net, coordinator=coord,
        tuner=tuner, runtime=runtime, harness=harness, bus=bus, dataset=dataset,
        global_batch=B, obs=obs, drift=drift,
    )


def build_fabric_fleet(
    num_hosts: int = 2,
    num_stages: int = 4,
    seed: int = 0,
    backend: str = "reference",
    tuning_interval: float = 0.0,
    vote_timeout: float = 30.0,
    boundary_lead: int = 2,
    decision_fn=None,
    d_model: int = 16,
    seq_len: int = 64,
    obs: Observability | None = None,
):
    """An N-host coordinator fabric over LocalTransport, sharing the Fig-10
    scenario's model/candidates.

    Each host owns a full :class:`PlanRuntime` replica training its own
    data shard (``seed + host``); the coordinator runs the unmodified
    AutoTuner over an *offline* profiler fed only by the hosts' merged
    telemetry windows, and dispatches switches through the two-phase
    barrier.  ``decision_fn`` (server -> spec | None) scripts the switch
    trail deterministically; without it the passive tuner decides.

    Returns ``(server, workers)`` — drive with
    ``run_fabric_rounds(server, workers, n)``.
    """
    from repro.runtime.fabric import (
        CoordinatorServer,
        FabricConfig,
        LocalTransport,
        WorkerAgent,
        fabric_probe_links,
    )

    cfg, costs, cands, B = fig10_parts(num_stages, d_model=d_model)
    S = num_stages
    costs_for = lambda c: costs  # noqa: E731
    # ONE shared observability bundle: every host's runtime spans, the
    # coordinator's barrier/tuner tracks, and the flight ring all land in
    # the same trace (in-process fleet — the multi-process launch gives
    # each worker its own bundle and merges the exports)
    obs = obs or Observability.create()
    profiler = NetworkProfiler(None, window=4)  # offline: telemetry-only
    tuner = AutoTuner(
        cands, costs_for, profiler, passive_staleness=float("inf"),
        flight=obs.flight, metrics=obs.metrics,
    )
    hosts = tuple(f"host{i}" for i in range(num_hosts))
    server = CoordinatorServer(
        hosts,
        initial_spec=cands[0].spec,
        tuner=tuner,
        config=FabricConfig(
            tuning_interval=tuning_interval,
            vote_timeout=vote_timeout,
            boundary_lead=boundary_lead,
        ),
        decision_fn=decision_fn,
        obs=obs,
    )
    probe_links = fabric_probe_links(cands, costs_for)
    workers = []
    for i, host in enumerate(hosts):
        opt = make_optimizer("adamw", schedule=lambda s: jnp.float32(1e-3))
        runtime = PlanRuntime(
            cfg, S, opt, global_batch=B, seq_len=seq_len, backend=backend,
            init_key=seed, obs=obs, obs_track=host,
        )
        dataset = SyntheticTextDataset(cfg.vocab_size, seq_len, B, seed=seed + i)

        def batch_fn(it: int, ds=dataset):
            batch = ds.batch_at(it)
            return batch.tokens, batch.labels

        workers.append(
            WorkerAgent(
                host, runtime, LocalTransport(server, host), batch_fn,
                costs=costs, initial_spec=cands[0].spec,
                probe_links=probe_links, obs=obs,
            )
        )
    return server, workers


def run_fabric_rounds(server, workers, num_iterations: int) -> dict:
    """Drive every worker through ``num_iterations`` fabric rounds
    (round-robin — the deterministic interleave tier-1 tests rely on) and
    return the fleet summary."""
    for _ in range(num_iterations):
        for w in workers:
            w.step()
    per_host = {
        w.host: {
            "iterations": len(w.runtime.iterations),
            "losses": [round(r.loss, 4) for r in w.runtime.iterations],
            "spec": dataclasses.asdict(w.current_spec),
            "switches": len(w.runtime.switch_events),
            "precompile_hit_rate": w.runtime.cache.stats.hit_rate,
        }
        for w in workers
    }
    return {"fabric": server.fabric_metrics(), "hosts": per_host}


def warm_switch_frac_from_trace(trace_payload: dict) -> float | None:
    """``median(warm switch span) / median(iteration span)`` over every
    ``*/switches`` and ``*/iterations`` track in a Chrome trace payload.

    This is the de-flaked definition of the warm-switch bench gate: medians
    over the recorded spans absorb the one-off scheduler hiccup that made
    the old ``max(switch)/mean(iter)`` wall-clock ratio noisy, and the spans
    come from the same recorder every other timeline number uses.  ``None``
    when the trace has no warm switch or no iteration spans."""
    by_track = spans_by_track(trace_payload)
    switch_durs = [
        e["dur"]
        for track, events in by_track.items()
        if track.endswith("/switches")
        for e in events
        if (e.get("args") or {}).get("warm")
    ]
    iter_durs = [
        e["dur"]
        for track, events in by_track.items()
        if track.endswith("/iterations")
        for e in events
    ]
    if not switch_durs or not iter_durs:
        return None
    med_iter = statistics.median(iter_durs)
    return statistics.median(switch_durs) / med_iter if med_iter else None


def summarize(sc: Fig10Scenario, summary) -> dict:
    """Canonical metric aggregation for a runtime Fig-10 run.

    The SINGLE definition consumed by this entry point's JSON, the
    benchmark trajectory's ``runtime_*`` metrics, and the acceptance test —
    so all three always report the same numbers for the same run."""
    rt, stats = sc.runtime, sc.runtime.cache.stats
    warm = [e for e in rt.switch_events if e.warm]
    cold = [e for e in rt.switch_events if not e.warm]
    mean_iter = rt.mean_iteration_seconds
    probes_run = sum(r.probes_run for r in summary.tuning)
    probes_total = sum(r.probes_run + r.probes_skipped for r in summary.tuning)
    full_suspend = sc.coordinator.tuning_overhead * len(summary.tuning)
    return {
        "iterations": len(rt.iterations),
        "losses": [round(r.loss, 4) for r in rt.iterations],
        "decision_trail": [
            {"t": round(r.time, 1), "chosen": r.chosen, "kind": r.chosen_spec.kind}
            for r in summary.tuning
        ],
        "kind_switches": sc.harness.kind_switches,
        "switch_events": [dataclasses.asdict(e) for e in rt.switch_events],
        "mean_iteration_seconds": mean_iter,
        "warm_switch_seconds": [e.seconds for e in warm],
        # median warm-switch span over median iteration span, both read from
        # the runtime's trace spans (see warm_switch_frac_from_trace) — the
        # de-flaked definition the bench gate consumes
        "warm_switch_latency_frac": warm_switch_frac_from_trace(
            sc.obs.trace.to_chrome_trace()
        ),
        "cold_switch_seconds": max(
            (e.seconds + e.compile_seconds for e in cold), default=0.0
        ),
        "precompile_hit_rate": stats.hit_rate,
        "cache": dataclasses.asdict(stats),
        "probe_rounds_run": probes_run,
        "probe_rounds_total": probes_total,
        "tuning_overhead_charged": summary.total_tuning_overhead,
        "probe_overhead_saved_frac": (
            1.0 - summary.total_tuning_overhead / full_suspend if full_suspend else 0.0
        ),
        "sim_total_time": summary.total_time,
        # observe-then-adapt health: rolling-median observed/predicted
        # iteration ratio (cost model vs discrete-event simulator — 1.0 is a
        # perfect model) and the flight ring's tuner decision trail
        "model_drift_ratio": sc.drift.ratio(),
        "drift_samples": sc.drift.samples,
        "tuner_decisions_logged": len(sc.obs.flight.events("tuner_decision")),
    }


def grad_parity_max_err(sc: Fig10Scenario, batch_index: int = 999) -> float:
    """Max abs gradient difference vs the ``jax.grad`` oracle on the run's
    CURRENT (switched-and-restacked) state — the acceptance's "matches the
    unswitched reference gradients" observable, defined once for the entry
    point, the benchmark, and the test."""
    import jax
    import numpy as np

    from repro.pipeline.engine import reference_pipeline_grads

    rt = sc.runtime
    plan = rt.current_table.plan
    staged = rt.staged_for(plan.num_virtual)
    M = plan.num_microbatches
    b = sc.global_batch // M
    batch = sc.dataset.batch_at(batch_index)
    tok = batch.tokens.reshape(M, b, rt.seq_len)
    lab = batch.labels.reshape(M, b, rt.seq_len)

    def oracle(p):
        return sum(staged.full_loss(p, tok[m], lab[m]) for m in range(M)) / M

    _, ograds = jax.value_and_grad(oracle)(rt.state.params)
    _, rgrads = reference_pipeline_grads(staged, rt.state.params, tok, lab, plan)
    return max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(g))))
        for a, g in zip(
            jax.tree_util.tree_leaves(ograds), jax.tree_util.tree_leaves(rgrads)
        )
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iterations", type=int, default=14)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--backend", choices=("reference", "spmd"), default="reference")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write the run summary JSON here")
    ap.add_argument(
        "--fabric", type=int, default=0, metavar="N",
        help="run an N-host coordinator fabric (in-process LocalTransport "
        "fleet: central tuner + barrier-safe switching) instead of the "
        "single-process harness",
    )
    ap.add_argument(
        "--vote-timeout", type=float, default=600.0,
        help="fabric PREPARE->deadline span in seconds (first-time "
        "precompiles must fit inside it or the epoch aborts and retries)",
    )
    ap.add_argument(
        "--trace", default=None, metavar="OUT.json",
        help="write a Chrome/Perfetto trace of the run here: observed "
        "spans (per-host iterations/switches, barrier epochs, tuner "
        "decisions) plus the simulator's predicted timeline of the final "
        "plan on predicted/* tracks (open both side-by-side in "
        "https://ui.perfetto.dev)",
    )
    args = ap.parse_args(argv)
    enable_persistent_cache()
    if os.environ.get("REPRO_SMOKE"):
        args.iterations = min(args.iterations, 6)

    if args.fabric:
        if args.backend != "reference":
            ap.error("--fabric currently supports the reference backend only")
        server, workers = build_fabric_fleet(
            num_hosts=args.fabric, num_stages=args.stages, seed=args.seed,
            vote_timeout=args.vote_timeout,
        )
        t0 = time.time()
        out = run_fabric_rounds(server, workers, args.iterations)
        out["wall_seconds"] = round(time.time() - t0, 2)
        if args.trace:
            # predicted side: the incumbent plan's simulated timeline on a
            # stable 50 GB/s-class network (the fabric itself is offline —
            # telemetry-fed — so a fixed reference wire keeps it readable)
            spec = server.incumbent
            w0 = workers[0]
            plan = make_plan(
                w0.runtime.num_stages,
                w0.runtime.global_batch // spec.micro_batch_size,
                spec=spec,
            )
            render_simulated_trace(
                plan, w0.costs,
                uniform_network(args.stages, lambda: StableTrace(50.0)),
                recorder=server.obs.trace,
            )
            server.obs.trace.save(args.trace)
            server.obs.flight.dump(args.trace + ".flight.json", reason="run end")
            print(f"wrote trace {os.path.abspath(args.trace)} (+ .flight.json)")
        fm = out["fabric"]
        print(
            f"fabric: {fm['hosts']} hosts, "
            f"{fm['telemetry_windows']} telemetry windows"
        )
        print(
            f"barrier epochs: {fm['barrier_epochs']} "
            f"(committed {fm['committed_switches']}, "
            f"aborted {fm['aborted_switches']})"
        )
        print(f"incumbent: {fm['incumbent']}")
        path = args.out
        if path is None:
            os.makedirs(ARTIFACT_DIR, exist_ok=True)
            path = os.path.join(ARTIFACT_DIR, "fig10_fabric.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1, default=str)
            f.write("\n")
        print(f"wrote {os.path.abspath(path)}")
        for w in workers:
            w.runtime.cache.shutdown()
        return 0

    mesh = None
    if args.backend == "spmd":
        from repro.pipeline import stage_mesh

        mesh = stage_mesh(args.stages)
    sc = build_fig10_scenario(
        num_stages=args.stages, backend=args.backend, mesh=mesh, seed=args.seed
    )
    t0 = time.time()
    summary = sc.coordinator.run(args.iterations)
    out = summarize(sc, summary)
    out["wall_seconds"] = round(time.time() - t0, 2)
    if args.trace:
        # predicted side: the FINAL chosen plan's simulated timeline under
        # the run's own (regime-traced) network; decision instants land at
        # simulated time on coordinator/tuner
        for rec in sc.tuner.history:
            sc.obs.trace.add_instant(
                "coordinator/tuner", f"decision {rec.chosen}", rec.time,
                estimates={k: rec.estimates[k] for k in sorted(rec.estimates)},
                rejected=[
                    {"name": n, "estimate": e, "reason": r}
                    for n, e, r in rec.rejected_candidates
                ],
            )
        render_simulated_trace(
            sc.runtime.current_table.plan, sc.costs, sc.network,
            recorder=sc.obs.trace,
        )
        sc.obs.trace.save(args.trace)
        print(f"wrote trace {os.path.abspath(args.trace)}")

    print("decision trail:")
    for d in out["decision_trail"]:
        print(f"  t={d['t']:7.1f}  {d['chosen']:30s} kind={d['kind']}")
    print(f"kind switches: {out['kind_switches']}")
    print(
        f"precompile hit rate: {out['precompile_hit_rate']:.2f}  "
        f"(cache: {out['cache']})"
    )
    if out["warm_switch_latency_frac"] is not None:
        print(
            f"warm switch latency: median trace span "
            f"= {100*out['warm_switch_latency_frac']:.2f}% of a "
            f"{out['mean_iteration_seconds']*1e3:.0f} ms iteration"
        )
    print(
        f"model drift ratio: {out['model_drift_ratio']:.3f} "
        f"({out['drift_samples']} samples; 1.0 = perfect cost model)"
    )
    print(
        f"probes run/total: {out['probe_rounds_run']}/{out['probe_rounds_total']}  "
        f"charged overhead {out['tuning_overhead_charged']:.2f}s (sim)"
    )
    print(f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")

    path = args.out
    if path is None:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        path = os.path.join(ARTIFACT_DIR, f"fig10_runtime_{args.backend}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
        f.write("\n")
    print(f"wrote {os.path.abspath(path)}")
    sc.runtime.cache.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
