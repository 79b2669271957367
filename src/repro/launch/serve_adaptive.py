"""Adaptive decode serving under the Fig-10 preemption regimes, end to end.

Serving is where the paper's adaptation argument is sharpest: a per-token
decode step is memory-bound (the committed ``pinned-4stage-decode``
workload prices ~1 ms/stage on a v5e-class part against ~26 ms/stage for
the training workload), so a preempted cross-stage link does not shave a
few percent off an iteration — it IS the token latency.  This entry point
drives the :class:`~repro.serve.runtime.ServeRuntime` tick loop through the
same bursty -> exclusive -> bursty regime world as
``launch/train_adaptive``, with:

* seeded bursty **arrivals** (Markov-modulated Poisson) feeding a
  continuous batcher over fixed decode slots;
* the unmodified :class:`~repro.core.tuner.AutoTuner` re-deciding
  ``ScheduleSpec`` (kind and k) live, under the serving objective
  (:func:`~repro.serve.runtime.make_slo_objective`): SLO-weighted makespan
  — pure throughput when the queue is deep, per-token latency when slack;
* tick timings feeding the profiler windows passively via the telemetry
  bus (``source="serve"``), so retuning rarely suspends the batch;
* TTFT/TPOT/token-latency histograms + per-slot request spans in the PR 9
  observability currency.

The headline comparison (also the bench gate): adaptive serving vs a
static 1F1B decode pipeline on identical seeds — p99 token latency, SLO
attainment, and a decision trail that crosses schedule kinds and differs
between the preempted and exclusive regimes.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_adaptive \
      [--requests 80] [--regime fig10] [--seed 0] [--out serve.json]

``REPRO_SMOKE=1`` shrinks the run for CI smoke jobs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

from repro.core import (
    AutoTuner,
    BurstyTrace,
    Candidate,
    Network,
    NetworkProfiler,
    RegimeTrace,
    StableTrace,
    StageCosts,
)
from repro.core.devicespec import (
    derive_stage_costs,
    load_device_spec,
    load_workload_profile,
    spec_root,
)
from repro.launch.train_adaptive import fig10_parts
from repro.models.common import ModelConfig
from repro.obs import Observability
from repro.runtime import PassiveLinkFeed, TelemetryBus, enable_persistent_cache
from repro.serve import ArrivalProcess, ServeRuntime, SLOTracker, make_slo_objective

ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "experiments", "serve_adaptive"
)

#: serving targets the attainment gate holds: time-to-first-token and
#: time-per-output-token on the simulated clock
TTFT_SLO = 1.0
TPOT_SLO = 0.05

#: serve-network bandwidths (bytes/s against the decode workload's 8 KB
#: per-token activation handoffs): an exclusive wire moves one in ~40 µs, a
#: free-but-shared wire in ~0.3 ms, a preempted one in ~5 ms — the
#: latency-dominated regime the paper's Fig-10 serving argument lives in
FREE_BW = 2.7e7
EXCLUSIVE_BW = 2.0e8
CONTENDED_FRAC = 0.06


def serve_costs(device: str = "tpu-v5e") -> tuple[StageCosts, StageCosts]:
    """(decode, prefill) stage costs: the committed workload profiles joined
    against a committed device spec — serving priced offline, per part."""
    spec = load_device_spec(os.path.join(spec_root(), f"{device}.json"))
    root = os.path.join(spec_root(), "workloads")
    decode = derive_stage_costs(
        load_workload_profile(os.path.join(root, "pinned-4stage-decode.json")), spec
    )
    prefill = derive_stage_costs(
        load_workload_profile(os.path.join(root, "pinned-4stage-prefill.json")), spec
    )
    return decode, prefill


def build_serve_network(
    num_stages: int, regime: str = "fig10", hour: float = 4.0, seed: int = 0
) -> Network:
    """``regime``: "fig10" (bursty -> exclusive -> bursty), "bursty"
    (preempted throughout), or "exclusive" (quiet throughout)."""

    def bursty(ss: int) -> BurstyTrace:
        # preemption-dominated dwell times: during a preempted regime the
        # link spends most wall clock contended, so every plan reliably sees
        # the degraded wire (the adaptation signal, not boundary luck)
        return BurstyTrace(
            FREE_BW, contended_frac=CONTENDED_FRAC,
            mean_free=0.25, mean_contended=2.5, seed=ss,
        )

    def link(a: int, c: int):
        s = 17 * a + c + 100 * seed
        if regime == "bursty":
            return bursty(s)
        if regime == "exclusive":
            return StableTrace(EXCLUSIVE_BW)
        return RegimeTrace(
            [hour, 2 * hour], [bursty(s), StableTrace(EXCLUSIVE_BW), bursty(s + 7)]
        )

    return Network.build(num_stages, link)


@dataclasses.dataclass
class ServeScenario:
    """One wired serving world (candidates, network, tuner, tick loop)."""

    cfg: ModelConfig
    candidates: list[Candidate]
    decode_costs: StageCosts
    prefill_costs: StageCosts
    network: Network
    tuner: AutoTuner
    runtime: ServeRuntime
    slo: SLOTracker
    bus: TelemetryBus
    obs: Observability


def build_serve_scenario(
    num_stages: int = 4,
    regime: str = "fig10",
    hour: float = 4.0,
    seed: int = 0,
    rate: float = 6.0,
    burst_factor: float = 3.0,
    max_slots: int = 8,
    retune_interval: float | None = 0.25,
    tuning_overhead: float = 0.02,
    passive_staleness: float | None = 2.0,
    latency_weight: float = 2.0,
    adaptive: bool = True,
    engine=None,
    obs: Observability | None = None,
    track: str = "host0",
) -> ServeScenario:
    """The seeded serving scenario shared by this entry point, the bench
    suite, and the tests.

    ``adaptive=False`` builds the static baseline: the same arrivals, the
    same network, the same costs — but a single 1F1B candidate and no
    retuning (``retune_interval=None``), so every difference in the summary
    is the adaptive loop's doing.
    """
    cfg, _train_costs, cands, _B = fig10_parts(num_stages)
    decode_costs, prefill_costs = serve_costs()
    net = build_serve_network(num_stages, regime=regime, hour=hour, seed=seed)
    if not adaptive:
        cands = cands[:1]  # kfkb k=1 — the static 1F1B decode pipeline
        retune_interval = None
    profiler = NetworkProfiler(net, window=4)
    obs = obs or Observability.create()
    bus = TelemetryBus(metrics=obs.metrics)
    bus.subscribe(PassiveLinkFeed(profiler, sources=("serve",)))
    arrivals = ArrivalProcess(
        rate, seed=seed, burst_factor=burst_factor,
        mean_calm=1.5, mean_burst=0.6,
        prompt_len=(16, 16), new_tokens=(16, 48),
    )
    slo = SLOTracker(
        obs.metrics, trace=obs.trace, track=f"{track}/requests",
        ttft_slo=TTFT_SLO, tpot_slo=TPOT_SLO,
    )
    # the objective needs the runtime's live queue pressure, the runtime
    # needs the tuner: late-bind through a box
    box: dict = {}
    objective = (
        make_slo_objective(lambda: box["rt"].queue_pressure(), latency_weight)
        if adaptive
        else None
    )
    tuner = AutoTuner(
        cands, lambda c: decode_costs, profiler,
        passive_staleness=passive_staleness,
        flight=obs.flight, metrics=obs.metrics, objective=objective,
    )
    rt = ServeRuntime(
        tuner, net, arrivals, slo, max_slots,
        decode_costs_for=lambda c: decode_costs,
        prefill_costs_for=lambda c: prefill_costs,
        telemetry_sink=bus,
        retune_interval=retune_interval,
        tuning_overhead=tuning_overhead,
        engine=engine, obs=obs, track=track,
    )
    box["rt"] = rt
    return ServeScenario(
        cfg=cfg, candidates=cands, decode_costs=decode_costs,
        prefill_costs=prefill_costs, network=net, tuner=tuner, runtime=rt,
        slo=slo, bus=bus, obs=obs,
    )


def compare_adaptive_static(
    max_requests: int = 80, regime: str = "fig10", seed: int = 0
) -> dict:
    """The headline experiment, defined ONCE for the entry point, the bench
    trajectory, and the acceptance tests: adaptive serving vs the static
    1F1B decode baseline on identical seeds (same arrivals, same network
    traces), p99 token latency head to head."""
    adaptive = build_serve_scenario(regime=regime, seed=seed, adaptive=True)
    static = build_serve_scenario(regime=regime, seed=seed, adaptive=False)
    a = adaptive.runtime.run(max_requests)
    s = static.runtime.run(max_requests)
    a_p99, s_p99 = a["token_latency_p99"], s["token_latency_p99"]
    return {
        "adaptive": a,
        "static": s,
        # >1.0 means adaptive serves the p99 token faster than static 1F1B
        "p99_ratio_vs_static": (s_p99 / a_p99) if a_p99 else 0.0,
        "kind_diversity": len(a["kinds_chosen"]),
        "slo_attainment": a["slo_attainment"],
        "no_overlap_tracks": _validated_tracks(adaptive),
    }


def _validated_tracks(sc: ServeScenario) -> int:
    """Run the existing no-overlap trace gate over every serving track
    (per-slot request lanes + the tick lane); returns the track count."""
    from repro.obs.trace import spans_by_track, validate_no_overlap

    payload = sc.obs.trace.to_chrome_trace()
    validate_no_overlap(payload, track_prefix=sc.runtime.track)
    return sum(
        1 for t in spans_by_track(payload) if t.startswith(sc.runtime.track)
    )


def chosen_specs_by_regime(max_requests: int = 40, seed: int = 0) -> dict:
    """Majority-chosen ScheduleSpec under a preempted vs an exclusive
    network — the acceptance's "the tuner chooses differently" observable."""
    out = {}
    for regime in ("bursty", "exclusive"):
        sc = build_serve_scenario(regime=regime, seed=seed, adaptive=True)
        sc.runtime.run(max_requests)
        trail = [r.chosen for r in sc.tuner.history]
        majority = max(set(trail), key=trail.count) if trail else None
        out[regime] = {
            "majority": majority,
            "final": trail[-1] if trail else None,
            "trail": trail,
            "final_spec": (
                dataclasses.asdict(sc.tuner.history[-1].chosen_spec)
                if sc.tuner.history
                else None
            ),
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=80)
    ap.add_argument("--regime", choices=("fig10", "bursty", "exclusive"), default="fig10")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write the comparison JSON here")
    ap.add_argument(
        "--trace", default=None, metavar="OUT.json",
        help="write a Chrome/Perfetto trace of the adaptive run (per-slot "
        "request lanes, tick lane, tuner decisions)",
    )
    args = ap.parse_args(argv)
    enable_persistent_cache()
    if os.environ.get("REPRO_SMOKE"):
        args.requests = min(args.requests, 24)

    t0 = time.time()
    out = compare_adaptive_static(
        max_requests=args.requests, regime=args.regime, seed=args.seed
    )
    out["regime_divergence"] = chosen_specs_by_regime(
        max_requests=max(12, args.requests // 3), seed=args.seed
    )
    out["wall_seconds"] = round(time.time() - t0, 2)

    a, s = out["adaptive"], out["static"]
    print(f"regime {args.regime}: {args.requests} requests, seed {args.seed}")
    print("decision trail (adaptive):")
    for d in a["decision_trail"]:
        print(f"  t={d['t']:8.3f}  {d['chosen']:30s} kind={d['kind']}")
    print(
        f"token latency p99: adaptive {a['token_latency_p99']*1e3:.1f} ms vs "
        f"static {s['token_latency_p99']*1e3:.1f} ms "
        f"(ratio {out['p99_ratio_vs_static']:.2f}x)"
    )
    print(
        f"ttft p99: adaptive {a['ttft_p99']*1e3:.1f} ms vs "
        f"static {s['ttft_p99']*1e3:.1f} ms"
    )
    print(
        f"slo attainment: adaptive {a['slo_attainment']:.2f} vs "
        f"static {s['slo_attainment']:.2f} "
        f"(ttft<={TTFT_SLO}s, tpot<={TPOT_SLO}s)"
    )
    print(
        f"kinds chosen: {a['kinds_chosen']} "
        f"(diversity {out['kind_diversity']})"
    )
    for regime, info in out["regime_divergence"].items():
        print(f"  {regime:10s} majority={info['majority']} final={info['final']}")

    if args.trace:
        sc = build_serve_scenario(regime=args.regime, seed=args.seed, adaptive=True)
        sc.runtime.run(args.requests)
        sc.obs.trace.save(args.trace)
        print(f"wrote trace {os.path.abspath(args.trace)}")

    path = args.out
    if path is None:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        path = os.path.join(ARTIFACT_DIR, f"serve_{args.regime}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
        f.write("\n")
    print(f"wrote {os.path.abspath(path)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
