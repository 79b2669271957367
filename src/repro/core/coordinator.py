"""Coordinator: drives training iterations under the currently-chosen plan.

The paper's coordinator dispatches the decided plan to all workers and swaps
plans with minimal overhead.  Here the coordinator advances a *simulated
cluster* iteration by iteration: every iteration executes the current plan's
task graph against the ground-truth network traces (whose state depends on
wall-clock simulated time — phase matters under periodic preemption), and at
the configured interval it invokes the auto-tuner, applying plan switches
immediately.

Its two extension points are the typed control-plane protocols of
:mod:`repro.core.interfaces`:

* ``telemetry_sink`` — a :class:`~repro.core.interfaces.TelemetrySink`
  (e.g. :class:`repro.runtime.telemetry.TelemetryBus`); every simulated
  iteration's observed length is published so passive subscribers can keep
  the :class:`~repro.core.profiler.NetworkProfiler` windows fresh.
* ``hooks`` — :class:`~repro.core.interfaces.IterationHook` participants
  whose ``on_iteration(rec)`` runs after every iteration.  That is where
  :class:`repro.runtime.harness.RealEngineHarness` attaches the live
  plan-switch runtime (compiled-step cache + warm kind switches), closing
  the adaptive loop on real gradients.

One more refinement (default-off, preserving the paper's behaviour): the
charged ``tuning_overhead`` is scaled by each round's
``TuningRecord.probe_fraction`` — with a passive tuner
(``passive_staleness``) and fresh windows, no link is actually probed and
the suspension cost goes to ~0 (§5.4's "minimal overhead", measured).

This is also the harness the Fig-10 experiment uses.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.core.candidates import Candidate
from repro.core.interfaces import IterationHook, TelemetrySink
from repro.core.network import Network
from repro.core.simulator import simulate_plan
from repro.core.tuner import AutoTuner, TuningRecord

__all__ = ["IterationRecord", "RunSummary", "Coordinator", "shifted_network"]


@dataclasses.dataclass
class IterationRecord:
    index: int
    start: float
    length: float
    plan_name: str
    k: int
    samples_per_s: float


@dataclasses.dataclass
class RunSummary:
    iterations: list[IterationRecord]
    tuning: list[TuningRecord]
    total_time: float
    total_samples: int
    # wall-clock actually spent suspended in probe rounds (already included
    # in total_time); ~0 when passive telemetry keeps the windows fresh
    total_tuning_overhead: float = 0.0

    @property
    def throughput(self) -> float:
        return self.total_samples / self.total_time if self.total_time else 0.0


class _ShiftedTrace:
    """View of a trace starting at absolute time ``t0`` (simulator runs at 0)."""

    def __init__(self, base, t0: float) -> None:
        self.base = base
        self.t0 = t0

    def bw_at(self, t: float):
        bw, until = self.base.bw_at(self.t0 + t)
        return bw, until - self.t0

    def finish_time(self, start: float, nbytes: float) -> float:
        return self.base.finish_time(self.t0 + start, nbytes) - self.t0

    def mean_bw(self, a: float, b: float) -> float:
        return self.base.mean_bw(self.t0 + a, self.t0 + b)


def shifted_network(net: Network, t0: float) -> Network:
    """The network as seen from absolute simulated time ``t0`` — what lets a
    driver evaluate ``simulate_plan`` (which runs at t=0) mid-regime.  Shared
    by the training coordinator's iteration loop and the serve runtime's tick
    loop."""
    return Network(
        default=_ShiftedTrace(net.default, t0),
        links={k: _ShiftedTrace(v, t0) for k, v in net.links.items()},
    )


class Coordinator:
    def __init__(
        self,
        tuner: AutoTuner,
        network: Network,
        global_batch: int,
        tuning_interval: float,
        tuning_overhead: float = 0.0,
        hooks: Sequence[IterationHook] = (),
        telemetry_sink: TelemetrySink | None = None,
    ) -> None:
        self.tuner = tuner
        self.network = network
        self.global_batch = global_batch
        self.tuning_interval = tuning_interval
        self.tuning_overhead = tuning_overhead
        self.hooks: list[IterationHook] = list(hooks)
        self.telemetry_sink = telemetry_sink

    def run(self, num_iterations: int, tune_first: bool = True) -> RunSummary:
        now = 0.0
        iters: list[IterationRecord] = []
        overhead_total = 0.0
        next_tune = 0.0 if tune_first else self.tuning_interval
        for i in range(num_iterations):
            if now >= next_tune:
                rec_t = self.tuner.tune(now)
                # suspension is only paid for the probes actually run: a
                # passive tuner with fresh windows charges ~0 (§5.4)
                frac = getattr(rec_t, "probe_fraction", 1.0)
                charged = self.tuning_overhead * frac
                now += charged
                overhead_total += charged
                next_tune = now + self.tuning_interval
            cand: Candidate = self.tuner.current
            costs = self.tuner.stage_costs_for(cand)
            result = simulate_plan(cand.plan, costs, shifted_network(self.network, now))
            rec = IterationRecord(
                index=i,
                start=now,
                length=result.pipeline_length,
                plan_name=cand.name,
                k=cand.k,
                samples_per_s=self.global_batch / result.pipeline_length,
            )
            iters.append(rec)
            if self.telemetry_sink is not None:
                self.telemetry_sink.publish_iteration(
                    index=i,
                    plan=cand.plan,
                    costs=costs,
                    seconds=result.pipeline_length,
                    end_time=now + result.pipeline_length,
                    source="sim",
                )
            for hook in self.hooks:
                hook.on_iteration(rec)
            now += result.pipeline_length
        return RunSummary(
            iterations=iters,
            tuning=list(self.tuner.history),
            total_time=now,
            total_samples=self.global_batch * num_iterations,
            total_tuning_overhead=overhead_total,
        )
