"""Live plan-switch runtime: the adaptive loop on the real JAX engine.

Until this subsystem existed the repo had two disconnected halves: the
*decision* stack (``core/`` — candidates, profiler, tuner, coordinator)
closed the paper's Fig-10 loop against the discrete-event simulator, while
the *execution* stack (``pipeline/engine``) compiled exactly one static
plan per process.  ``repro.runtime`` is the missing layer between them —
the paper's §5.4 coordinator-worker runtime ("dispatches the decided plan
to all workers and swaps plans with minimal overhead"), realized as:

========================  ===================================================
module                    role (paper anchor)
========================  ===================================================
``compile_cache``         §5.4 "minimal overhead", compile half: AOT
                          compiled-step cache keyed by lowered
                          ``TabularPlan`` identity, with background
                          precompilation of the tuner's top-N candidates so
                          a switch dispatches an already-compiled step
                          (Zero Bubble's observation that post-hoc schedule
                          swaps only pay off with recompilation off the
                          critical path).
``executor``              §5.4 "no effect on model parameters", state half:
                          :class:`PlanRuntime` owns params + optimizer
                          state and performs warm switches at iteration
                          boundaries across schedule *kinds* — including
                          the bitwise parameter re-stacking between the
                          flat stage layout and Megatron's looped
                          virtual-stage layout that interleaved members
                          need, optimizer moments carried bit-for-bit.
``telemetry``             §5.2 probing made passive: a per-iteration timing
                          bus; observed iteration lengths are inverted to
                          effective link bandwidths and fed into
                          ``NetworkProfiler``'s moving-average windows, so
                          the tuner suspends-and-probes only links whose
                          windows went stale (``tuning_overhead`` -> ~0).
``harness``               Fig-10 end-to-end: ``RealEngineHarness`` rides
                          the coordinator's typed ``IterationHook`` surface,
                          mirroring every tuner decision onto the live
                          engine with real gradients (entry point:
                          ``python -m repro.launch.train_adaptive``).
``fabric``                §5.4 across *hosts*: the cross-host control plane
                          — :class:`CoordinatorServer` merges per-host
                          telemetry partitions into the central tuner and
                          drives barrier-safe (all-or-none, deadline-forced)
                          spec switches on every :class:`WorkerAgent`'s
                          local ``PlanRuntime``, over in-process or TCP
                          transports (entry points: ``train_adaptive
                          --fabric N``, ``repro.launch.fabric_worker``).
``repro.serve`` (sibling) the decision+execution stacks pointed at decode
                          serving: continuous batching over fixed slots,
                          the tuner re-deciding ``ScheduleSpec`` live under
                          an SLO-weighted objective, and (optionally) real
                          compiled prefill/decode programs through the
                          *stateless* ``PlanRuntime`` mode
                          (``optimizer=None`` + ``program_factory`` +
                          ``run_program``) — same compile cache, same
                          warm-switch path, no ``TrainState`` (entry point:
                          ``python -m repro.launch.serve_adaptive``).
``repro.obs`` (sibling)   the observe half as a first-class layer: every
                          module above records into its deterministic trace
                          spans (Chrome/Perfetto export, predicted-vs-
                          observed tracks), labeled metrics registry
                          (``fabric_metrics()``/``CacheStats`` are now
                          views over it), flight-recorder ring (tuner
                          decisions, barrier transitions — auto-dumped on
                          abort/failure), and ``model_drift_ratio`` gauge
                          (see ``src/repro/obs/README.md``).
========================  ===================================================

The compiled-step programs run either the single-device reference executor
or the real ``shard_map`` engine; both consume the same lowered
``TabularPlan`` the tuner dispatches, so the decision and execution stacks
finally share one artifact end-to-end.
"""

from repro.runtime.compile_cache import (
    CacheStats,
    CompiledEntry,
    CompiledStepCache,
    enable_persistent_cache,
)
from repro.runtime.executor import (
    IterationResult,
    PlanRuntime,
    SwitchEvent,
    restack_train_state,
)
from repro.runtime.harness import HarnessRecord, RealEngineHarness
from repro.runtime.telemetry import (
    IterationTiming,
    PassiveLinkFeed,
    TelemetryBus,
    invert_effective_bandwidth,
    link_probe_specs,
)

__all__ = [
    "CacheStats",
    "CompiledEntry",
    "CompiledStepCache",
    "enable_persistent_cache",
    "IterationResult",
    "PlanRuntime",
    "SwitchEvent",
    "restack_train_state",
    "HarnessRecord",
    "RealEngineHarness",
    "IterationTiming",
    "PassiveLinkFeed",
    "TelemetryBus",
    "invert_effective_bandwidth",
    "link_probe_specs",
]
