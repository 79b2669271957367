"""Ada-Grouper core: kFkB scheduling, candidate search, cost model, tuner.

Public API re-exports the pieces a user composes:

    plan      = make_plan(S, M, spec=ScheduleSpec(kind, k, ...))
    cands     = enumerate_candidates(S, B, memory_model, limit, SearchSpace(...))
    tuner     = AutoTuner(cands, stage_costs_for, NetworkProfiler(net))
    summary   = Coordinator(tuner, net, B, interval).run(iters)
"""

from repro.core.candidates import (
    Candidate,
    enumerate_candidates,
    largest_admissible_warmup,
)
from repro.core.coordinator import (
    Coordinator,
    IterationRecord,
    RunSummary,
    shifted_network,
)
from repro.core.costmodel import CostModel, closed_form_1f1b_length, link_probe_specs
from repro.core.devicespec import (
    DeviceSpec,
    DeviceSpecError,
    WorkloadProfile,
    derive_memory_model,
    derive_stage_costs,
    load_device_spec,
    load_workload_profile,
)
from repro.core.interfaces import IterationHook, TelemetrySink
from repro.core.kinds import (
    KindSpec,
    ScheduleSpec,
    SearchSpace,
    get_kind,
    known_kinds,
    register_kind,
    registered_kinds,
)
from repro.core.memory_model import (
    ZB_SLOT_POLICIES,
    MemoryModel,
    StageMemorySpec,
    limit_curve,
    predicted_peak_live,
)
from repro.core.network import (
    BandwidthTrace,
    BurstyTrace,
    Network,
    PeriodicPreemptionTrace,
    RegimeTrace,
    StableTrace,
    uniform_network,
)
from repro.core.placement import optimize_weight_placement
from repro.core.profiler import (
    ComputeProfiler,
    LinkSample,
    MovingAverage,
    NetworkProfiler,
    merge_link_samples,
)
from repro.core.schedule import (
    Op,
    PlanEdge,
    SchedulePlan,
    TabularPlan,
    Task,
    lower_to_table,
    make_plan,
    normalize_warmup,
    peak_live_activations,
    tick_table,
    tick_table_stats,
)
from repro.core.simulator import PipelineSimulator, SimResult, simulate, simulate_plan
from repro.core.taskgraph import StageCosts, TaskGraph, TransferSpec, build_task_graph
from repro.core.tuner import AutoTuner, TuningRecord

__all__ = [
    "Candidate",
    "KindSpec",
    "ScheduleSpec",
    "SearchSpace",
    "get_kind",
    "known_kinds",
    "register_kind",
    "registered_kinds",
    "enumerate_candidates",
    "largest_admissible_warmup",
    "Coordinator",
    "shifted_network",
    "IterationRecord",
    "RunSummary",
    "IterationHook",
    "TelemetrySink",
    "CostModel",
    "closed_form_1f1b_length",
    "link_probe_specs",
    "DeviceSpec",
    "DeviceSpecError",
    "WorkloadProfile",
    "derive_memory_model",
    "derive_stage_costs",
    "load_device_spec",
    "load_workload_profile",
    "MemoryModel",
    "StageMemorySpec",
    "ZB_SLOT_POLICIES",
    "limit_curve",
    "predicted_peak_live",
    "optimize_weight_placement",
    "BandwidthTrace",
    "BurstyTrace",
    "Network",
    "PeriodicPreemptionTrace",
    "RegimeTrace",
    "StableTrace",
    "uniform_network",
    "ComputeProfiler",
    "LinkSample",
    "MovingAverage",
    "NetworkProfiler",
    "merge_link_samples",
    "Op",
    "PlanEdge",
    "SchedulePlan",
    "TabularPlan",
    "Task",
    "lower_to_table",
    "make_plan",
    "normalize_warmup",
    "peak_live_activations",
    "tick_table",
    "tick_table_stats",
    "PipelineSimulator",
    "SimResult",
    "simulate",
    "simulate_plan",
    "StageCosts",
    "TaskGraph",
    "TransferSpec",
    "build_task_graph",
    "AutoTuner",
    "TuningRecord",
]
