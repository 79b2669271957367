"""PlanRuntime: warm plan switches across schedule *kinds* on the real engine.

§5.4: "Switching between schedule plans does not require variable buffers
to be dumped out and restored ... no effect on model parameters."  That
holds verbatim for (k, b, w) switches — the parameter pytree is identical —
but switching into (or out of) an *interleaved* member changes the
parameter **layout**: a flat ``S``-stage model stacks its leaves ``[S,
reps, ...]`` while a ``v``-way interleaved plan runs the ``S * v``
virtual-stage sibling stacked ``[S * v, reps / v, ...]`` in global
virtual-stage order (the engine maps that to Megatron's looped placement
internally).  :func:`restack_train_state` performs that re-stacking
bitwise:

* block (per-layer) leaves: a pure ``reshape`` — stage ``s``'s layers are
  contiguous, and global virtual stage ``j`` owns exactly the ``reps / v``
  layers at offset ``j * reps / v``, so row-major reshape IS the layout
  map (bitwise, both directions);
* replicated leaves (``embed`` / ``final_norm``): every virtual stage
  carries a copy, but only virtual stage 0 (token embedding) and the last
  virtual stage (final norm + unembed head) receive gradients, so
  expansion repeats each flat row for its ``v`` chunks and collapse picks
  each flat stage's canonical copy — row ``s * v``, EXCEPT the last flat
  stage, whose authoritative copy is the final virtual stage's row
  ``S * v - 1`` (dropping it would discard the trained unembed head);
* everything else (step counters) passes through untouched.

Optimizer state (AdamW ``m``/``v`` mirror the params pytree) re-stacks with
the same function — reshape and row-gather are bitwise, so the optimizer
moments carry over bit-for-bit, which is what makes a mid-training kind
switch mathematically invisible (the switch-equivalence suite holds the
runtime to 5e-6 against unswitched per-segment references).

:class:`PlanRuntime` owns the :class:`~repro.training.TrainState` and a
:class:`~repro.runtime.compile_cache.CompiledStepCache`; ``switch_to`` is
the warm path (fetch executable, re-stack if the layout changed, swap a
pointer) and ``run_iteration`` executes + times the current compiled step,
publishing to the telemetry bus.  Backends: ``"reference"`` (single-device
grid walk — in-process, used by tests/benchmarks) and ``"spmd"`` (the real
``shard_map`` engine on a ``stage``-axis mesh).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.interfaces import TelemetrySink
from repro.core.schedule import TabularPlan
from repro.models.common import ModelConfig
from repro.obs import Observability, span
from repro.pipeline.engine import make_pipeline_step, reference_pipeline_grads
from repro.pipeline.stage import StagedModel
from repro.runtime.compile_cache import CompiledStepCache
from repro.training.state import TrainState, create_train_state

__all__ = ["SwitchEvent", "IterationResult", "PlanRuntime", "restack_train_state"]


# ---------------------------------------------------------------------------
# Bitwise parameter re-stacking between virtual-stage layouts
# ---------------------------------------------------------------------------


def _leaf_role(path) -> str | None:
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "name", None)
        if key in ("embed", "final_norm"):
            return "replicated"
        if key == "blocks":
            return "blocks"
    return None


def _collapse_replicated(y, num_stages: int, v: int):
    """Row selection for replicated leaves, ``S*v -> S``: flat stage ``s``
    takes its first chunk's copy, except the last flat stage, which must
    keep the FINAL virtual stage's copy (the trained unembed head).

    Written as a reshape plus a select along the chunk axis, not a row
    gather: flat stage ``s``'s rows are the ``v`` rows its device already
    holds, so a stage-sharded leaf collapses without communication (a
    gather would make the compiler all-gather every replicated leaf)."""
    y = y.reshape((num_stages, v) + y.shape[1:])
    last = (jnp.arange(num_stages) == num_stages - 1).reshape(
        (num_stages,) + (1,) * (y.ndim - 2)
    )
    return jnp.where(last, y[:, v - 1], y[:, 0])


def restack_train_state(state, num_stages: int, v_from: int, v_to: int):
    """Re-stack a :class:`TrainState` (or any params-shaped pytree wrapped
    in one) between the ``v_from``- and ``v_to``-way virtual layouts.

    Bitwise: block leaves reshape, replicated leaves repeat/select, scalars
    pass through.  ``v_from == v_to`` returns the state unchanged."""
    if v_from == v_to:
        return state
    S = num_stages

    def leaf(path, x):
        role = _leaf_role(path)
        if role is None:
            return x
        y = x
        if v_from > 1:  # collapse to flat
            if role == "blocks":
                if y.shape[0] != S * v_from:
                    raise ValueError(
                        f"blocks leaf leading dim {y.shape[0]} != S*v={S * v_from}"
                    )
                y = y.reshape((S, v_from * y.shape[1]) + y.shape[2:])
            else:
                y = _collapse_replicated(y, S, v_from)
        if v_to > 1:  # expand to the target layout
            if role == "blocks":
                reps = y.shape[1]
                if reps % v_to:
                    raise ValueError(
                        f"cannot split {reps} reps/stage over v={v_to} chunks "
                        f"(need v | reps)"
                    )
                y = y.reshape((S * v_to, reps // v_to) + y.shape[2:])
            else:
                y = jnp.repeat(y, v_to, axis=0)
        return y

    return jax.tree_util.tree_map_with_path(leaf, state)


# ---------------------------------------------------------------------------
# The runtime
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SwitchEvent:
    iteration: int
    from_plan: str
    to_plan: str
    from_kind: str
    to_kind: str
    restacked: bool  # the parameter layout changed (interleaved boundary)
    warm: bool  # executable was ready before the switch was requested
    seconds: float  # dispatch latency: fetch + re-stack + pointer swap
    compile_seconds: float  # 0 for warm hits
    # full schedule coordinates of both sides — the same ScheduleSpec the
    # candidate set, the tuning record and the compile-cache key carry
    from_spec: "object | None" = None
    to_spec: "object | None" = None


@dataclasses.dataclass
class IterationResult:
    index: int
    plan_name: str
    kind: str
    loss: float
    seconds: float


class PlanRuntime:
    """Owns params/optimizer state; executes and hot-swaps compiled steps."""

    def __init__(
        self,
        cfg: ModelConfig,
        num_stages: int,
        optimizer,
        global_batch: int,
        seq_len: int,
        backend: str = "reference",
        mesh=None,
        data_axis: str | None = None,
        cache: CompiledStepCache | None = None,
        telemetry: TelemetrySink | None = None,
        init_key: int = 0,
        obs: Observability | None = None,
        obs_track: str = "runtime",
        program_factory=None,
    ) -> None:
        if backend not in ("reference", "spmd"):
            raise ValueError(f"unknown backend {backend!r}")
        if backend == "spmd" and mesh is None:
            raise ValueError("spmd backend needs a mesh with a 'stage' axis")
        if mesh is not None and any(t != AxisType.Auto for t in mesh.axis_types):
            raise ValueError(
                f"mesh axes {mesh.axis_types} are not all auto: build the mesh "
                "with repro.pipeline.stage_mesh"
            )
        self.cfg = cfg
        self.num_stages = num_stages
        self.optimizer = optimizer
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.backend = backend
        self.mesh = mesh
        self.data_axis = data_axis
        self.telemetry = telemetry
        self._staged: dict[int, StagedModel] = {}
        # program_factory overrides the training-step factory: the serving
        # stack compiles grouped decode/prefill programs per plan through the
        # same cache and warm-switch path.  With optimizer=None the runtime
        # is *stateless* — it owns no TrainState (the serve engine owns its
        # params/caches) and run_iteration is unavailable; use run_program.
        self.program_factory = program_factory
        if optimizer is None:
            if program_factory is None:
                raise ValueError(
                    "optimizer=None (stateless serving mode) requires a "
                    "program_factory"
                )
            self.state = None
            self._flat_spec = None
        else:
            staged0 = self.staged_for(1)

            def init():
                params = staged0.init_all_stages(jax.random.PRNGKey(init_key))
                return create_train_state(params, optimizer)

            # layout specs are value-free, so the background compile thread
            # can read them while the main thread trains
            self._flat_spec = jax.eval_shape(init)
            # the state is born in the mesh layout every executable is
            # AOT-compiled against (stage-stacked leaves sharded over the
            # stage axis, scalars replicated): no device ever holds it whole
            shardings = self._state_sharding(1) if backend == "spmd" else None
            self.state: TrainState = jax.jit(init, out_shardings=shardings)()
        self.current_v = 1
        # a fresh cache joins the shared registry (series scoped by track so
        # an in-process fleet's per-host stats stay per-host); a borrowed
        # cache keeps whatever registry its owner gave it
        self.cache = cache or CompiledStepCache(
            program_factory or self._program_for,
            metrics=obs.metrics if obs is not None else None,
            labels={"track": obs_track} if obs is not None else None,
        )
        self.current_table: TabularPlan | None = None
        self._compiled = None
        # AOT-compiled re-stacking programs per (v_from, v_to): the warm
        # switch path must not pay tracing for the layout change either
        self._restack_compiled: dict[tuple[int, int], Any] = {}
        self._restack_lock = threading.Lock()
        self.switch_events: list[SwitchEvent] = []
        self.iterations: list[IterationResult] = []
        self.last_grads = None
        # every span lands in a profiler trace (``repro.runtime.*``); with
        # ``obs`` it is also recorded on "{obs_track}/switches" or
        # "{obs_track}/iterations", and switches go to the flight ring
        self.obs = obs
        self.obs_track = obs_track
        self._recorder = obs.trace if obs is not None else None

    # -- model/program plumbing ----------------------------------------------

    def staged_for(self, v: int) -> StagedModel:
        if v not in self._staged:
            self._staged[v] = StagedModel.build(self.cfg, self.num_stages * v)
        return self._staged[v]

    def _state_sharding(self, v: int):
        """Mesh placement of the layout-``v`` state (spmd backend): leaves
        stacked over the ``S * v`` virtual stages shard on the stage axis,
        scalars replicate."""
        lead = self.num_stages * v
        stage = NamedSharding(self.mesh, P("stage"))
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda sp: stage if sp.ndim >= 1 and sp.shape[0] == lead else rep,
            self._spec_for(v),
        )

    def _spec_for(self, v: int):
        return jax.eval_shape(
            lambda s: restack_train_state(s, self.num_stages, 1, v), self._flat_spec
        )

    def _state_spec_for(self, v: int):
        spec = self._spec_for(v)
        if self.backend != "spmd":
            return spec
        return jax.tree_util.tree_map(
            lambda sp, sh: jax.ShapeDtypeStruct(sp.shape, sp.dtype, sharding=sh),
            spec,
            self._state_sharding(v),
        )

    def _data_sharding(self):
        spec = P(None, self.data_axis) if self.data_axis else P()
        return NamedSharding(self.mesh, spec)

    def _data_spec_for(self, plan) -> tuple:
        M = plan.num_microbatches
        if self.global_batch % M:
            raise ValueError(
                f"plan {plan.name} needs M={M} | global_batch={self.global_batch}"
            )
        b = self.global_batch // M
        shape = (M, b, self.seq_len)
        sharding = self._data_sharding() if self.backend == "spmd" else None
        one = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
        return (one, one)

    def _program_for(self, table: TabularPlan):
        """Cache factory: (jitted step, example args) for one lowered plan.

        The step consumes/produces the plan's OWN layout; re-stacking at
        switch time is the runtime's job, so each executable stays valid
        for the whole run."""
        plan = table.plan
        v = plan.num_virtual
        staged = self.staged_for(v)
        optimizer = self.optimizer

        if self.backend == "reference":

            def grads_fn(params, tokens, labels):
                return reference_pipeline_grads(staged, params, tokens, labels, plan)

        else:
            engine = make_pipeline_step(
                staged, plan, self.mesh, data_axis=self.data_axis
            )

            def grads_fn(params, tokens, labels):
                return engine(params, tokens, labels)

        def step(state: TrainState, tokens, labels):
            loss, grads = grads_fn(state.params, tokens, labels)
            new_params, new_opt, metrics = optimizer.update(
                state.params, grads, state.opt_state
            )
            new_state = TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            )
            return new_state, loss, grads

        args = (self._state_spec_for(v),) + self._data_spec_for(plan)
        # the old state dies with the step: donating it lets the new state
        # reuse its buffers instead of holding both on the device
        return jax.jit(step, donate_argnums=0), args

    # -- the warm switch path -------------------------------------------------

    def _restack_program(self, v_from: int, v_to: int):
        """AOT-compiled layout change ``v_from -> v_to`` (compiled at most
        once per direction; compiled in the background by ``precompile``)."""
        key = (v_from, v_to)
        with self._restack_lock:
            prog = self._restack_compiled.get(key)
        if prog is None:
            S = self.num_stages
            out = self._state_sharding(v_to) if self.backend == "spmd" else None
            fn = jax.jit(
                lambda s: restack_train_state(s, S, v_from, v_to), out_shardings=out
            )
            # compiled, not run: warming it on a zero state would hold a
            # third full state (v_from in, v_to out) beside the live one,
            # more than a v5e holds for GPT-XL on four chips
            prog = fn.lower(self._state_spec_for(v_from)).compile()
            with self._restack_lock:
                self._restack_compiled.setdefault(key, prog)
        return prog

    def precompile(self, tables) -> int:
        """Background-compile step programs for ``tables`` plus the
        re-stacking programs any of their layout transitions could need."""
        tables = list(tables)
        layouts = {t.plan.num_virtual for t in tables} | {self.current_v, 1}
        for a in sorted(layouts):
            for b in sorted(layouts):
                if a != b and (a, b) not in self._restack_compiled:
                    self.cache.background(lambda a=a, b=b: self._restack_program(a, b))
        return self.cache.precompile(tables)

    def switch_to(self, table: TabularPlan) -> SwitchEvent:
        """Dispatch a new plan at an iteration boundary.

        Warm path: executable already compiled -> fetch + (if the layout
        changed) bitwise re-stack + pointer swap.  Cold path additionally
        pays the synchronous compile (recorded separately so the warm
        latency the acceptance gate tracks is not polluted)."""
        warm = self.cache.contains(table)
        v_new = table.plan.num_virtual
        # stateless (serving) runtimes track the layout but have no owned
        # state to re-stack — the engine's params are layout-independent
        restacked = v_new != self.current_v and self.state is not None
        from_plan = self.current_table.plan.name if self.current_table else ""
        with span(
            "repro.runtime.switch",
            recorder=self._recorder,
            track=f"{self.obs_track}/switches",
            title=f"switch {table.plan.name}",
            to_plan=table.plan.name,
            from_plan=from_plan,
            warm=warm,
            restacked=restacked,
            iteration=len(self.iterations),
        ):
            t0 = time.perf_counter()
            entry = self.cache.get(table)
            t1 = time.perf_counter()
            if restacked:
                prog = self._restack_program(self.current_v, v_new)
                self.state = jax.block_until_ready(prog(self.state))
            self.current_v = v_new
            seconds = time.perf_counter() - t0
        event = SwitchEvent(
            iteration=len(self.iterations),
            from_plan=from_plan,
            to_plan=table.plan.name,
            from_kind=self.current_table.plan.kind if self.current_table else "",
            to_kind=table.plan.kind,
            restacked=restacked,
            warm=warm,
            seconds=seconds if warm else seconds - (t1 - t0),
            compile_seconds=0.0 if warm else (t1 - t0),
            from_spec=self.current_table.plan.spec if self.current_table else None,
            to_spec=table.plan.spec,
        )
        self.current_table = table
        self._compiled = entry.compiled
        self.switch_events.append(event)
        if self.obs is not None:
            self.obs.flight.record(
                "plan_switch",
                iteration=event.iteration,
                from_plan=event.from_plan,
                to_plan=event.to_plan,
                warm=warm,
                restacked=restacked,
            )
        return event

    # -- execution ------------------------------------------------------------

    def run_iteration(self, tokens, labels) -> IterationResult:
        """One training step of the current plan on ``[global_batch, T]``
        data (re-shaped to the plan's ``[M, b, T]`` micro-batch grid)."""
        if self.state is None:
            raise RuntimeError(
                "stateless serving runtime owns no TrainState; use run_program"
            )
        if self.current_table is None:
            raise RuntimeError("no plan dispatched; call switch_to first")
        plan = self.current_table.plan
        index = len(self.iterations)
        with span(
            "repro.runtime.iteration",
            recorder=self._recorder,
            track=f"{self.obs_track}/iterations",
            title=f"iter {index} {plan.name}",
            step=index,
            plan=plan.name,
            index=index,
        ) as sp:
            with span("repro.runtime.feed"):
                M = plan.num_microbatches
                b = self.global_batch // M
                tokens = jnp.asarray(tokens).reshape(M, b, self.seq_len)
                labels = jnp.asarray(labels).reshape(M, b, self.seq_len)
                if self.backend == "spmd":
                    sharding = self._data_sharding()
                    tokens = jax.device_put(tokens, sharding)
                    labels = jax.device_put(labels, sharding)
            # the previous step's grads must not stay live beside this step's
            self.last_grads = None
            t0 = time.perf_counter()
            with span("repro.runtime.launch"):
                state, loss, grads = self._compiled(self.state, tokens, labels)
            with span("repro.runtime.sync"):
                loss = float(jax.block_until_ready(loss))
            seconds = time.perf_counter() - t0
            self.state = state
            self.last_grads = grads
            if sp is not None:
                sp.args["loss"] = loss
        result = IterationResult(
            index=index,
            plan_name=plan.name,
            kind=plan.kind,
            loss=loss,
            seconds=seconds,
        )
        self.iterations.append(result)
        if self.telemetry is not None:
            self.telemetry.publish_iteration(
                index=result.index,
                plan=plan,
                seconds=seconds,
                end_time=time.perf_counter(),
                source="engine",
            )
        return result

    def run_program(self, *args, label: str = "serve"):
        """Execute the current compiled program on explicit operands.

        The serving execution path: programs built by ``program_factory``
        (grouped decode ticks, fused prefill) carry their own state in their
        operands, so the runtime only times them and keeps the observability
        surface identical to training (span per execution on
        ``{obs_track}/iterations``).  Returns ``(outputs, seconds)``."""
        if self._compiled is None:
            raise RuntimeError("no plan dispatched; call switch_to first")
        plan = self.current_table.plan
        with span(
            "repro.runtime.program",
            recorder=self._recorder,
            track=f"{self.obs_track}/iterations",
            title=f"{label} {plan.name}",
            plan=plan.name,
            label=label,
        ):
            t0 = time.perf_counter()
            out = jax.block_until_ready(self._compiled(*args))
            seconds = time.perf_counter() - t0
        return out, seconds

    # -- inspection -----------------------------------------------------------

    @property
    def compiled(self):
        """The current plan's compiled executable (None before a switch)."""
        return self._compiled

    def state_in_flat_layout(self) -> TrainState:
        """The owned state re-stacked to the canonical flat (v=1) layout —
        what checkpoints and cross-kind comparisons consume."""
        return restack_train_state(self.state, self.num_stages, self.current_v, 1)

    def grads_in_flat_layout(self) -> Any:
        if self.last_grads is None:
            return None
        return restack_train_state(
            self.last_grads, self.num_stages, self.current_v, 1
        )

    @property
    def mean_iteration_seconds(self) -> float:
        if not self.iterations:
            return 0.0
        return sum(r.seconds for r in self.iterations) / len(self.iterations)
