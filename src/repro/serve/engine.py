"""ServeEngine: real compiled prefill/decode behind the serve tick loop.

The simulated :class:`~repro.serve.runtime.ServeRuntime` prices every tick
against the trace network; this engine makes the *tokens* real.  It owns the
model params plus the decode state: one K/V/SSM cache in the decode step's
own batch layout (``api.init_cache(cfg, max_slots, max_len)``, a row per
slot), per-slot positions and per-slot last tokens.  The device holds only
the parameters and the cache: positions (``[max_slots]``) and last tokens
(``[max_slots, 1]``) are host NumPy arrays, rebound (never written in
place) whenever they change, so an array a caller holds stays as it was.
A tick is one ``device_put`` of both, one program and one ``device_get``
of its new tokens; prefill and release touch them on the host alone.  It
runs two kinds of programs:

* **grouped decode tick** — one compiled program per dispatched
  :class:`~repro.core.schedule.TabularPlan`, built by the ``program_factory``
  hook of a *stateless* :class:`~repro.runtime.executor.PlanRuntime`
  (``optimizer=None``).  The program is one batched
  :func:`repro.models.api.decode_fn` with ``groups=M``: its single loop
  walks every (layer, group) step, group g being the plan's b = max_slots/M
  slots ``[g·b, g·b + b)`` at their own positions (continuous batching),
  and writes one new K/V token per slot in place.  The cache is donated,
  so the tick updates it where it lies.  The executable genuinely depends
  on the plan through M, so the tuner's live ``switch_to`` exercises the
  same ``CompiledStepCache`` warm-switch path training uses.
* **fused prefill** — :func:`repro.models.api.prefill_with_cache` on a
  batch-1 program per prompt length (compiled once per length), written
  into the admitted slot's cache row.  Prefill is plan-independent: it runs
  before the request joins the grouped grid.

Decoding is greedy (temperature 0) so serving runs are reproducible
token-for-token; emitted tokens accumulate in ``outputs[rid]``.

Each call opens ``repro.serve.*`` profiler spans (:func:`repro.obs.span`)
around its host steps, so a trace puts the device's idle time down to them:
``decode_tick`` carries ``occupied``/``max_slots``, ``host_reads`` (the
device→host transfers the tick makes: 1, whatever the occupancy) and
``kv_aliased_bytes`` (the cache bytes the decode program aliases from input
to output; 0 means XLA declined the donation and copies the cache), a
prefill request ``prompt_len`` and ``new_program`` (1 when its length
compiles a new prefill program).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.common import ModelConfig
from repro.obs import span
from repro.runtime.executor import PlanRuntime

__all__ = ["ServeEngine"]


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        num_stages: int,
        max_slots: int,
        max_len: int,
        init_key: int = 0,
        obs=None,
        track: str = "serve",
    ) -> None:
        if cfg.family in ("encdec", "vlm"):
            raise NotImplementedError(f"serving does not support family {cfg.family!r}")
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.params = api.init_params(jax.random.PRNGKey(init_key), cfg)
        self.kv = api.init_cache(cfg, max_slots, max_len)  # row s is slot s
        self.positions = np.zeros((max_slots,), np.int32)
        self.tokens = np.zeros((max_slots, 1), np.int32)
        self.outputs: dict[int, list[int]] = {}
        self._slot_rid: list[int | None] = [None] * max_slots
        self._prefills: dict[int, object] = {}  # prompt length -> jitted prefill
        self._aliased: dict[tuple, int] = {}  # plan key -> kv_aliased_bytes
        self.kv_aliased_bytes = 0
        # stateless runtime: no TrainState, programs come from our factory,
        # but the compile cache / warm-switch machinery is the training one
        self.runtime = PlanRuntime(
            cfg,
            num_stages,
            optimizer=None,
            global_batch=max_slots,
            seq_len=max_len,
            program_factory=self._program_for,
            obs=obs,
            obs_track=track,
        )

    # -- program factory (one executable per dispatched plan) ------------------

    def _program_for(self, table):
        plan = table.plan
        M = plan.num_microbatches
        if self.max_slots % M:
            raise ValueError(
                f"plan {plan.name} needs M={M} | max_slots={self.max_slots}"
            )
        cfg = self.cfg

        def step(params, kv, positions, tokens):
            logits, kv = api.decode_fn(params, cfg, kv, positions, {"tokens": tokens}, groups=M)
            return kv, jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]

        spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t
        )
        args = (spec(self.params), spec(self.kv), spec(self.positions), spec(self.tokens))
        return jax.jit(step, donate_argnums=(1,)), args

    # -- ServeRuntime hooks ----------------------------------------------------

    def switch_to(self, table):
        event = self.runtime.switch_to(table)
        key = self.runtime.cache.plan_key(table)
        if key not in self._aliased:
            stats = self.runtime.compiled.memory_analysis()
            self._aliased[key] = int(stats.alias_size_in_bytes) if stats is not None else 0
        self.kv_aliased_bytes = self._aliased[key]
        return event

    def _prefill_program(self, prompt_len: int):
        """The jitted batch-1 prefill of one prompt length (compiled on its
        first call)."""
        cfg, max_len = self.cfg, self.max_len

        def prefill(params, tokens):
            cache = api.init_cache(cfg, 1, max_len)
            logits, cache = api.prefill_with_cache(params, cfg, cache, {"tokens": tokens})
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache

        return jax.jit(prefill)

    def prefill(self, admitted) -> None:
        """Fused-prefill each admitted request's prompt into its slot row;
        the prompt is a deterministic seeded token sequence per request."""
        for inf in admitted:
            req = inf.request
            program = self._prefills.get(req.prompt_len)
            new_program = program is None
            if new_program:
                program = self._prefills[req.prompt_len] = self._prefill_program(
                    req.prompt_len
                )
            with span(
                "repro.serve.prefill.request",
                prompt_len=req.prompt_len,
                new_program=int(new_program),
            ):
                with span("repro.serve.prefill.prompt"):
                    key = jax.random.PRNGKey(req.rid)
                    prompt = jax.random.randint(
                        key, (1, req.prompt_len), 0, self.cfg.vocab_size, jnp.int32
                    )
                with span("repro.serve.prefill.program"):
                    tok, row = program(self.params, prompt)
                s = inf.slot
                with span("repro.serve.prefill.insert"):
                    self.kv = _set_slot(self.kv, row, s)
                self._slot_rid[s] = req.rid
                with span("repro.serve.prefill.emit"):
                    first = int(jax.device_get(tok)[0])
                    self.outputs[req.rid] = [first]
                    self._set_host(s, req.prompt_len, first)

    def decode_tick(self, in_flight) -> None:
        """One grouped decode step of the CURRENT plan over all slots (empty
        slots compute padding, as a fixed-shape batch would)."""
        with span(
            "repro.serve.decode_tick",
            occupied=len(in_flight),
            max_slots=self.max_slots,
            host_reads=1,  # the tick's one device_get of its new tokens
            kv_aliased_bytes=self.kv_aliased_bytes,
        ):
            positions, tokens = jax.device_put((self.positions, self.tokens))
            (self.kv, new_tok), _seconds = self.runtime.run_program(
                self.params, self.kv, positions, tokens, label="decode"
            )
            with span("repro.serve.decode.emit"):
                self.tokens = jax.device_get(new_tok)
                advance = np.zeros((self.max_slots,), np.int32)
                for inf in in_flight:
                    advance[inf.slot] = 1
                    self.outputs[inf.request.rid].append(int(self.tokens[inf.slot, 0]))
                self.positions = self.positions + advance

    def slot_cache(self, s: int):
        """Slot ``s``'s rows of the cache, as a batch-1 cache."""
        return _slot_rows(self.kv, s)

    def release(self, slots) -> None:
        with span("repro.serve.release", slots=len(slots)):
            for s in slots:
                self._slot_rid[s] = None
                self._set_host(s, 0, 0)

    def _set_host(self, s: int, position: int, token: int) -> None:
        """Slot ``s``'s host position and last token, in fresh arrays."""
        self.positions = self.positions.copy()
        self.positions[s] = position
        self.tokens = self.tokens.copy()
        self.tokens[s] = token


def _slot_rows(kv, s: int):
    """Slot ``s``'s rows of the cache ``kv`` as a batch-1 cache; a block
    cache's leaves carry their layer axis before the batch."""
    take = lambda axis: lambda x: jax.lax.dynamic_slice_in_dim(x, s, 1, axis)  # noqa: E731
    out = dict(kv, prefix=jax.tree_util.tree_map(take(0), kv["prefix"]))
    if "blocks" in kv:
        out["blocks"] = jax.tree_util.tree_map(take(1), kv["blocks"])
    return out


def _set_slot(kv, row, s: int):
    """The cache ``kv`` with the batch-1 cache ``row`` written where
    :func:`_slot_rows` reads slot ``s``."""
    put = lambda axis: lambda x, r: jax.lax.dynamic_update_slice_in_dim(x, r, s, axis)  # noqa: E731
    out = dict(kv, prefix=jax.tree_util.tree_map(put(0), kv["prefix"], row["prefix"]))
    if "blocks" in kv:
        out["blocks"] = jax.tree_util.tree_map(put(1), kv["blocks"], row["blocks"])
    return out
