"""Schedule-family pipeline execution engines.

Two executors drive the SAME lowered :class:`~repro.core.schedule.TabularPlan`,
which is what makes the scheduling layer real rather than simulated:

* :func:`reference_pipeline_grads` — single-device Python walk of the
  tabular grid.  Executes every task kind (forward, combined backward,
  zero-bubble ``BWD_INPUT``/``BWD_WEIGHT``, interleaved chunks) in exactly
  the plan's order with explicit activation slots and transfer buffers;
  used to validate that ANY family plan computes gradients identical to the
  unpipelined model.

Both executors are op-driven off the lowered grid, so the whole schedule
family — ``kfkb``, ``zb_h1``, ``zb_h2`` (deeper warmup, same zb task
bodies), ``interleaved``, and the joint ``interleaved_zb`` (chunked
``BWD_INPUT``/``BWD_WEIGHT`` over the virtual-stage ring) — runs through
the same code paths; a new kind only has to lower to a valid
:class:`~repro.core.schedule.TabularPlan`.  Lowering goes through
``plan.lower()``, which caches the table on the static plan (shared with
the tuner's dispatch path — never re-lowered).

* :func:`make_pipeline_step` — the real lock-step ``shard_map`` program:
  devices live on the mesh's ``stage`` axis, data parallel over the
  remaining axis.  Each tick every device executes at most one task
  (``lax.switch`` on its grid row), then the plan's transfer *channels*
  move payloads: one ``ppermute`` per used ring direction (DOWN ``s ->
  s+1``, UP ``s -> s-1``) per payload kind, plus a ppermute-free LOOP
  channel for intra-device chain hops.  Flat plans use DOWN for
  activations and UP for gradients; Megatron's looped placement rings the
  same two (virtual stage ``j`` lives on device ``j % S``, so the forward
  chain wraps ``S-1 -> 0``); ZB-V's mirrored placement is what exercises
  everything at once — chunk-0 forwards ride DOWN, chunk-1 forwards ride
  UP, and the turn is a LOOP.  Which channels exist, which queue a task
  pops and where a payload lands are all *static* tables derived from the
  grid plus the kind's placement map (:func:`_channel_tables`), so §4.4's
  early-arrival buffering stays structural, exactly as analyzed in the
  paper — per (channel, device) every queue is a single-source FIFO link.

Backward uses the stage-input checkpoint policy: a stage saves only its
input per in-flight micro-batch and rematerializes the stage body inside
``jax.vjp`` during the backward task — matching the memory model
(``checkpoint_policy="stage_input"``).  Zero-bubble plans split that
backward, and the plan's per-stage ``zb_policy[s]`` picks how the split is
paid for:

* ``"double_remat"`` (default): ``BWD_INPUT`` rematerializes and emits only
  the input gradient (keeping the upstream critical path short) while
  stashing the incoming output gradient in a per-slot context;
  ``BWD_WEIGHT`` later rematerializes *again* to produce the weight
  gradients and frees the slot.  The split costs one extra
  rematerialization — the price of filling bubbles with W work without
  storing per-layer activations.
* ``"saved_residual"``: ``BWD_INPUT`` runs ONE combined ``jax.vjp`` over
  ``(params, x)`` — XLA dead-code-eliminates the unused weight-gradient
  half — and its closure residuals stay in the live slot (the reference
  engine keeps the pullback itself; the SPMD engine packs the residual
  leaves into a per-slot f32 row, see :mod:`repro.pipeline.residuals`).
  ``BWD_WEIGHT`` is then a pure pullback with NO second rematerialization,
  spending the residual bytes the memory model priced for exactly this
  stage.  Chosen per stage by the tuner against the memory-limit curve.

Interleaved plans expect a :class:`~repro.pipeline.stage.StagedModel` built
with ``S * v`` stages; parameter stacks are in *global virtual-stage
order*, and the engine internally re-orders them to Megatron's looped
placement (device ``s`` hosts chunks ``{c * S + s}``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P
import numpy as np

from repro.core.schedule import Op, SchedulePlan
from repro.pipeline.residuals import (
    pack_residuals,
    probe_residual_layout,
    rebuild_vjp,
)
from repro.pipeline.stage import StagedModel

__all__ = [
    "reference_pipeline_grads",
    "make_pipeline_step",
    "stage_mesh",
    "queue_capacities",
    "arrival_tables",
]


# ---------------------------------------------------------------------------
# Static schedule-derived tables
# ---------------------------------------------------------------------------


_BWD_SENDERS = (int(Op.BWD), int(Op.BWD_INPUT))


def _grid_chunks(table: np.ndarray) -> np.ndarray:
    """Chunk column of a grid; legacy [S, T, 3] tick tables are chunkless."""
    if table.shape[-1] >= 4:
        return table[:, :, 2]
    return np.zeros(table.shape[:2], dtype=np.int32)


def arrival_tables(
    table: np.ndarray, num_virtual: int = 1
) -> tuple[np.ndarray, np.ndarray]:
    """``fwd_arrive[s, t]`` — device ``s`` receives a forward activation at
    the END of tick ``t`` (its upstream neighbour executed a sending FWD at
    ``t``); ``bwd_arrive[s, t]`` likewise for gradients from downstream.
    Accepts both the legacy ``[S, T, 3]`` tick table and the ``[S, T, 4]``
    tabular grid; for interleaved plans the neighbours wrap around the ring
    and a task only sends if it is not the boundary virtual stage."""
    S, T = table.shape[:2]
    ops = table[:, :, 0]
    vstage = _grid_chunks(table) * S + np.arange(S)[:, None]
    V = S * num_virtual
    sends_f = (ops == int(Op.FWD)) & (vstage != V - 1)
    sends_b = np.isin(ops, _BWD_SENDERS) & (vstage != 0)
    fwd = np.zeros((S, T), bool)
    bwd = np.zeros((S, T), bool)
    for s in range(S):
        up = (s - 1) % S if num_virtual > 1 else s - 1
        if up >= 0:
            fwd[s] = sends_f[up]
        down = (s + 1) % S if num_virtual > 1 else s + 1
        if down < S:
            bwd[s] = sends_b[down]
    return fwd, bwd


def queue_capacities(table: np.ndarray, num_virtual: int = 1) -> tuple[int, int]:
    """Exact max in-flight depth of the fwd / bwd arrival queues."""
    S, T = table.shape[:2]
    ops = table[:, :, 0]
    vstage = _grid_chunks(table) * S + np.arange(S)[:, None]
    V = S * num_virtual
    fwd_arr, bwd_arr = arrival_tables(table, num_virtual)
    cap_f = cap_b = 1
    for s in range(S):
        depth_f = depth_b = 0
        for t in range(T):
            # consumption happens during tick t, arrivals at its end
            if ops[s, t] == int(Op.FWD) and vstage[s, t] != 0:
                depth_f -= 1
            if ops[s, t] in _BWD_SENDERS and vstage[s, t] != V - 1:
                depth_b -= 1
            if fwd_arr[s, t]:
                depth_f += 1
            if bwd_arr[s, t]:
                depth_b += 1
            cap_f = max(cap_f, depth_f)
            cap_b = max(cap_b, depth_b)
    return cap_f, cap_b


def _placement_perm(plan: SchedulePlan) -> np.ndarray:
    """Permutation mapping device-major position ``s * v + c`` to the global
    virtual stage device ``s``'s chunk ``c`` hosts, under the plan kind's
    placement map (looped ``c * S + s`` by default; ZB-V's mirrored V).
    Identity when ``v == 1``."""
    S, v = plan.num_stages, plan.num_virtual
    pl = plan.placement
    return np.array(
        [int(pl.vstage_of[s, c]) for s in range(S) for c in range(v)], dtype=np.int64
    )


#: transfer channels of the lock-step engine: a payload leaving device ``s``
#: at the end of a tick either shifts DOWN the ring (to ``s + 1``), UP (to
#: ``s - 1``), or stays LOCAL (ZB-V's intra-device turn — no ppermute).
#: Flat plans use DOWN for activations and UP for gradients; Megatron rings
#: the same two; the V placement is what exercises all of them per
#: direction (chunk-0 forwards go down, chunk-1 forwards come back up).
_CH_DOWN, _CH_UP, _CH_LOOP = 0, 1, 2
_NUM_CH = 3


def _channel_of(src: int, dst: int, S: int) -> int:
    if src == dst:
        return _CH_LOOP
    if (dst - src) % S == 1:
        return _CH_DOWN
    if (src - dst) % S == 1:
        return _CH_UP
    raise ValueError(
        f"placement requires a non-neighbour transfer {src} -> {dst}; the "
        "lock-step engine only implements ring shifts of +-1"
    )


def _channel_tables(plan: SchedulePlan, grid: np.ndarray):
    """Static per-channel send / arrival / input-source tables of a plan.

    Derived from the lowered grid plus the kind's placement map:

    * ``send_f[ch][s, t]`` / ``send_b[ch][s, t]`` — the task device ``s``
      executes at tick ``t`` emits its forward / backward payload into
      channel ``ch``;
    * ``arr_f`` / ``arr_b`` — the matching arrival masks at the receiving
      device (end of the send tick, consumable from ``t + 1``);
    * ``in_f[s, c]`` / ``in_b[s, c]`` — which channel queue the FWD input /
      backward ``dy`` of device ``s``'s chunk ``c`` is popped from (``-1``
      = no queue: the embedding for virtual stage 0, the loss seed for the
      last);
    * ``caps_f`` / ``caps_b`` — exact max in-flight depth per channel
      queue (>= 1 so zero-traffic channels still get a dummy buffer).
    """
    pl = plan.placement
    S, T = grid.shape[:2]
    v = plan.num_virtual
    V = plan.total_virtual_stages
    send_f = np.zeros((_NUM_CH, S, T), bool)
    send_b = np.zeros((_NUM_CH, S, T), bool)
    in_f = np.full((S, v), -1, np.int32)
    in_b = np.full((S, v), -1, np.int32)
    for s in range(S):
        for c in range(v):
            vs = int(pl.vstage_of[s, c])
            if vs > 0:
                in_f[s, c] = _channel_of(int(pl.device_of[vs - 1]), s, S)
            if vs < V - 1:
                in_b[s, c] = _channel_of(int(pl.device_of[vs + 1]), s, S)
    for s in range(S):
        for t in range(T):
            op, _, c, _ = (int(x) for x in grid[s, t])
            if op == int(Op.IDLE):
                continue
            vs = int(pl.vstage_of[s, c])
            if op == int(Op.FWD) and vs < V - 1:
                send_f[_channel_of(s, int(pl.device_of[vs + 1]), S), s, t] = True
            elif op in _BWD_SENDERS and vs > 0:
                send_b[_channel_of(s, int(pl.device_of[vs - 1]), S), s, t] = True
    arr_f = np.zeros_like(send_f)
    arr_b = np.zeros_like(send_b)
    for ch, shift in ((_CH_DOWN, 1), (_CH_UP, -1), (_CH_LOOP, 0)):
        src_of = (np.arange(S) - shift) % S
        arr_f[ch] = send_f[ch][src_of]
        arr_b[ch] = send_b[ch][src_of]
    caps_f, caps_b = [], []
    for ch in range(_NUM_CH):
        cap_f = cap_b = 1
        for s in range(S):
            df = db = 0
            for t in range(T):
                op, _, c, _ = (int(x) for x in grid[s, t])
                # consumption happens during tick t, arrivals at its end
                if op == int(Op.FWD) and in_f[s, c] == ch:
                    df -= 1
                elif op in _BWD_SENDERS and in_b[s, c] == ch:
                    db -= 1
                if arr_f[ch, s, t]:
                    df += 1
                if arr_b[ch, s, t]:
                    db += 1
                cap_f = max(cap_f, df)
                cap_b = max(cap_b, db)
        caps_f.append(cap_f)
        caps_b.append(cap_b)
    return send_f, send_b, arr_f, arr_b, in_f, in_b, caps_f, caps_b


# ---------------------------------------------------------------------------
# Reference executor (single device, Python loop over the tabular grid)
# ---------------------------------------------------------------------------


def reference_pipeline_grads(
    staged: StagedModel, all_params, tokens, labels, plan: SchedulePlan
):
    """Execute any family plan on one device, following the grid exactly.

    tokens/labels: [M, b, T].  ``all_params`` leaves are stacked over the
    ``S * v`` virtual stages in global order.  Returns (mean loss, grads
    pytree like ``all_params``) — bitwise comparable against ``jax.grad``
    of ``staged.full_loss`` up to float reduction order.
    """
    S, M = plan.num_stages, plan.num_microbatches
    v = plan.num_virtual
    V = S * v
    assert V == staged.num_stages, (
        f"staged model has {staged.num_stages} stages; plan needs {V} virtual stages"
    )
    table = plan.lower()
    grid = table.grid
    pl = plan.placement  # kind-owned virtual-stage map (looped, V-shaped, ...)

    def p_of(vs):
        return jax.tree_util.tree_map(lambda p: p[vs], all_params)

    slots: list[dict[tuple[int, int], Any]] = [dict() for _ in range(S)]
    wctx: list[dict[tuple[int, int], Any]] = [dict() for _ in range(S)]
    fwd_wire: list[dict[tuple[int, int], Any]] = [dict() for _ in range(S)]
    bwd_wire: list[dict[tuple[int, int], Any]] = [dict() for _ in range(S)]
    grads = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), all_params
    )
    loss_sum = jnp.zeros((), jnp.float32)

    def add_grad(grads, vs, dparams):
        def upd(g, d):
            return g.at[vs].add(d.astype(jnp.float32))

        return jax.tree_util.tree_map(upd, grads, dparams)

    for t in range(table.num_ticks):
        sends: list[tuple[str, int, tuple[int, int], Any]] = []
        for s in range(S):
            op, mb, chunk, _ = (int(x) for x in grid[s, t])
            if op == int(Op.IDLE):
                continue
            vs = int(pl.vstage_of[s, chunk])
            params_v = p_of(vs)
            key = (mb, chunk)
            if op == int(Op.FWD):
                x = (
                    staged.embed_tokens(params_v, tokens[mb])
                    if vs == 0
                    else fwd_wire[s].pop(key)
                )
                slots[s][key] = x
                if vs < V - 1:
                    y = staged.stage_hidden(params_v, x)
                    nxt = vs + 1
                    sends.append(
                        ("f", int(pl.device_of[nxt]), (mb, int(pl.chunk_of[nxt])), y)
                    )
                # last virtual stage: fwd output feeds its own bwd; recomputed
            elif op in (int(Op.BWD), int(Op.BWD_INPUT)):
                zb = op == int(Op.BWD_INPUT)
                sr = zb and plan.zb_policy[s] == "saved_residual"
                x = slots[s][key] if zb else slots[s].pop(key)
                if vs == V - 1:
                    def loss_fn(p, xx):
                        h = staged.stage_hidden(p, xx)
                        return staged.head_loss(p, h, labels[mb])

                    if sr:
                        # combined vjp over (params, x): keep the pullback —
                        # its residuals ARE the priced saved_residual bytes;
                        # W replays it with no rematerialization
                        loss, vjp = jax.vjp(loss_fn, params_v, x)
                        seed = jnp.ones((), loss.dtype) / M
                        _, dx = vjp(seed)
                        wctx[s][key] = (vjp, seed)
                    elif zb:
                        loss, vjp = jax.vjp(lambda xx: loss_fn(params_v, xx), x)
                        (dx,) = vjp(jnp.ones((), loss.dtype) / M)
                        wctx[s][key] = None  # W recomputes the loss path
                    else:
                        loss, vjp = jax.vjp(loss_fn, params_v, x)
                        dparams, dx = vjp(jnp.ones((), loss.dtype) / M)
                    loss_sum = loss_sum + loss / M
                else:
                    dy = bwd_wire[s].pop(key)
                    if sr:
                        _, vjp = jax.vjp(lambda p, xx: staged.stage_hidden(p, xx), params_v, x)
                        _, dx = vjp(dy)
                        wctx[s][key] = (vjp, dy)
                    elif zb:
                        _, vjp = jax.vjp(lambda xx: staged.stage_hidden(params_v, xx), x)
                        (dx,) = vjp(dy)
                        wctx[s][key] = dy
                    else:
                        _, vjp = jax.vjp(lambda p, xx: staged.stage_hidden(p, xx), params_v, x)
                        dparams, dx = vjp(dy)
                if vs == 0:
                    # gradient into the embedding via the first stage input
                    def embed_fn(p):
                        return staged.embed_tokens(p, tokens[mb])

                    _, evjp = jax.vjp(embed_fn, params_v)
                    (dparams_e,) = evjp(dx)
                    if zb:
                        grads = add_grad(grads, vs, dparams_e)
                    else:
                        dparams = jax.tree_util.tree_map(jnp.add, dparams, dparams_e)
                else:
                    prv = vs - 1
                    sends.append(
                        ("b", int(pl.device_of[prv]), (mb, int(pl.chunk_of[prv])), dx)
                    )
                if not zb:
                    grads = add_grad(grads, vs, dparams)
            else:  # BWD_WEIGHT
                x = slots[s].pop(key)
                ctx = wctx[s].pop(key)
                if plan.zb_policy[s] == "saved_residual":
                    # replay B's saved pullback — no second rematerialization
                    vjp, cot = ctx
                    dparams = vjp(cot)[0]
                elif vs == V - 1:
                    def loss_p(p):
                        h = staged.stage_hidden(p, x)
                        return staged.head_loss(p, h, labels[mb])

                    loss, vjp = jax.vjp(loss_p, params_v)
                    (dparams,) = vjp(jnp.ones((), loss.dtype) / M)
                else:
                    dy = ctx
                    _, vjp = jax.vjp(lambda p: staged.stage_hidden(p, x), params_v)
                    (dparams,) = vjp(dy)
                grads = add_grad(grads, vs, dparams)
        for kind, dst, key, payload in sends:
            (fwd_wire if kind == "f" else bwd_wire)[dst][key] = payload
    return loss_sum, grads


# ---------------------------------------------------------------------------
# Real SPMD engine (shard_map, lock-step ticks, ppermute transfers)
# ---------------------------------------------------------------------------


def stage_mesh(num_stages: int, data: int | None = None) -> Mesh:
    """The engine's mesh over the local devices: a ``stage`` axis, plus a
    ``data`` axis when ``data`` is given, in auto (GSPMD) mode.  The
    engine's placement gathers and the runtime's re-stacking leave layouts
    to the compiler; on JAX's default explicit axes each of them would need
    a hand-written output sharding."""
    shape = (num_stages,) if data is None else (num_stages, data)
    names = ("stage",) if data is None else ("stage", "data")
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(shape))


def make_pipeline_step(
    staged: StagedModel,
    plan: SchedulePlan,
    mesh: Mesh,
    stage_axis: str = "stage",
    data_axis: str | None = None,
):
    """Build ``step(all_params, tokens, labels) -> (loss, grads)``.

    ``all_params`` leaves are stacked [S * v, ...] in global virtual-stage
    order; tokens/labels [M, b, T].  Devices map onto ``stage_axis`` (size
    S); if ``data_axis`` is given the micro-batch dim ``b`` is
    data-parallel over it and grads are psum'd.  The returned function is
    shard_map'd but NOT jitted (callers jit).
    """
    S, M = plan.num_stages, plan.num_microbatches
    v = plan.num_virtual
    V = S * v
    assert V == staged.num_stages, (
        f"staged model has {staged.num_stages} stages; plan needs {V} virtual stages"
    )
    cfg = staged.cfg
    tabular = plan.lower()
    tabular.validate()  # engine ring queues require the FIFO invariants
    grid_np = tabular.grid  # [S, T, 4]
    T_ticks = tabular.num_ticks
    n_slots = int(grid_np[:, :, 3].max()) + 1
    # per-stage BWD_WEIGHT policy: stages with "saved_residual" keep B's
    # combined-vjp residuals in a per-slot f32 row and skip W's remat; with
    # no SR stage the row is zero-width and the traced program is the
    # double-remat one, bit for bit
    sr_stage_np = np.array([p == "saved_residual" for p in plan.zb_policy])
    any_sr = bool(sr_stage_np.any())
    pl = plan.placement
    send_f_np, send_b_np, arr_f_np, arr_b_np, in_f_np, in_b_np, caps_f, caps_b = (
        _channel_tables(plan, grid_np)
    )
    used_f = [bool(send_f_np[ch].any()) for ch in range(_NUM_CH)]
    used_b = [bool(send_b_np[ch].any()) for ch in range(_NUM_CH)]
    placement = _placement_perm(plan)
    inverse_placement = np.argsort(placement)
    perm_of = {
        _CH_DOWN: [(i, (i + 1) % S) for i in range(S)],
        _CH_UP: [(i, (i - 1) % S) for i in range(S)],
    }

    # lax.switch over only the ops this plan actually uses
    present_ops = sorted({int(o) for o in np.unique(grid_np[:, :, 0])})
    branch_of = np.full(int(max(present_ops)) + 1, -1, dtype=np.int32)
    for i, o in enumerate(present_ops):
        branch_of[o] = i

    def device_body(all_params, tokens, labels):
        # all_params leaves [v, ...] (this device's chunks, in chunk order
        # under the plan's placement map)
        params = all_params
        s = jax.lax.axis_index(stage_axis)
        grid = jnp.asarray(grid_np)[s]  # [T_ticks, 4]
        vs_tbl = jnp.asarray(np.asarray(pl.vstage_of, dtype=np.int32))[s]  # [v]
        f_in_tbl = jnp.asarray(in_f_np)[s]  # [v]: FWD input channel (-1 = embed)
        b_in_tbl = jnp.asarray(in_b_np)[s]  # [v]: dy channel (-1 = loss seed)
        sf_rows = [jnp.asarray(send_f_np[ch])[s] for ch in range(_NUM_CH)]
        sb_rows = [jnp.asarray(send_b_np[ch])[s] for ch in range(_NUM_CH)]
        af_rows = [jnp.asarray(arr_f_np[ch])[s] for ch in range(_NUM_CH)]
        ab_rows = [jnp.asarray(arr_b_np[ch])[s] for ch in range(_NUM_CH)]
        b, T = tokens.shape[1], tokens.shape[2]
        d = cfg.d_model
        act = jnp.zeros((n_slots, b, T, d), cfg.dtype)
        wctx = jnp.zeros((n_slots, b, T, d), cfg.dtype)  # zb: stashed dy per slot
        if any_sr:
            # abstract probe (no compute) of the combined-vjp residual
            # layouts; the slot row is padded to the wider of the two bodies
            p_probe = jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype), params
            )
            x_probe = jax.ShapeDtypeStruct((b, T, d), cfg.dtype)
            lbl_probe = jax.ShapeDtypeStruct(labels.shape[1:], labels.dtype)
            mid_layout = probe_residual_layout(
                lambda p, xx: staged.stage_hidden(p, xx), p_probe, x_probe
            )
            last_layout = probe_residual_layout(
                lambda p, xx, lbl: staged.head_loss(
                    p, staged.stage_hidden(p, xx), lbl
                ),
                p_probe,
                x_probe,
                lbl_probe,
            )
            r_width = max(mid_layout.width, last_layout.width)
        else:
            r_width = 0
        res = jnp.zeros((n_slots, r_width), jnp.float32)
        zeros_row = jnp.zeros((r_width,), jnp.float32)
        sr_here = jnp.asarray(sr_stage_np)[s]
        fqs = tuple(
            jnp.zeros((caps_f[ch], b, T, d), cfg.dtype) for ch in range(_NUM_CH)
        )
        bqs = tuple(
            jnp.zeros((caps_b[ch], b, T, d), cfg.dtype) for ch in range(_NUM_CH)
        )
        zeros_bTd = jnp.zeros((b, T, d), cfg.dtype)
        grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        loss_sum = jnp.zeros((), jnp.float32)
        zero_i = jnp.zeros((), jnp.int32)
        fpops = (zero_i, zero_i, zero_i)
        bpops = (zero_i, zero_i, zero_i)
        fpush = [zero_i, zero_i, zero_i]
        bpush = [zero_i, zero_i, zero_i]

        def params_of(chunk):
            return jax.tree_util.tree_map(
                lambda p: jax.lax.dynamic_index_in_dim(p, chunk, 0, keepdims=False),
                params,
            )

        def add_grads(grads, chunk, dparams):
            return jax.tree_util.tree_map(
                lambda g, dp: g.at[chunk].add(dp.astype(jnp.float32)), grads, dparams
            )

        def vstage_flags(chunk):
            vs = vs_tbl[chunk]
            return vs == 0, vs == V - 1

        def pop_queue(qs, pops, caps, code):
            """Select the queue entry ``code`` points at (cheap reads of
            every channel head + a select chain) and advance that
            channel's pop cursor; ``code == -1`` selects nothing."""
            heads = [
                jax.lax.dynamic_index_in_dim(
                    qs[ch], pops[ch] % caps[ch], axis=0, keepdims=False
                )
                for ch in range(_NUM_CH)
            ]
            x = zeros_bTd
            for ch in range(_NUM_CH):
                x = jnp.where(code == ch, heads[ch], x)
            new_pops = tuple(
                pops[ch] + (code == ch).astype(jnp.int32) for ch in range(_NUM_CH)
            )
            return x, new_pops

        def fwd_task(state, mb, chunk, slot):
            act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum = state
            p_c = params_of(chunk)
            is_first, _ = vstage_flags(chunk)
            code = f_in_tbl[chunk]
            x_wire, fpops = pop_queue(fqs, fpops, caps_f, code)
            x_emb = staged.embed_tokens(p_c, tokens[mb])
            x = jnp.where(is_first, x_emb, x_wire)
            act = jax.lax.dynamic_update_index_in_dim(
                act, x.astype(act.dtype), slot, axis=0
            )
            y = staged.stage_hidden(p_c, x)
            return (
                (act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum),
                y.astype(cfg.dtype),
                zeros_bTd,
            )

        def bwd_task(state, mb, chunk, slot):
            """Combined backward (kFkB / interleaved plans)."""
            act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum = state
            p_c = params_of(chunk)
            is_first, is_last = vstage_flags(chunk)
            x = jax.lax.dynamic_index_in_dim(act, slot, axis=0, keepdims=False)
            dy, bpops = pop_queue(bqs, bpops, caps_b, b_in_tbl[chunk])

            def last_branch(_):
                def loss_fn(p, xx):
                    h = staged.stage_hidden(p, xx)
                    return staged.head_loss(p, h, labels[mb])

                loss, vjp = jax.vjp(loss_fn, p_c, x)
                dparams, dx = vjp(jnp.ones((), loss.dtype) / M)
                return loss / M, dparams, dx

            def mid_branch(_):
                _, vjp = jax.vjp(lambda p, xx: staged.stage_hidden(p, xx), p_c, x)
                dparams, dx = vjp(dy.astype(cfg.dtype))
                return jnp.zeros((), jnp.float32), dparams, dx

            dloss, dparams, dx = jax.lax.cond(is_last, last_branch, mid_branch, None)

            def first_branch(dp):
                _, evjp = jax.vjp(lambda p: staged.embed_tokens(p, tokens[mb]), p_c)
                (dpe,) = evjp(dx.astype(cfg.dtype))
                return jax.tree_util.tree_map(jnp.add, dp, dpe)

            dparams = jax.lax.cond(is_first, first_branch, lambda dp: dp, dparams)
            grads = add_grads(grads, chunk, dparams)
            return (
                (act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum + dloss),
                zeros_bTd,
                dx.astype(cfg.dtype),
            )

        def bwd_input_task(state, mb, chunk, slot):
            """Zero-bubble B: input gradient only; stash W's context per slot
            (double-remat: the dy cotangent; saved_residual: the packed
            combined-vjp residual row)."""
            act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum = state
            p_c = params_of(chunk)
            is_first, is_last = vstage_flags(chunk)
            x = jax.lax.dynamic_index_in_dim(act, slot, axis=0, keepdims=False)
            dy, bpops = pop_queue(bqs, bpops, caps_b, b_in_tbl[chunk])

            def dr_last(_):
                def loss_fn(xx):
                    h = staged.stage_hidden(p_c, xx)
                    return staged.head_loss(p_c, h, labels[mb])

                loss, vjp = jax.vjp(loss_fn, x)
                (dx,) = vjp(jnp.ones((), loss.dtype) / M)
                return loss / M, dx, zeros_bTd, zeros_row  # W recomputes

            def dr_mid(_):
                _, vjp = jax.vjp(lambda xx: staged.stage_hidden(p_c, xx), x)
                (dx,) = vjp(dy.astype(cfg.dtype))
                return jnp.zeros((), jnp.float32), dx, dy.astype(cfg.dtype), zeros_row

            if any_sr:
                # combined vjp over (params, x): the weight-gradient half is
                # dead here (it is W's job) and XLA removes it; the
                # pullback's residual leaves ride the slot row instead
                def sr_last(_):
                    def loss_fn(p, xx):
                        h = staged.stage_hidden(p, xx)
                        return staged.head_loss(p, h, labels[mb])

                    loss, vjp = jax.vjp(loss_fn, p_c, x)
                    _, dx = vjp(jnp.ones((), loss.dtype) / M)
                    row = pack_residuals(vjp, last_layout, r_width, params=p_c)
                    return loss / M, dx, zeros_bTd, row

                def sr_mid(_):
                    _, vjp = jax.vjp(lambda p, xx: staged.stage_hidden(p, xx), p_c, x)
                    _, dx = vjp(dy.astype(cfg.dtype))
                    row = pack_residuals(vjp, mid_layout, r_width, params=p_c)
                    return jnp.zeros((), jnp.float32), dx, dy.astype(cfg.dtype), row

                def last_branch(_):
                    return jax.lax.cond(sr_here, sr_last, dr_last, None)

                def mid_branch(_):
                    return jax.lax.cond(sr_here, sr_mid, dr_mid, None)
            else:
                last_branch, mid_branch = dr_last, dr_mid

            dloss, dx, dy_keep, res_row = jax.lax.cond(
                is_last, last_branch, mid_branch, None
            )
            wctx = jax.lax.dynamic_update_index_in_dim(wctx, dy_keep, slot, axis=0)
            res = jax.lax.dynamic_update_index_in_dim(res, res_row, slot, axis=0)

            def first_branch(g):
                _, evjp = jax.vjp(lambda p: staged.embed_tokens(p, tokens[mb]), p_c)
                (dpe,) = evjp(dx.astype(cfg.dtype))
                return add_grads(g, chunk, dpe)

            grads = jax.lax.cond(is_first, first_branch, lambda g: g, grads)
            return (
                (act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum + dloss),
                zeros_bTd,
                dx.astype(cfg.dtype),
            )

        def bwd_weight_task(state, mb, chunk, slot):
            """Zero-bubble W: weight gradients — via a second
            rematerialization (double-remat) or by replaying B's saved
            pullback from the slot's residual row (saved_residual)."""
            act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum = state
            p_c = params_of(chunk)
            _, is_last = vstage_flags(chunk)
            x = jax.lax.dynamic_index_in_dim(act, slot, axis=0, keepdims=False)
            dy = jax.lax.dynamic_index_in_dim(wctx, slot, axis=0, keepdims=False)

            def dr_last(_):
                def loss_fn(p):
                    h = staged.stage_hidden(p, x)
                    return staged.head_loss(p, h, labels[mb])

                loss, vjp = jax.vjp(loss_fn, p_c)
                (dparams,) = vjp(jnp.ones((), loss.dtype) / M)
                return dparams

            def dr_mid(_):
                _, vjp = jax.vjp(lambda p: staged.stage_hidden(p, x), p_c)
                (dparams,) = vjp(dy.astype(cfg.dtype))
                return dparams

            if any_sr:
                row = jax.lax.dynamic_index_in_dim(res, slot, axis=0, keepdims=False)

                # the dummy vjp traces give the pullback's STRUCTURE only —
                # their forward compute is dead once the saved leaves are
                # substituted, so XLA eliminates it (no rematerialization)
                def sr_last(_):
                    def loss_fn(p, xx):
                        h = staged.stage_hidden(p, xx)
                        return staged.head_loss(p, h, labels[mb])

                    loss_dead, vjp_dummy = jax.vjp(loss_fn, p_c, x)
                    vjp_saved = rebuild_vjp(vjp_dummy, last_layout, row, params=p_c)
                    dparams, _ = vjp_saved(jnp.ones((), loss_dead.dtype) / M)
                    return dparams

                def sr_mid(_):
                    _, vjp_dummy = jax.vjp(
                        lambda p, xx: staged.stage_hidden(p, xx), p_c, x
                    )
                    vjp_saved = rebuild_vjp(vjp_dummy, mid_layout, row, params=p_c)
                    dparams, _ = vjp_saved(dy.astype(cfg.dtype))
                    return dparams

                def last_branch(_):
                    return jax.lax.cond(sr_here, sr_last, dr_last, None)

                def mid_branch(_):
                    return jax.lax.cond(sr_here, sr_mid, dr_mid, None)
            else:
                last_branch, mid_branch = dr_last, dr_mid

            dparams = jax.lax.cond(is_last, last_branch, mid_branch, None)
            grads = add_grads(grads, chunk, dparams)
            return (
                (act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum),
                zeros_bTd,
                zeros_bTd,
            )

        def idle_task(state, mb, chunk, slot):
            return state, zeros_bTd, zeros_bTd

        all_branches = {
            int(Op.IDLE): idle_task,
            int(Op.FWD): fwd_task,
            int(Op.BWD): bwd_task,
            int(Op.BWD_INPUT): bwd_input_task,
            int(Op.BWD_WEIGHT): bwd_weight_task,
        }
        branches = [all_branches[o] for o in present_ops]
        branch_lut = jnp.asarray(branch_of)

        def push(qs, pushes, caps, rows, recvs, t):
            """Static-schedule arrivals into the per-channel ring queues.
            The write must be CONDITIONAL — when a ring is exactly full,
            the push cursor aliases the oldest unconsumed entry, and an
            unconditional write would clobber it."""
            out = list(qs)
            for ch, recv in recvs.items():
                idx = pushes[ch] % caps[ch]
                cur = jax.lax.dynamic_index_in_dim(
                    out[ch], idx, axis=0, keepdims=False
                )
                out[ch] = jax.lax.dynamic_update_index_in_dim(
                    out[ch], jnp.where(rows[ch][t], recv, cur), idx, axis=0
                )
                pushes[ch] = pushes[ch] + rows[ch][t].astype(jnp.int32)
            return tuple(out)

        for t in range(T_ticks):
            op, mb, chunk, slot = grid[t, 0], grid[t, 1], grid[t, 2], grid[t, 3]
            state = (act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum)
            state, send_f, send_b = jax.lax.switch(
                branch_lut[op], branches, state, mb, chunk, slot
            )
            act, wctx, res, fqs, fpops, bqs, bpops, grads, loss_sum = state
            # lock-step transfers on whichever channels the plan uses:
            # activations and gradients each ride ring shifts of +-1 (flat
            # chains and Megatron rings use one direction each; ZB-V uses
            # both) plus the ppermute-free LOOP channel for intra-device
            # turns.  Payloads are masked by the static send tables, so a
            # tick with no send on a channel moves zeros (and the arrival
            # mask ignores them).
            recvs_f, recvs_b = {}, {}
            for ch in (_CH_DOWN, _CH_UP):
                if used_f[ch]:
                    payload = jnp.where(sf_rows[ch][t], send_f, zeros_bTd)
                    recvs_f[ch] = jax.lax.ppermute(payload, stage_axis, perm_of[ch])
                if used_b[ch]:
                    payload = jnp.where(sb_rows[ch][t], send_b, zeros_bTd)
                    recvs_b[ch] = jax.lax.ppermute(payload, stage_axis, perm_of[ch])
            if used_f[_CH_LOOP]:
                recvs_f[_CH_LOOP] = jnp.where(sf_rows[_CH_LOOP][t], send_f, zeros_bTd)
            if used_b[_CH_LOOP]:
                recvs_b[_CH_LOOP] = jnp.where(sb_rows[_CH_LOOP][t], send_b, zeros_bTd)
            fqs = push(fqs, fpush, caps_f, af_rows, recvs_f, t)
            bqs = push(bqs, bpush, caps_b, ab_rows, recvs_b, t)

        # replicated leaves (embed, final_norm) accumulate their one non-zero
        # contribution per virtual stage; stage-local leaves (blocks) stay
        # local.  Replicated rows are broadcast back across local chunks so
        # every [v, ...] row carries the global sum (as in the v == 1 case).
        def reduce_replicated(path, g):
            top = path[0].key if hasattr(path[0], "key") else str(path[0])
            if top in ("embed", "final_norm"):
                total = jax.lax.psum(g.sum(axis=0), stage_axis)
                return jnp.broadcast_to(total[None], g.shape)
            return g

        grads = jax.tree_util.tree_map_with_path(reduce_replicated, grads)
        loss = jax.lax.psum(loss_sum, stage_axis)
        if data_axis is not None:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, data_axis), grads
            )
            loss = jax.lax.pmean(loss, data_axis)
        return loss, grads

    param_spec = P(stage_axis)
    data_spec = P(None, data_axis) if data_axis else P()
    sharded = jax.shard_map(
        device_body,
        mesh=mesh,
        in_specs=(param_spec, data_spec, data_spec),
        out_specs=(P(), param_spec),
        check_vma=False,
    )

    if v == 1:
        return sharded  # placement is the identity — no re-ordering needed

    def step(all_params, tokens, labels):
        # global virtual-stage order -> looped device placement, and back
        placed = jax.tree_util.tree_map(lambda p: p[placement], all_params)
        loss, grads = sharded(placed, tokens, labels)
        return loss, jax.tree_util.tree_map(lambda g: g[inverse_placement], grads)

    return step


def pipeline_train_step(staged, plan, mesh, optimizer, **kw):
    """Full train step: engine grads -> optimizer update (jit-ready)."""
    engine = make_pipeline_step(staged, plan, mesh, **kw)

    def step(state, tokens, labels):
        loss, grads = engine(state.params, tokens, labels)
        new_params, new_opt, metrics = optimizer.update(
            state.params, grads, state.opt_state
        )
        from repro.training import TrainState

        return (
            TrainState(step=state.step + 1, params=new_params, opt_state=new_opt),
            {"loss": loss, **metrics},
        )

    return step
