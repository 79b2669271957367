"""Differential conformance harness for the WHOLE schedule family.

One oracle, every kind: for each (kind, k, num_virtual, extra_warmup, S, M)
cell of the family matrix — ``extra_warmup`` may be a scalar OR a per-stage
vector ``w[s]`` — the same battery runs —

* the plan validates and lowers to a dependency-valid :class:`TabularPlan`,
* every directed link is FIFO-consistent (the i-th send is the i-th recv —
  what the engine's static ring queues structurally require),
* exact per-device liveness never exceeds the closed-form memory-model
  prediction (:func:`repro.core.predicted_peak_live`), with equality for
  the kinds whose builders carry a hard guarantee: kFkB and ZB-H1 hit the
  1F1B bound, uniform-w ZB-H2 hits 1F1B + w (clamped at the group count;
  non-uniform vectors are upstream-limited, so the prediction is a bound),
  plain interleaved hits Megatron's warmup depth + 1,
* total and per-op task counts conserve (F/B[/W] each exactly M per chunk),
* slot assignment is liveness-exact (slots form a gap-free prefix),
* the discrete-event simulator executes the cell to completion under
  NON-UNIFORM per-stage costs (skewed F, B/W split and optimizer epilogue)
  with exact per-stage busy-time conservation — heterogeneous pricing must
  not depend on the schedule kind.

This file replaces the per-kind ad-hoc structure checks that used to live
in ``test_schedule_family.py`` (kind-specific *semantic* claims — memory
pricing, degenerate aliases, divisibility guards — stay there).  A
hypothesis sweep widens the same oracle to random family cells when
``hypothesis`` is installed (via the ``tests/_hyp.py`` shim).
"""

import pytest
from _hyp import given, settings, st  # hypothesis optional: property tests skip cleanly

from repro.core import predicted_peak_live
from repro.core.kinds import ScheduleSpec, get_kind, registered_kinds, warmup_kinds
from repro.core.network import StableTrace, uniform_network
from repro.core.schedule import (
    Op,
    make_plan,
    normalize_warmup,
    peak_live_activations,
)
from repro.core.simulator import simulate_plan
from repro.core.taskgraph import StageCosts

# ---------------------------------------------------------------------------
# The family grid: every kind x k x num_virtual x (S, M) cell that satisfies
# the kind's divisibility constraints (k | M everywhere so the closed-form
# peak predictions are exact, S | M/k for the Megatron-interleaved kinds).
# The kinds and their axes come from the REGISTRY — a newly registered kind
# grows its own cells from its capability flags, and the coverage gates
# below fail closed if it somehow contributes none.
# ---------------------------------------------------------------------------

_SHAPES = [(2, 4), (2, 8), (4, 8), (4, 16), (3, 12)]
_KS = (1, 2, 4)
_VS = (2, 3)
_WS = (1, 2)
#: heterogeneous warmup vectors per stage count — the vector-w cells
_W_VECS = {
    2: ((0, 1), (2, 1)),
    3: ((1, 0, 2), (0, 2, 1)),
    4: ((3, 2, 1, 0), (0, 1, 0, 2)),
}

#: builders whose peak-live contract is an equality, not just a bound —
#: derived from the registry's peak_is_exact flag, never hand-listed
_EXACT_PEAK_KINDS = tuple(
    k for k in registered_kinds() if get_kind(k).peak_is_exact
)


def _kind_cells(kind, S, M, k):
    """One registered kind's conformance cells at a given (S, M, k) —
    derived from its capability flags."""
    spec = get_kind(kind)
    G = M // k
    if spec.needs_group_multiple_of_stages and G % S:
        return
    for v in spec.virtual_axis(_VS):
        if not spec.requires_warmup:
            yield (kind, k, v, 0, S, M)
        if spec.supports_extra_warmup:
            if G < 2:
                continue  # no warmup headroom: the w axis degenerates
            scalar_ws = _WS if spec.requires_warmup else _WS[:1]
            for w in scalar_ws:
                yield (kind, k, v, w, S, M)
            vecs = _W_VECS[S] if v == 1 else _W_VECS[S][:1]
            for w_vec in vecs:
                yield (kind, k, v, w_vec, S, M)


def _family_cells():
    cells = []
    for S, M in _SHAPES:
        for k in _KS:
            if M % k:
                continue
            for kind in registered_kinds():
                cells.extend(_kind_cells(kind, S, M, k))
    return cells


CELLS = _family_cells()


def _ids(cell):
    kind, k, v, w, S, M = cell
    wtag = "x".join(map(str, w)) if isinstance(w, tuple) else str(w)
    return f"{kind}-k{k}-v{v}-w{wtag}-S{S}-M{M}"


def _skewed_costs(S):
    """Deterministic non-uniform per-stage costs: F, an uneven B/W split and
    a per-stage optimizer epilogue all vary across stages."""
    fwd = [1.0 + 0.25 * s for s in range(S)]
    bwd_i = [0.6 + 0.2 * ((s + 1) % S) for s in range(S)]
    bwd_w = [1.4 + 0.3 * ((S - s) % S) for s in range(S)]
    return StageCosts(
        fwd_time=fwd,
        bwd_time=[bi + bw for bi, bw in zip(bwd_i, bwd_w)],
        fwd_bytes=[1.0 + 0.5 * s for s in range(S)],
        bwd_bytes=[2.0 - 0.1 * s for s in range(S)],
        optimizer_time=[0.1 * (s + 1) for s in range(S)],
        bwd_input_time=bwd_i,
        bwd_weight_time=bwd_w,
    )


def _conformance(kind, k, v, w, S, M):
    """The single differential oracle every family member must pass."""
    plan = make_plan(S, M, spec=ScheduleSpec(kind=kind, k=k, num_virtual=v, extra_warmup=w))
    plan.validate()
    table = plan.lower()
    table.validate()  # dependency validity + per-link FIFO + stream order
    w_vec = normalize_warmup(w, S)
    uniform_w = len(set(w_vec)) == 1

    # -- FIFO send/recv order on every TabularPlan edge ---------------------
    links = {}
    for e in table.edges:
        assert e.send_tick < e.recv_tick
        links.setdefault((e.src_stage, e.dst_stage, e.is_forward), []).append(e)
    for es in links.values():
        es.sort(key=lambda e: e.send_tick)
        recvs = [e.recv_tick for e in es]
        assert recvs == sorted(recvs), "link recv order diverges from send order"

    # -- op-count conservation ---------------------------------------------
    zb = get_kind(kind).has_split_backward
    per_device = (3 if zb else 2) * M * v
    busy = int((table.grid[:, :, 0] != int(Op.IDLE)).sum())
    assert busy == per_device * S == sum(len(o) for o in plan.orders)
    for s, order in enumerate(plan.orders):
        for c in range(v):
            ops_expected = [Op.FWD, Op.BWD_INPUT, Op.BWD_WEIGHT] if zb else [Op.FWD, Op.BWD]
            for op in ops_expected:
                mbs = [t.mb for t in order if t.op == op and t.chunk == c]
                assert mbs == sorted(mbs), f"{op} stream not FIFO at device {s}"
                assert set(mbs) == set(range(M)), f"device {s} chunk {c}: {op} incomplete"

    # -- edge-count conservation -------------------------------------------
    # every CROSS-device virtual-stage hop carries one F and one B per
    # micro-batch; same-device hops (ZB-V's turn) ride the device order
    V = S * v
    pl = plan.placement
    n_cross = sum(
        1 for u in range(V - 1) if pl.device_of[u] != pl.device_of[u + 1]
    )
    n_fwd = sum(1 for e in table.edges if e.is_forward)
    n_bwd = len(table.edges) - n_fwd
    assert n_fwd == M * n_cross
    assert n_bwd == M * n_cross

    # -- memory: exact liveness vs the closed-form model prediction --------
    peaks = peak_live_activations(plan)
    predicted = predicted_peak_live(plan)
    assert all(1 <= p <= pr for p, pr in zip(peaks, predicted)), (peaks, predicted)
    if kind in _EXACT_PEAK_KINDS and uniform_w:
        assert peaks == predicted, (kind, peaks, predicted)
    if kind == "zb_h2":
        h1 = predicted_peak_live(make_plan(S, M, spec=ScheduleSpec(kind="zb_h1", k=k)))
        G = M // k
        bound = [min(p + w_vec[s] * k, G * k) for s, p in enumerate(h1)]
        if uniform_w:
            assert peaks == bound  # 1F1B + w, clamped
        else:
            # a stage can only go as deep as upstream feeds it: bound, and
            # never below H1 (the vector can only ADD warmup depth)
            assert all(a <= b for a, b in zip(peaks, bound)), (peaks, bound)
            assert all(a >= p for a, p in zip(peaks, h1)), (peaks, h1)
    if kind == "interleaved_zb":
        plain = peak_live_activations(make_plan(S, M, spec=ScheduleSpec(kind="interleaved", k=k, num_virtual=v)))
        bound = [p + w_vec[s] * k for s, p in enumerate(plain)]
        assert all(p <= q for p, q in zip(peaks, bound))  # plain + w[s], at most

    # -- slots are a liveness-exact, gap-free prefix ------------------------
    for s, order in enumerate(plan.orders):
        slots_used = {t.slot for t in order if t.op == Op.FWD}
        assert slots_used == set(range(peaks[s]))

    # -- heterogeneous-cost execution: the simulator completes the cell with
    # exact per-stage busy-time conservation under skewed costs ------------
    costs = _skewed_costs(S)
    res = simulate_plan(plan, costs, uniform_network(S, lambda: StableTrace(4.0)))
    assert len(res.task_finish) == sum(len(o) for o in plan.orders)
    for s in range(S):
        if zb:
            expected = M * v * (
                costs.fwd_time[s] + costs.bwd_input_time[s] + costs.bwd_weight_time[s]
            ) / v
        else:
            expected = M * v * (costs.fwd_time[s] + costs.bwd_time[s]) / v
        assert res.busy_time[s] == pytest.approx(expected, rel=1e-9)
    last_finish = max(res.task_finish.values())
    assert res.pipeline_length == pytest.approx(
        max(
            max(res.task_finish[t.key()] for t in plan.orders[s])
            + costs.optimizer_time[s]
            for s in range(S)
        ),
        rel=1e-12,
    )
    assert res.pipeline_length >= last_finish


@pytest.mark.parametrize("cell", CELLS, ids=_ids)
def test_family_conformance(cell):
    _conformance(*cell)


def test_grid_covers_every_plan_kind():
    """Tier-1 gate, auto-derived from the REGISTRY: every registered kind
    must contribute conformance cells — adding a kind without grid
    coverage fails here before it can ship."""
    assert {c[0] for c in CELLS} == set(registered_kinds())


def test_grid_covers_vector_warmup():
    """...and the heterogeneous (non-uniform w[s]) cells can't drop out
    either — for EVERY warmup-capable kind the registry declares."""
    vec_kinds = {
        c[0] for c in CELLS if isinstance(c[3], tuple) and len(set(c[3])) > 1
    }
    assert vec_kinds == set(warmup_kinds())


@given(
    st.sampled_from(registered_kinds()),
    st.integers(0, 2).map(lambda e: 2**e),  # k
    st.integers(2, 3),  # v (interleaved kinds only)
    st.lists(st.integers(0, 3), min_size=5, max_size=5),  # w[s] (warmup kinds)
    st.integers(2, 5),  # S
    st.integers(1, 4),  # M = S * k * mult for divisibility
)
@settings(max_examples=40, deadline=None)
def test_family_conformance_hypothesis(kind, k, v, w, S, mult):
    """Random family cells — including random per-stage warmup vectors —
    through the same oracle (skips without hypothesis)."""
    spec = get_kind(kind)
    M = S * k * mult  # guarantees k | M and S | (M / k)
    if spec.requires_warmup and M // k < 2:
        M *= 2
    w_vec = tuple(w[:S])
    if spec.requires_warmup and max(w_vec) == 0:
        w_vec = w_vec[:-1] + (1,)
    _conformance(
        kind,
        k,
        spec.virtual_axis((v,))[0],
        w_vec if spec.supports_extra_warmup else 0,
        S,
        M,
    )
