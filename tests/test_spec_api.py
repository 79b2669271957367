"""The ScheduleSpec / SearchSpace / kind-registry API, proven.

Four suites:

* **One coordinate currency** — every plan is built from one
  ``ScheduleSpec``; ``plan.spec`` comes back normalized (aliases folded,
  per-stage ``w`` and ``zb_policy`` vectors) and rebuilding from it gives
  the same lowered ``TabularPlan`` digest.  The paper's positional
  ``make_plan(S, M, k, micro_batch_size=b)`` is bit-identical to its kfkb
  ``spec=``, and ``enumerate_candidates`` without ``space=`` is the
  paper's kfkb-only search.
* **Fail-closed registry** — an unregistered kind is a loud ``ValueError``
  naming the registered kinds, everywhere a kind string enters the system.
* **No string dispatch** — the tier-1 gate: no module under
  ``src/repro`` outside ``core/kinds.py`` / ``core/schedule.py`` may
  dispatch on schedule-kind strings (the CI lint job runs the same scan;
  this test makes it bite locally).
* **ZB-V acceptance** — the first registry-only family member shows the
  controllable-memory trade: peak live strictly below the equal-(S, M, k)
  plain-interleaved plan's, makespan no worse than 1F1B on the preemption
  traces, and full participation in candidate search, tuning records and
  the compile-cache key through the one ScheduleSpec currency.
"""

import hashlib
import os
import re
import warnings as _warnings

import pytest

from repro.core import (
    MemoryModel,
    ScheduleSpec,
    SearchSpace,
    StageCosts,
    enumerate_candidates,
    get_kind,
    known_kinds,
    make_plan,
    registered_kinds,
    simulate_plan,
    uniform_network,
)
from repro.core.network import PeriodicPreemptionTrace
from repro.core.schedule import peak_live_activations

_SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


def _digest(plan) -> str:
    table = plan.lower()
    edges = tuple(
        sorted(
            (e.src_stage, e.dst_stage, int(e.op), e.mb, e.src_chunk,
             e.dst_chunk, e.send_tick, e.recv_tick)
            for e in table.edges
        )
    )
    return hashlib.sha1(table.grid.tobytes() + repr(edges).encode()).hexdigest()


def _mm(S):
    return MemoryModel.uniform(
        num_stages=S, seq_len=64, param_bytes=1e6, optimizer_bytes=2e6,
        grad_bytes=1e6, stage_input_bytes_per_token=512.0,
        layer_act_bytes_per_token=64.0, num_layers_per_stage=2,
    )


# ---------------------------------------------------------------------------
# One coordinate currency: ScheduleSpec in, normalized ScheduleSpec out
# ---------------------------------------------------------------------------

_S, _M = 4, 8
_W0, _DR = (0,) * _S, ("double_remat",) * _S

#: (spec, its normalized form at S=4, M=8) — one per family coordinate
_SPECS = [
    (ScheduleSpec(), ScheduleSpec("kfkb", 1, 1, _W0, 1, _DR)),
    (ScheduleSpec(k=2, micro_batch_size=2), ScheduleSpec("kfkb", 2, 1, _W0, 2, _DR)),
    (ScheduleSpec(kind="1f1b"), ScheduleSpec("kfkb", 1, 1, _W0, 1, _DR)),
    (ScheduleSpec(kind="gpipe"), ScheduleSpec("kfkb", _M, 1, _W0, 1, _DR)),
    (ScheduleSpec(kind="zb_h1", k=2), ScheduleSpec("zb_h1", 2, 1, _W0, 1, _DR)),
    (ScheduleSpec(kind="zb_h2", extra_warmup=2),
     ScheduleSpec("zb_h2", 1, 1, (2,) * _S, 1, _DR)),
    (ScheduleSpec(kind="zb_h2", extra_warmup=(0, 1, 0, 2)),
     ScheduleSpec("zb_h2", 1, 1, (0, 1, 0, 2), 1, _DR)),
    (ScheduleSpec(kind="interleaved", k=2, num_virtual=2),
     ScheduleSpec("interleaved", 2, 2, _W0, 1, _DR)),
    (ScheduleSpec(kind="interleaved_zb", num_virtual=2, extra_warmup=1),
     ScheduleSpec("interleaved_zb", 1, 2, (1,) * _S, 1, _DR)),
    (ScheduleSpec(kind="zbv"), ScheduleSpec("zbv", 1, 2, _W0, 1, _DR)),
    (ScheduleSpec(kind="zbv", k=2, extra_warmup=(1, 0, 2, 1)),
     ScheduleSpec("zbv", 2, 2, (1, 0, 2, 1), 1, _DR)),
]
_ROUNDTRIP_IDS = [s.kind + f"-k{s.k}" for s, _ in _SPECS]
#: alias folding: gpipe forces k = M whatever k the spec carries
_SPECS.append((ScheduleSpec(kind="gpipe", k=2), ScheduleSpec("kfkb", _M, 1, _W0, 1, _DR)))
_ROUNDTRIP_IDS.append("gpipe-folds-to-kfkb-kM")


@pytest.mark.parametrize("spec,normalized", _SPECS, ids=_ROUNDTRIP_IDS)
def test_plan_spec_roundtrip(spec, normalized):
    """plan.spec is normalized (aliases folded, w and zb_policy vectors)
    and rebuilding from it reproduces the plan, bit for bit (grid + exact
    edge list) and by name."""
    plan = make_plan(_S, _M, spec=spec)
    assert plan.spec == normalized
    again = make_plan(_S, _M, spec=plan.spec)
    assert _digest(again) == _digest(plan)
    assert again.name == plan.name
    assert again.spec == plan.spec


def test_make_plan_rejects_mixing_spec_and_kwargs():
    with pytest.raises(ValueError, match="not both"):
        make_plan(4, 8, 2, spec=ScheduleSpec(kind="zb_h1"))
    with pytest.raises(ValueError, match="not both"):
        make_plan(4, 8, micro_batch_size=2, spec=ScheduleSpec(kind="zb_h1"))


#: the (S, M, k, b) the benchmark's set-up builds: the one-stage training
#: cell (1 stage, 8 micro-batches of 1, 1F1B) and the chat serving cell
#: (4 stages, 4 groups of 8 slots, 2F2B)
_PAPER_FORMS = [(1, 8, 1, 1), (4, 4, 2, 8)]


@pytest.mark.parametrize(
    "S,M,k,b", _PAPER_FORMS, ids=[f"S{S}-M{M}-k{k}-b{b}" for S, M, k, b in _PAPER_FORMS]
)
def test_make_plan_paper_form_equals_kfkb_spec(S, M, k, b):
    """The paper's positional ``(S, M, k, micro_batch_size=b)`` form is
    shorthand for the kfkb ``spec=``: the same plan bit for bit, the same
    name and coordinates, and neither call warns."""
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        paper = make_plan(S, M, k, micro_batch_size=b)
        spec = make_plan(S, M, spec=ScheduleSpec(kind="kfkb", k=k, micro_batch_size=b))
    assert _digest(paper) == _digest(spec)
    assert paper.name == spec.name == f"{k}F{k}B(b={b})"
    assert paper.spec == spec.spec


def test_enumerate_candidates_default_space_is_the_papers():
    """No ``space=`` is ``SearchSpace(kinds=("kfkb",))``: the same
    candidates in the same order, with the same names, coordinates,
    lowered digests and memory pricing."""
    S, B = 4, 32
    mm = _mm(S)
    for limit in (1e8, 1e9):
        default = enumerate_candidates(S, B, mm, limit)
        papers = enumerate_candidates(S, B, mm, limit, space=SearchSpace(kinds=("kfkb",)))
        assert default, limit
        assert [c.name for c in default] == [c.name for c in papers]
        assert [c.spec for c in default] == [c.spec for c in papers]
        assert [c.est_peak_bytes for c in default] == [c.est_peak_bytes for c in papers]
        assert [_digest(c.plan) for c in default] == [_digest(c.plan) for c in papers]
        assert {c.kind for c in default} == {"kfkb"}


def test_candidate_record_cache_share_one_spec_currency():
    """Candidate.spec == TuningRecord.chosen_spec == the ScheduleSpec
    inside the compile-cache key: one currency end to end."""
    from repro.core import AutoTuner, NetworkProfiler, StableTrace
    from repro.runtime.compile_cache import CompiledStepCache

    S, B = 4, 32
    cands = enumerate_candidates(
        S, B, _mm(S), 1e8, space=SearchSpace(kinds=("kfkb", "zb_h1", "zbv"), max_k=2),
    )
    assert any(c.kind == "zbv" for c in cands)  # the registry-only member searches
    costs_for = lambda c: StageCosts.uniform(S, 0.1, act_bytes=1.0)  # noqa: E731
    net = uniform_network(S, lambda: StableTrace(100.0))
    tuner = AutoTuner(cands, costs_for, NetworkProfiler(net))
    rec = tuner.tune(now=0.0)
    winner = next(c for c in cands if c.name == rec.chosen)
    assert rec.chosen_spec == winner.spec == winner.plan.spec
    key = CompiledStepCache.plan_key(winner.table)
    assert winner.spec in key


# ---------------------------------------------------------------------------
# Fail-closed registry
# ---------------------------------------------------------------------------


def test_unknown_kind_fails_closed_everywhere():
    """An unregistered kind raises a ValueError NAMING the registered
    kinds — in the registry lookup, in make_plan, and in the candidate
    search; it is never silently skipped."""
    for call in (
        lambda: get_kind("zb_h3"),
        lambda: make_plan(4, 8, spec=ScheduleSpec(kind="zb_h3")),
    ):
        with pytest.raises(ValueError, match="registered kinds") as ei:
            call()
        for kind in registered_kinds():
            assert kind in str(ei.value)
    with pytest.raises(ValueError, match="unknown schedule kind"):
        enumerate_candidates(4, 32, _mm(4), 1e8, space=SearchSpace(kinds=("kfkb", "zb_h3")))


def test_known_kinds_covers_registry_and_aliases():
    """The candidate search accepts exactly the registry + aliases — a
    registered kind can never be rejected as unknown (a hardcoded tuple of
    kinds plus aliases would drift by construction)."""
    ks = known_kinds()
    assert set(registered_kinds()) <= set(ks)
    assert {"1f1b", "gpipe"} <= set(ks)
    # every known name is accepted by the search (smoke: no ValueError)
    enumerate_candidates(4, 16, _mm(4), 1e9, space=SearchSpace(kinds=ks, max_k=1))


def test_duplicate_registration_rejected():
    from repro.core import KindSpec, register_kind

    with pytest.raises(ValueError, match="already registered"):
        register_kind(
            KindSpec(
                name="kfkb",
                build_orders=lambda *a: [],
                peak_live_groups=lambda *a: [],
            )
        )


def test_capability_flags_gate_coordinates():
    """Coordinate validation is capability-driven: virtual degrees only on
    virtual-capable kinds, ZB-V pinned to 2 chunks, warmup only on
    warmup-capable kinds."""
    with pytest.raises(ValueError, match="interleaved kind"):
        make_plan(4, 8, spec=ScheduleSpec(kind="zb_h1", num_virtual=2))
    with pytest.raises(ValueError, match="exactly 2 chunks"):
        make_plan(4, 8, spec=ScheduleSpec(kind="zbv", num_virtual=3))
    assert make_plan(4, 8, spec=ScheduleSpec(kind="zbv")).num_virtual == 2  # coerced default


# ---------------------------------------------------------------------------
# The grep gate: no kind-string dispatch outside kinds.py / schedule.py
# ---------------------------------------------------------------------------

_ALLOWED = {os.path.join("core", "kinds.py"), os.path.join("core", "schedule.py")}
#: schedule-kind string dispatch (`plan.kind == "zb_h2"`-style ladders) or
#: membership tests against a literal tuple or an upper-case kind-set constant
_DISPATCH = [
    re.compile(
        r"kind\s*(?:==|!=)\s*[\"']"
        r"(?:kfkb|zb_h1|zb_h2|interleaved|interleaved_zb|zbv|1f1b|gpipe)[\"']"
    ),
    re.compile(r"kind\s+(?:not\s+)?in\s+\("),
    re.compile(r"kind\s+(?:not\s+)?in\s+[A-Z_]*KINDS\b"),
]


def test_no_kind_string_dispatch_outside_registry():
    """The redesign's lock: every schedule-kind decision outside the
    registry and the schedule module itself must go through KindSpec
    capability flags.  New ``kind == "..."`` ladders fail here (and the CI
    lint job runs the same scan)."""
    offenders = []
    for root, _, files in os.walk(_SRC):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            rel = os.path.relpath(path, _SRC)
            if rel in _ALLOWED:
                continue
            with open(path) as fh:
                for i, line in enumerate(fh, 1):
                    if any(p.search(line) for p in _DISPATCH):
                        offenders.append(f"{rel}:{i}: {line.strip()}")
    assert not offenders, (
        "schedule-kind string dispatch outside core/kinds.py + "
        "core/schedule.py:\n" + "\n".join(offenders)
    )


# ---------------------------------------------------------------------------
# ZB-V: the registry-only member's acceptance gates
# ---------------------------------------------------------------------------


def test_zbv_peak_live_below_plain_interleaved():
    """The controllable-memory trade, memory half: at equal (S, M, k) the
    V placement's worst-device peak live count is strictly below plain
    interleaved's (whose looped placement forces the deep Megatron
    warmup) — and exactly the registered closed-form row prices it."""
    from repro.core import predicted_peak_live

    for S, M, k in ((4, 16, 1), (4, 32, 2), (8, 32, 1), (3, 12, 1)):
        zbv = make_plan(S, M, spec=ScheduleSpec(kind="zbv", k=k))
        il = make_plan(S, M, spec=ScheduleSpec(kind="interleaved", k=k, num_virtual=2))
        assert max(peak_live_activations(zbv)) < max(peak_live_activations(il))
        assert all(
            p <= pr
            for p, pr in zip(peak_live_activations(zbv), predicted_peak_live(zbv))
        )


def test_zbv_makespan_no_worse_than_1f1b_under_preemption():
    """The controllable-memory trade, time half: on the preemption traces
    ZB-V's simulated makespan is no worse than 1F1B's (the W filler +
    V-shaped turn absorb the stalls), despite holding ~half the
    plain-interleaved peak."""
    for S, M in ((4, 16), (8, 32), (3, 12)):
        costs = StageCosts.uniform(S, 1.0, act_bytes=1.0)

        def trace():
            return PeriodicPreemptionTrace(high=50.0, low=0.5, period=20.0, duty=0.3)

        len_1f1b = simulate_plan(
            make_plan(S, M, 1), costs, uniform_network(S, trace)
        ).pipeline_length
        len_zbv = simulate_plan(
            make_plan(S, M, spec=ScheduleSpec(kind="zbv")), costs, uniform_network(S, trace)
        ).pipeline_length
        assert len_zbv <= len_1f1b * 1.001, (S, M, len_zbv, len_1f1b)


def test_zbv_lowered_plan_is_near_zero_bubble():
    """Unit-cost bubble fraction of the lowered V stays single-digit —
    the 2S-slot cap actually buys the zero-bubble operating point."""
    for S, M in ((4, 16), (8, 32)):
        stats = make_plan(S, M, spec=ScheduleSpec(kind="zbv")).lower().stats()
        assert stats["bubble_fraction"] < 0.10, (S, M, stats)


def test_zbv_weight_placement_refinable():
    """ZB-V's registry record opts into the W-placement refinement; the
    optimizer must preserve the task multiset and the plan's peak-live
    price on the V placement."""
    from repro.core import optimize_weight_placement

    plan = make_plan(4, 8, spec=ScheduleSpec(kind="zbv"))
    assert get_kind("zbv").weight_placement_refinable
    skew = StageCosts(
        fwd_time=[1.0, 0.8, 1.2, 0.9], bwd_time=[3.0, 2.0, 2.4, 2.8],
        fwd_bytes=[1.0] * 4, bwd_bytes=[1.0] * 4,
        bwd_input_time=[0.7, 1.1, 0.9, 1.3], bwd_weight_time=[2.3, 0.9, 1.5, 1.5],
    )
    bw = {(a, b): 2.0 for a in range(4) for b in range(4) if abs(a - b) == 1}
    opt = optimize_weight_placement(plan, skew, bw, evaluator="full")
    assert sorted(t.key() for o in opt.orders for t in o) == sorted(
        t.key() for o in plan.orders for t in o
    )
    assert max(peak_live_activations(opt)) <= max(peak_live_activations(plan))
    opt.lower().validate()
