"""The program's own spans and scopes, read back from a profiler trace.

``PlanRuntime`` and ``ServeEngine`` open ``repro.*`` spans through
:func:`repro.obs.span`; a ``jax.profiler`` trace holds them on the host
planes beside the device timeline, with their arguments as event stats.
The step programs carry ``jax.named_scope`` names in their HLO metadata.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedule import make_plan
from repro.models.common import ModelConfig
from repro.obs import TraceRecorder, span, spans_by_track
from repro.optim import make_optimizer
from repro.runtime import PlanRuntime


def _cfg():
    return ModelConfig(
        name="spans-tiny", family="dense", num_layers=2, d_model=8,
        num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=32,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``repro.*`` host spans of the
    trace as ``(start_ns, end_ns, name, {stat: value})``, in start order."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # only the annotations are read
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(tmp_path).glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.append((e.start_ns, e.end_ns, e.name, dict(e.stats)))
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _inside(child, parent) -> bool:
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_runtime_spans_nest_and_carry_their_arguments(tmp_path):
    rt = PlanRuntime(_cfg(), 1, make_optimizer("adamw"), global_batch=2, seq_len=8)
    table = make_plan(1, 2, 1).lower()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 32, (2, 8)).astype(np.int32)
    labels = rng.integers(0, 32, (2, 8)).astype(np.int32)

    def work():
        rt.switch_to(table)
        rt.run_iteration(tokens, labels)

    try:
        spans = _profiled(tmp_path, work)
    finally:
        rt.cache.shutdown()

    (switch,) = _named(spans, "repro.runtime.switch")
    assert switch[3]["warm"] == 0 and switch[3]["restacked"] == 0
    assert switch[3]["to_plan"] == table.plan.name
    (it,) = _named(spans, "repro.runtime.iteration")
    assert it[3]["plan"] == table.plan.name and it[3]["step_num"] == 0
    phases = [_named(spans, f"repro.runtime.{p}") for p in ("feed", "launch", "sync")]
    assert all(len(p) == 1 and _inside(p[0], it) for p in phases)
    (feed,), (launch,), (sync,) = phases
    assert feed[1] <= launch[0] and launch[1] <= sync[0]


def test_serve_spans_nest_and_count(tmp_path):
    from repro.serve import ServeEngine
    from repro.serve.arrival import Request
    from repro.serve.batching import ContinuousBatcher, RequestQueue

    engine = ServeEngine(_cfg(), 2, max_slots=4, max_len=16)
    engine.switch_to(make_plan(2, 2, 2, micro_batch_size=2).lower())
    queue, batcher = RequestQueue(), ContinuousBatcher(4)
    for rid, n in enumerate((6, 6, 8)):
        queue.push(Request(rid, 0.0, n, 2))

    def work():
        admitted = batcher.admit(queue, 0.0)
        engine.prefill(admitted)
        for inf in admitted:
            inf.tokens_emitted = 1
        engine.decode_tick(batcher.in_flight)
        engine.release([admitted[0].slot])

    try:
        spans = _profiled(tmp_path, work)
    finally:
        engine.runtime.cache.shutdown()

    requests = _named(spans, "repro.serve.prefill.request")
    assert [(r[3]["prompt_len"], r[3]["new_program"]) for r in requests] == [
        (6, 1), (6, 0), (8, 1)
    ]
    for step in ("prompt", "program", "insert", "emit"):
        found = _named(spans, f"repro.serve.prefill.{step}")
        assert len(found) == 3
        assert all(_inside(f, r) for f, r in zip(found, requests))

    (tick,) = _named(spans, "repro.serve.decode_tick")
    assert tick[3]["occupied"] == len(batcher.in_flight) == 3
    assert tick[3]["max_slots"] == 4 and tick[3]["host_reads"] == 1
    # the decode program updates the donated cache in place: all of it aliased
    cache_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(engine.kv))
    assert tick[3]["kv_aliased_bytes"] == engine.kv_aliased_bytes == cache_bytes > 0
    (program,) = _named(spans, "repro.runtime.program")
    (emit,) = _named(spans, "repro.serve.decode.emit")
    assert _inside(program, tick) and _inside(emit, tick) and program[1] <= emit[0]
    assert program[3]["label"] == "decode"
    (release,) = _named(spans, "repro.serve.release")
    assert release[3]["slots"] == 1 and release[0] >= tick[1]


def test_training_step_hlo_carries_named_scopes():
    cfg = _cfg()
    rt = PlanRuntime(cfg, 1, make_optimizer("adamw"), global_batch=2, seq_len=8)
    try:
        step, args = rt._program_for(make_plan(1, 2, 1).lower())
        hlo = step.lower(*args).compile().as_text()
    finally:
        rt.cache.shutdown()
    names = [line.split('op_name="', 1)[1].split('"', 1)[0]
             for line in hlo.splitlines() if 'op_name="' in line]
    for scope in ("attention", "mlp", "head_loss", "optimizer"):
        assert any(f"/{scope}/" in n or f"({scope})" in n for n in names), scope


def test_span_helper_mirrors_onto_a_recorder():
    rec = TraceRecorder(clock=iter(float(i) for i in range(10)).__next__)
    with span("repro.runtime.iteration", recorder=rec, track="host0/iterations",
              title="iter 0 p", step=0, plan="p") as sp:
        sp.args["loss"] = 1.5
    with span("repro.runtime.feed") as none:
        assert none is None
    (recorded,) = spans_by_track(rec.to_chrome_trace())["host0/iterations"]
    assert recorded["name"] == "iter 0 p"
    assert recorded["args"] == {"plan": "p", "loss": 1.5}
    assert recorded["dur"] == pytest.approx(1e6)
