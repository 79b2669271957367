"""Serving window: ``ServeEngine`` behind an open loop of requests.

Set-up builds one ``ServeEngine``, gives it the weights the benchmark made
from ``--seed``, compiles the cell's decode plan and warms every program
the window uses: one prefill per prompt length of the mix and a decode tick
with every slot filled. The window replays the server loop of
``chip_smoke.run_server`` on the wall clock: requests join the queue when
they are due, then finished requests retire, queued ones are admitted and
prefilled, and otherwise one grouped decode tick runs. Requests are due
over ``--seconds``; the loop then serves on until every one has finished,
for at most ``DRAIN_SECONDS`` more. A request that has not finished by then
has failed.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, generate, program
from chipbench.common import CompileCounter, Context, Profile, clock, memory_peak_bytes
from chipbench.reference import gpt as ref

DRAIN_SECONDS = 60.0
WARM_RID = 2**31 - 1  # the warm-up's requests; the schedule draws below it


def build(ctx: Context):
    """The engine with the seed's weights, its plan and every shape warmed,
    and the jitted maker of its parameters from a key."""
    from repro.core.schedule import make_plan
    from repro.serve import ServeEngine

    cfg, tr = ctx.config, ctx.traffic
    S, slots, groups = tr["stages"], tr["slots"], tr["groups"]
    engine = ServeEngine(program.model_config(cfg), S, max_slots=slots, max_len=tr["max_len"])
    make = jax.jit(lambda key: program.to_api(ref.init_weights(key, cfg)))
    key = program.seed_key(ctx.seed, 0)
    program.check_layout(jax.eval_shape(make, key), engine.params, "serving parameters")
    engine.params = None  # the program's own weights; ours replace them
    engine.params = make(key)
    engine.switch_to(make_plan(S, groups, tr["k"], micro_batch_size=slots // groups).lower())
    if ctx.patch is not None:
        ctx.patch(engine)
    _warm(engine, tr)
    return engine, make


def _warm(engine, tr: dict) -> None:
    from repro.serve.arrival import Request
    from repro.serve.batching import ContinuousBatcher, RequestQueue

    lengths = sorted(tr["prompt"]["buckets"])
    queue, batcher = RequestQueue(), ContinuousBatcher(tr["slots"])
    for i in range(tr["slots"]):
        queue.push(Request(WARM_RID - i, 0.0, lengths[i % len(lengths)], 2))
    admitted = batcher.admit(queue, 0.0)
    engine.prefill(admitted)
    for inf in admitted:
        inf.tokens_emitted = 1
    engine.decode_tick(batcher.in_flight)
    for inf in batcher.in_flight:
        inf.tokens_emitted += 1
    engine.release([inf.slot for inf in batcher.retire_finished(0.0)])
    jax.block_until_ready((engine.kv, engine.tokens, engine.positions))
    engine.outputs.clear()


def window(ctx: Context, engine, prof: Profile, counter: CompileCounter) -> dict:
    from repro.serve.arrival import Request
    from repro.serve.batching import ContinuousBatcher, RequestQueue

    tr = ctx.traffic
    plan = generate.serve_schedule(tr, ctx.seed, ctx.seconds)
    queue, batcher = RequestQueue(), ContinuousBatcher(tr["slots"])
    due = {p.rid: p.due for p in plan}
    late, first, last, finished = [], {}, {}, {}
    ticks, prefills, emitted_in_window = [], [], 0
    nxt = 0
    t0 = clock()
    counter.active = True
    while True:
        now = clock() - t0
        prof.tick(now)
        while nxt < len(plan) and plan[nxt].due <= now:
            p = plan[nxt]
            queue.push(Request(p.rid, p.due, p.prompt_len, p.new_tokens))
            late.append(now - p.due)
            nxt += 1
        with jax.profiler.TraceAnnotation("chipbench.serve.retire"):
            done = batcher.retire_finished(now)
            engine.release([inf.slot for inf in done])
        for inf in done:
            finished[inf.request.rid] = inf.request
        with jax.profiler.TraceAnnotation("chipbench.serve.admit"):
            admitted = batcher.admit(queue, now)
        if admitted:
            with jax.profiler.TraceAnnotation("chipbench.serve.prefill"):
                engine.prefill(admitted)
                jax.block_until_ready(engine.kv)
            t = clock() - t0
            prefills.append((now, t, sum(inf.request.prompt_len for inf in admitted)))
            for inf in admitted:
                inf.tokens_emitted = 1
                first[inf.request.rid] = last[inf.request.rid] = t
            emitted_in_window += len(admitted) * (t <= ctx.seconds)
            continue
        if batcher.occupancy:
            flight = batcher.in_flight
            occupied = len(flight)
            positions = sum(inf.request.prompt_len + inf.tokens_emitted - 1 for inf in flight)
            with jax.profiler.TraceAnnotation("chipbench.serve.decode_tick"):
                engine.decode_tick(flight)
            t = clock() - t0
            ticks.append((now, t, occupied, positions))
            for inf in flight:
                inf.tokens_emitted += 1
                last[inf.request.rid] = t
            emitted_in_window += occupied * (t <= ctx.seconds)
        elif nxt < len(plan):
            time.sleep(max(0.0, min(plan[nxt].due - now, 0.05)))
        else:
            break
        if now > ctx.seconds + DRAIN_SECONDS:
            break
    prof.stop(clock() - t0)
    counter.active = False
    ttft, tpot = [], []
    for p in plan:
        if p.rid in finished:
            ttft.append(first[p.rid] - due[p.rid])
            tpot.append((last[p.rid] - first[p.rid]) / max(p.new_tokens - 1, 1))
        else:
            ttft.append(math.inf)
            tpot.append(math.inf)
    kept_ticks = [x for x in ticks if not prof.covers(x[0], x[1])]
    kept_prefills = [x for x in prefills if not prof.covers(x[0], x[1])]
    return {
        "attempted": len(plan),
        "failed": len(plan) - len(finished),
        "serve_ttft_p95_ms": generate.quantile(ttft, 0.95) * 1e3,
        "serve_tpot_p95_ms": generate.quantile(tpot, 0.95) * 1e3,
        "serve_output_tokens_per_s": emitted_in_window / ctx.seconds,
        "ttft_p50_ms": generate.quantile(ttft, 0.5) * 1e3,
        "tpot_p50_ms": generate.quantile(tpot, 0.5) * 1e3,
        "generator_late_p95_ms": generate.quantile(late, 0.95) * 1e3,
        "generator_late_max_ms": max(late) * 1e3,
        "ticks": [(t - a, occ, pos) for a, t, occ, pos in kept_ticks],
        "traced_tick_s": [t - a for a, t, _, _ in ticks if prof.inside(a, t)],
        "prefills": [(t - a, n) for a, t, n in kept_prefills],
        "served_s": clock() - t0,
        "compiles_in_window": counter.count,
        "finished": finished,
    }


def sample(ctx: Context, finished: dict) -> list:
    """The requests the check reads: the one with most served tokens and a
    draw from the seed of the rest, ``check_sample`` in all."""
    reqs = sorted(finished.values(), key=lambda r: (r.max_new_tokens, r.prompt_len, r.rid))
    if not reqs:
        return []
    rest = reqs[:-1]
    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 3]))
    k = min(ctx.traffic["check_sample"] - 1, len(rest))
    picked = [rest[i] for i in sorted(rng.choice(len(rest), size=k, replace=False))] if k else []
    return picked + [reqs[-1]]


def sequences(ctx: Context, reqs: list, outputs: dict):
    """Prompt plus served tokens of each request, padded to ``max_len``;
    the prompt is the one the engine makes from the request id."""
    V, T = ctx.config["vocab_size"], ctx.traffic["max_len"]
    seqs = np.zeros((len(reqs), T), np.int32)
    for i, r in enumerate(reqs):
        prompt = jax.random.randint(jax.random.PRNGKey(r.rid), (1, r.prompt_len), 0, V, jnp.int32)
        served = outputs[r.rid][: r.max_new_tokens]
        row = np.concatenate([np.asarray(prompt[0]), np.asarray(served[:-1], np.int32)])
        seqs[i, : len(row)] = row
    return seqs


def logit_gap(ctx: Context, reqs: list, outputs: dict, mode: str = "f32", pick: str = "served") -> float:
    """Widest gap of the served tokens below the f32 reference's best
    logit. ``mode``/``pick="own"`` read the control instead: the token that
    the reference in ``mode`` puts first, held to the f32 reference."""
    if not reqs:
        return math.nan
    key = program.seed_key(ctx.seed, 0)
    w = ref.make_weights(key, ctx.config)
    seqs = sequences(ctx, reqs, outputs)
    chunk = ctx.traffic["check_chunk"]
    logits = ref.logits_at(w, jnp.asarray(seqs), ctx.config, "f32", chunk)
    low = ref.logits_at(w, jnp.asarray(seqs), ctx.config, mode, chunk) if pick == "own" else None
    worst = 0.0
    for i, r in enumerate(reqs):
        c, j = divmod(i, chunk)
        pos = slice(r.prompt_len - 1, r.prompt_len - 1 + r.max_new_tokens)
        rows = np.asarray(logits[c][j, pos], np.float32)
        if pick == "own":
            chosen = np.asarray(low[c][j, pos], np.float32).argmax(axis=-1)
        else:
            chosen = np.asarray(outputs[r.rid][: r.max_new_tokens], np.int64)
            if len(chosen) < r.max_new_tokens:
                return math.inf
        worst = max(worst, compare.served_gap(rows, chosen))
    return worst


def run(ctx: Context) -> dict:
    counter = CompileCounter()
    engine, _ = build(ctx)
    setup_s = clock() - ctx.t_start
    ctx.log(f"set-up {setup_s:.2f} s")
    out = window(ctx, engine, Profile(ctx), counter)
    out["setup_s"] = setup_s
    out["memory_peak_bytes"] = memory_peak_bytes()
    leaves = jax.tree_util.tree_leaves(engine.params)
    out["param_bytes"] = sum(x.nbytes for x in leaves)
    out["kv_itemsize"] = jax.tree_util.tree_leaves(engine.kv)[0].dtype.itemsize
    ctx.log(
        f"window: {out['attempted']} requests, {out['failed']} failed, ttft p95 "
        f"{out['serve_ttft_p95_ms']:.1f} ms, tpot p95 {out['serve_tpot_p95_ms']:.1f} ms, "
        f"{out['serve_output_tokens_per_s']:.1f} tokens/s; generator late p95 "
        f"{out['generator_late_p95_ms']:.1f} ms, max {out['generator_late_max_ms']:.1f} ms; "
        f"{out['compiles_in_window']} compiles"
    )
    outputs = dict(engine.outputs)
    engine.params = engine.kv = None
    engine.runtime.cache.shutdown()
    del engine
    gc.collect()
    finished = out.pop("finished")
    t = clock()
    reqs = sample(ctx, finished)
    out["checked_tokens"] = sum(r.max_new_tokens for r in reqs)
    gaps = {"logit_gap": logit_gap(ctx, reqs, outputs)}
    out["reference_s"] = clock() - t
    out["correct"], out["checks"] = compare.judge(gaps, ctx.limits)
    return out
