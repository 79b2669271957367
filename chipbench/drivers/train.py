"""Training window: ``PlanRuntime.run_iteration`` step after step.

Set-up builds one ``PlanRuntime``, gives it the weights the benchmark made
from ``--seed``, compiles the cell's plan and drives the first steps
through the same ``run_iteration`` call and batch feed the window uses;
those steps are what the reference checks. The window then runs steps, a
fresh batch from the generator before each, until ``--seconds`` have
passed; it ends at the last completed step.
"""

from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp

from chipbench import compare, generate, program
from chipbench.common import CompileCounter, Context, Profile, clock, memory_peak_bytes
from chipbench.reference import gpt as ref

CHECK_STEPS = 3


def _optimizer(opt: dict):
    from repro.optim import make_optimizer

    lr = opt["lr"]
    return make_optimizer(
        "adamw",
        schedule=lambda step: jnp.float32(lr),
        max_grad_norm=opt["max_grad_norm"],
        b1=opt["b1"],
        b2=opt["b2"],
        eps=opt["eps"],
        weight_decay=opt["weight_decay"],
    )


def build(ctx: Context):
    """The runtime with the seed's weights and the cell's plan compiled, and
    the jitted maker of a fresh state from a key."""
    from repro.core.schedule import make_plan
    from repro.runtime import PlanRuntime
    from repro.training.state import create_train_state

    cfg, tr = ctx.config, ctx.traffic
    S, M, b, T = tr["stages"], tr["microbatches"], tr["micro_batch"], tr["seq_len"]
    opt = _optimizer(cfg["optimizer"])
    kw = {}
    if tr["backend"] == "spmd":
        from repro.pipeline import stage_mesh

        kw = {"backend": "spmd", "mesh": stage_mesh(S)}
    rt = PlanRuntime(
        program.model_config(cfg, remat=tr["remat"]), S, opt, global_batch=M * b, seq_len=T, **kw
    )
    sharding = jax.tree_util.tree_map(lambda x: x.sharding, rt.state)

    def make_state(key):
        return create_train_state(program.to_staged(ref.init_weights(key, cfg), S), opt)

    make_state = jax.jit(make_state, out_shardings=sharding)
    key = program.seed_key(ctx.seed, 0)
    program.check_layout(jax.eval_shape(make_state, key), rt.state, "train state")
    rt.state = None  # the program's own initial state; ours replaces it
    rt.state = make_state(key)
    rt.switch_to(make_plan(S, M, tr["k"], micro_batch_size=b).lower())
    if ctx.patch is not None:
        ctx.patch(rt)
    return rt, make_state


def check_steps(ctx: Context, rt) -> dict:
    """Drive the first steps and read what the reference is held to."""
    S, b1 = ctx.traffic["stages"], ctx.config["optimizer"]["b1"]
    vocab = ctx.config["vocab_size"]
    grad_norms = jax.jit(lambda m: ref.piece_norms(program.from_staged(m, S)) / (1.0 - b1))
    change = jax.jit(
        lambda p, key: ref.change_norms(program.from_staged(p, S), ref.init_weights(key, ctx.config))
    )
    losses, first = [], None
    for step in range(CHECK_STEPS):
        tokens, labels = generate.train_batch(ctx.traffic, vocab, ctx.seed, step)
        losses.append(rt.run_iteration(tokens, labels).loss)
        if first is None:
            first = [float(x) for x in grad_norms(rt.state.opt_state.m)]
    moved = [float(x) for x in change(rt.state.params, program.seed_key(ctx.seed, 0))]
    return {"losses": losses, "grad_norms": first, "change_norms": moved}


def window(ctx: Context, rt, prof: Profile, counter: CompileCounter) -> dict:
    tr, vocab = ctx.traffic, ctx.config["vocab_size"]
    tokens_per_step = tr["microbatches"] * tr["micro_batch"] * tr["seq_len"]
    steps, failed = [], 0
    t0 = clock()
    counter.active = True
    step = CHECK_STEPS
    while clock() - t0 < ctx.seconds:
        a = clock() - t0
        prof.tick(a)
        with jax.profiler.TraceAnnotation("chipbench.train.data"):
            tokens, labels = generate.train_batch(tr, vocab, ctx.seed, step)
        with jax.profiler.TraceAnnotation("chipbench.train.step"):
            loss = rt.run_iteration(tokens, labels).loss
        failed += not math.isfinite(loss)
        steps.append((a, clock() - t0))
        step += 1
    prof.stop(clock() - t0)
    counter.active = False
    end = steps[-1][1]
    kept = [(a, e) for a, e in steps if not prof.covers(a, e)]
    return {
        "t0": t0,
        "attempted": len(steps),
        "failed": failed,
        "window_s": end,
        "tokens_per_step": tokens_per_step,
        "train_tokens_per_s": len(steps) * tokens_per_step / end,
        "untraced_tokens_per_s": len(kept) * tokens_per_step / max(sum(e - a for a, e in kept), 1e-9),
        "step_s": [e - a for a, e in steps],
        "traced_step_s": [e - a for a, e in steps if prof.inside(a, e)],
        "compiles_in_window": counter.count,
    }


def reference_readings(ctx: Context, mode: str = "f32") -> dict:
    vocab = ctx.config["vocab_size"]
    batches = [generate.train_batch(ctx.traffic, vocab, ctx.seed, s) for s in range(CHECK_STEPS)]
    return ref.train_readings(program.seed_key(ctx.seed, 0), batches, ctx.config, ctx.config["optimizer"], mode)


def run(ctx: Context) -> dict:
    counter = CompileCounter()
    rt, _ = build(ctx)
    readings = check_steps(ctx, rt)
    setup_s = clock() - ctx.t_start
    ctx.log(f"set-up {setup_s:.2f} s; check-step losses {readings['losses']}")
    out = window(ctx, rt, Profile(ctx), counter)
    out["setup_s"] = setup_s
    out["memory_peak_bytes"] = memory_peak_bytes()
    out["param_bytes"] = sum(x.nbytes for x in jax.tree_util.tree_leaves(rt.state.params))
    ctx.log(
        f"window: {out['attempted']} steps in {out['window_s']:.3f} s, "
        f"{out['train_tokens_per_s']:.1f} tokens/s, {out['compiles_in_window']} compiles"
    )
    rt.state = rt.last_grads = None
    rt.cache.shutdown()
    del rt
    gc.collect()
    t = clock()
    gaps = compare.train_gaps(readings, reference_readings(ctx))
    out["reference_s"] = clock() - t
    out["correct"], out["checks"] = compare.judge(gaps, ctx.limits)
    return out
