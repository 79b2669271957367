"""Smoke run of the main path on a TPU: the kFkB pipeline trainer and the
decode server, at the full widths of the paper's Table 1 GPTs.

This is a smoke run, not a benchmark: it proves that the entry points a user
calls compile, run and agree with a plain reference on the chip.  The times
and byte counts it prints are what one run saw; they are not measurements.

  python chip_smoke.py             one chip (default):
      * trainer — GPT-Medium (24 layers, d 1024, vocab 50257, T 1024) through
        ``PlanRuntime`` on the single-device reference backend, four logical
        stages on the one chip: 1F1B steps, then a precompiled warm switch to
        2F2B (the paper's k > 1 grouping) and more steps.  Step 1's loss and
        gradients are checked against ``jax.value_and_grad`` of
        ``StagedModel.full_loss`` on the same parameters and batch.
      * server — GPT-Medium behind ``ServeEngine``: requests of one prompt
        length through fused prefill and grouped decode ticks; one request's
        logits are checked against the full forward pass of
        ``repro.models.api``.
  python chip_smoke.py --chips 4   four chips of one host, and nothing else:
      GPT-XL (its AdamW state is ~21 GB, more than one chip holds) on the
      ``spmd`` shard_map engine over a ``stage`` mesh: 1F1B steps, then warm
      switches to 2F2B and to interleaved_zb (v=2, which re-stacks the
      parameters).  Step 1 is checked against a jitted GSPMD
      ``value_and_grad(full_loss)`` on the same stage-sharded parameters.

Every phase runs in this one process, which holds the chip(s); it starts no
child process.  Any failed phase or comparison exits non-zero.  The last line
of standard output is the JSON result, printed only when every phase passed.
With no TPU the script exits non-zero before running anything.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances.  The GPT configs keep f32 parameters and compute in bf16
# (dense inputs, activations and the hidden stream between stages are bf16;
# cross-entropy is f32).  The pipeline and its reference run the same bf16
# operations on the same device, so they differ only where the compiler
# fuses or orders bf16 work differently: a few bf16 ulps (2**-8 = 3.9e-3
# relative) on a value, much less on a mean.
#
# LOSS_TOL: absolute, on a mean cross-entropy of ~ln(50257) = 10.8.  A
# dropped or doubled micro-batch moves the mean by >= 1/M of it (> 1.3 for
# M <= 8); 1e-2 leaves a few hundred times more room than bf16 noise on a
# mean over M*T tokens needs, and is still 100x below that shift.
LOSS_TOL = 1e-2
# GRAD_TOL: relative L2 error per parameter leaf and per stage row
# (||g - g_ref|| / ||g_ref||).  bf16 rounding differences between the two
# programs are a few bf16 ulps in norm: on a v5e the worst row measured
# 1.1e-2 (reference backend) and 1.6e-2 (spmd engine vs GSPMD).  A dropped
# micro-batch changes a row by >= 1/M (>= 12.5% for M <= 8); a gradient
# landing on the wrong stage row, or a stage's gradient missing, changes it
# by ~100%.
GRAD_TOL = 3e-2
# LOGIT_TOL: largest |logit difference| over the vocabulary, relative to the
# largest |reference logit|.  Serving's decode step attends over the cache
# one query at a time while the reference attends over the whole sequence,
# so bf16 rounding differs layer by layer; logits are themselves bf16
# (step 2**-8 relative); on a v5e the error measured 1.4e-2.  A cache row
# written to the wrong slot or position gives unrelated logits (relative
# error of order 1).
LOGIT_TOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, non-finite or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def _adamw():
    from repro.optim import make_optimizer

    return make_optimizer("adamw", schedule=lambda s: jnp.float32(1e-4))


def _is_replicated(path) -> bool:
    return any(getattr(p, "key", None) in ("embed", "final_norm") for p in path)


def oracle_loss_and_grads(staged, params, tokens, labels):
    """Mean loss and gradients of ``staged.full_loss`` over the micro-batch
    grid ``[M, b, T]``: one jitted ``value_and_grad`` per micro-batch, summed
    on the host.  Plain jit: on a mesh the compiler partitions it (GSPMD), no
    schedule and no shard_map.  To fit beside the trainer's state it
    rematerializes each layer in the backward pass (same values) and keeps
    the gradients in the parameters' sharding."""
    from repro.pipeline.stage import StagedModel

    ref = StagedModel.build(staged.cfg.replace(remat_blocks=True), staged.num_stages)
    placement = jax.tree_util.tree_map(lambda p: p.sharding, params)

    @jax.jit
    def vg(p, t, lbl):
        loss, grads = jax.value_and_grad(ref.full_loss)(p, t, lbl)
        return loss, jax.lax.with_sharding_constraint(grads, placement)

    M = tokens.shape[0]
    loss, acc = 0.0, None
    for m in range(M):
        lm, gm = vg(params, tokens[m], labels[m])
        gm = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(gm)]
        acc = gm if acc is None else [a + g for a, g in zip(acc, gm)]
        loss += float(lm)
    return loss / M, [a / M for a in acc]


def compare_grads(grads, ref_leaves, summed_replicated: bool) -> float:
    """Largest relative L2 error over leaves and stage rows.

    ``summed_replicated``: the spmd engine psums the replicated leaves
    (embedding, final norm) over stages and writes the total on every row;
    the reference then carries the sum over its rows."""
    worst = 0.0
    for (path, g), ref in zip(jax.tree_util.tree_leaves_with_path(grads), ref_leaves):
        name = jax.tree_util.keystr(path)
        g = np.asarray(g, np.float32)
        if summed_replicated and _is_replicated(path):
            ref = np.broadcast_to(ref.sum(axis=0, keepdims=True), ref.shape)
        check(g.shape == ref.shape, f"grad {name}: shape {g.shape} != {ref.shape}")
        check(bool(np.isfinite(g).all()), f"grad {name} is not finite")
        for row in range(g.shape[0]):
            ref_norm = float(np.linalg.norm(ref[row]))
            err = float(np.linalg.norm(g[row] - ref[row]))
            if ref_norm == 0.0:
                check(err == 0.0, f"grad {name}[{row}] should be zero, |g| = {err}")
                continue
            rel = err / ref_norm
            check(rel <= GRAD_TOL, f"grad {name}[{row}]: rel err {rel:.3e} > {GRAD_TOL}")
            worst = max(worst, rel)
    return worst


def _run_steps(rt, data, first: int, n: int) -> list[float]:
    losses = []
    for i in range(first, first + n):
        batch = data.batch_at(i)
        r = rt.run_iteration(batch.tokens, batch.labels)
        check(bool(np.isfinite(r.loss)), f"step {i + 1} ({r.plan_name}): loss {r.loss}")
        say(f"  step {i + 1:2d} {r.plan_name:24s} loss {r.loss:.5f}  {r.seconds:.3f} s")
        losses.append(r.loss)
    return losses


def _check_step_one(rt, data, summed_replicated: bool) -> None:
    """Run step 1 and hold its loss and grads to the unpipelined reference
    computed on the same parameters (before the step updates them)."""
    M = rt.current_table.plan.num_microbatches
    b = rt.global_batch // M
    batch = data.batch_at(0)
    tokens = batch.tokens.reshape(M, b, rt.seq_len)
    labels = batch.labels.reshape(M, b, rt.seq_len)
    t0 = time.perf_counter()
    ref_loss, ref_grads = oracle_loss_and_grads(rt.staged_for(1), rt.state.params, tokens, labels)
    say(f"reference value_and_grad (compile + {M} micro-batches): {time.perf_counter() - t0:.1f} s")
    (loss,) = _run_steps(rt, data, 0, 1)
    loss_err = abs(loss - ref_loss)
    check(loss_err <= LOSS_TOL, f"step 1 loss {loss} vs reference {ref_loss}: |d| {loss_err:.3e}")
    grad_err = compare_grads(rt.last_grads, ref_grads, summed_replicated)
    say(
        f"step 1 vs reference: loss {loss:.6f} vs {ref_loss:.6f} (|d| {loss_err:.3e} <= "
        f"{LOSS_TOL}); grads max rel L2 err {grad_err:.3e} (<= {GRAD_TOL})"
    )


def run_trainer(cfg, *, stages, microbatches, micro_batch, seq_len, steps, seed=0) -> None:
    """One chip: ``PlanRuntime`` on the reference backend, 1F1B then a warm
    switch to 2F2B."""
    from repro.core.schedule import make_plan
    from repro.data import SyntheticTextDataset
    from repro.runtime import PlanRuntime

    M, b, T = microbatches, micro_batch, seq_len
    say(
        f"trainer: {cfg.name} L={cfg.num_layers} d={cfg.d_model} vocab={cfg.vocab_size}; "
        f"S={stages} stages on one device, M={M} x b={b} x T={T} tokens/step"
    )
    t0 = time.perf_counter()
    rt = PlanRuntime(cfg, stages, _adamw(), global_batch=M * b, seq_len=T, init_key=seed)
    jax.block_until_ready(rt.state)
    say(f"state init: {time.perf_counter() - t0:.1f} s, peak {peak_bytes()}")
    data = SyntheticTextDataset(cfg.vocab_size, T, M * b, seed=seed)
    one_f1b = make_plan(stages, M, 1, micro_batch_size=b).lower()
    two_f2b = make_plan(stages, M, 2, micro_batch_size=b).lower()

    ev = rt.switch_to(one_f1b)
    say(f"{ev.to_plan}: cold compile {ev.compile_seconds:.1f} s")
    _check_step_one(rt, data, summed_replicated=False)
    _run_steps(rt, data, 1, steps - 1)

    t0 = time.perf_counter()
    rt.precompile([two_f2b])
    rt.cache.wait_idle()
    say(f"precompile {two_f2b.plan.name}: {time.perf_counter() - t0:.1f} s (background)")
    ev = rt.switch_to(two_f2b)
    check(ev.warm, f"switch to {ev.to_plan} was not served by the precompile cache")
    say(f"warm switch {ev.from_plan} -> {ev.to_plan}: {ev.seconds * 1e3:.2f} ms")
    _run_steps(rt, data, steps, steps)
    stats = rt.cache.stats
    check(stats.cold_misses == 1, f"expected only the first plan to compile cold: {stats}")
    say(f"trainer done: peak {peak_bytes()}, cache {stats}")
    rt.cache.shutdown()


def run_server(cfg, *, stages, slots, groups, prompt_len, new_tokens, requests, seed=0) -> None:
    """``ServeEngine``: fused prefill + grouped decode ticks over continuous
    batching; one request's logits vs the full forward pass."""
    from repro.core.schedule import make_plan
    from repro.models import api
    from repro.serve import ServeEngine
    from repro.serve.arrival import Request
    from repro.serve.batching import ContinuousBatcher, RequestQueue

    P, N = prompt_len, new_tokens
    say(
        f"server: {cfg.name}, {requests} requests of prompt {P} + {N} new tokens, "
        f"{slots} decode slots in {groups} groups"
    )
    engine = ServeEngine(cfg, stages, max_slots=slots, max_len=P + N, init_key=seed)
    ev = engine.switch_to(make_plan(stages, groups, 2, micro_batch_size=slots // groups).lower())
    say(f"decode program {ev.to_plan}: cold compile {ev.compile_seconds:.1f} s")
    queue, batcher = RequestQueue(), ContinuousBatcher(slots)
    for rid in range(requests):
        queue.push(Request(rid, 0.0, P, N))
    target = requests - 1  # admitted last, into a slot another request left
    checked = False
    served = ticks = 0
    t_prefill = t_decode = 0.0
    while served < requests:
        for inf in batcher.in_flight:
            if inf.request.rid == target and inf.done:
                _check_serve_logits(engine, cfg, api, inf, P)
                checked = True
        done = batcher.retire_finished(0.0)
        engine.release([inf.slot for inf in done])
        served += len(done)
        admitted = batcher.admit(queue, 0.0)
        if admitted:
            t0 = time.perf_counter()
            engine.prefill(admitted)
            jax.block_until_ready(engine.kv)
            t_prefill += time.perf_counter() - t0
            for inf in admitted:
                inf.tokens_emitted = 1
            continue
        if batcher.occupancy:
            t0 = time.perf_counter()
            engine.decode_tick(batcher.in_flight)
            t_decode += time.perf_counter() - t0
            ticks += 1
            for inf in batcher.in_flight:
                inf.tokens_emitted += 1
    check(checked, "the checked request never finished")
    lengths = {len(t) for t in engine.outputs.values()}
    check(len(engine.outputs) == requests and lengths == {N}, f"outputs per request: {lengths}")
    say(
        f"served {requests} requests ({requests * N} tokens): prefill total {t_prefill:.2f} s "
        f"(first includes its compile), {ticks} decode ticks {t_decode:.2f} s; "
        f"peak {peak_bytes()}"
    )


def _check_serve_logits(engine, cfg, api, inf, prompt_len) -> None:
    """The finished request's cache row, filled by fused prefill and the
    grouped decode ticks, must give the logits the full forward pass gives
    for prompt + emitted tokens; and every emitted token must be a (near)
    argmax of the reference logits at its position."""
    rid, slot = inf.request.rid, inf.slot
    prompt = jax.random.randint(
        jax.random.PRNGKey(rid), (1, prompt_len), 0, cfg.vocab_size, jnp.int32
    )
    emitted = jnp.asarray(engine.outputs[rid], jnp.int32)[None]
    seq = jnp.concatenate([prompt, emitted], axis=1)
    row = engine.slot_cache(slot)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda p, c, i, t: api.decode_fn(p, cfg, c, i, {"tokens": t})[0])
        got = step(engine.params, row, engine.positions[slot], engine.tokens[slot][None])
        full = jax.jit(lambda p, t: api.forward_fn(p, cfg, {"tokens": t})[0])
        ref = full(engine.params, seq)
    got = np.asarray(got[0, -1], np.float32)
    ref = np.asarray(ref[0], np.float32)  # [P + n, V]
    check(bool(np.isfinite(got).all()), "serving logits are not finite")
    scale = float(np.abs(ref[-1]).max())
    rel = float(np.abs(got - ref[-1]).max()) / scale
    check(rel <= LOGIT_TOL, f"request {rid}: logits rel err {rel:.3e} > {LOGIT_TOL}")
    # emitted token i was chosen from the logits at position P - 1 + i
    picked = ref[prompt_len - 1 : -1][np.arange(emitted.shape[1]), np.asarray(emitted[0])]
    gap = float((ref[prompt_len - 1 : -1].max(axis=-1) - picked).max()) / scale
    check(gap <= LOGIT_TOL, f"request {rid}: an emitted token is {gap:.3e} below the argmax")
    say(
        f"request {rid} (slot {slot}): logits vs full forward max rel err {rel:.3e} "
        f"(<= {LOGIT_TOL}); emitted tokens within {gap:.3e} of the reference argmax"
    )


def run_spmd_trainer(cfg, mesh, *, microbatches, micro_batch, seq_len, steps, seed=0) -> None:
    """Four chips: the shard_map engine, 1F1B -> 2F2B -> interleaved_zb."""
    from repro.core.kinds import ScheduleSpec
    from repro.core.schedule import make_plan
    from repro.data import SyntheticTextDataset
    from repro.runtime import PlanRuntime

    S = mesh.shape["stage"]
    M, b, T = microbatches, micro_batch, seq_len
    say(
        f"spmd trainer: {cfg.name} L={cfg.num_layers} d={cfg.d_model} vocab={cfg.vocab_size}; "
        f"S={S} stages on {S} chips, M={M} x b={b} x T={T} tokens/step"
    )
    t0 = time.perf_counter()
    rt = PlanRuntime(
        cfg, S, _adamw(), global_batch=M * b, seq_len=T, backend="spmd", mesh=mesh,
        init_key=seed,
    )
    jax.block_until_ready(rt.state)
    say(f"sharded state init: {time.perf_counter() - t0:.1f} s, peak {peak_bytes()}")
    data = SyntheticTextDataset(cfg.vocab_size, T, M * b, seed=seed)
    plans = [
        make_plan(S, M, 1, micro_batch_size=b),
        make_plan(S, M, 2, micro_batch_size=b),
        make_plan(
            S, M,
            spec=ScheduleSpec(kind="interleaved_zb", num_virtual=2, micro_batch_size=b),
        ),
    ]
    tables = [p.lower() for p in plans]
    # the later plans and the re-stack programs between the layouts compile
    # in the background while the first compiles and runs here
    t0 = time.perf_counter()
    rt.precompile(tables[1:])
    ev = rt.switch_to(tables[0])
    say(f"{ev.to_plan}: cold compile {ev.compile_seconds:.1f} s")
    _check_step_one(rt, data, summed_replicated=True)
    done = 1
    _run_steps(rt, data, done, steps - 1)
    done += steps - 1
    rt.cache.wait_idle()
    say(f"all programs ready {time.perf_counter() - t0:.1f} s after the first compile began")
    for table in tables[1:]:
        ev = rt.switch_to(table)
        check(ev.warm, f"switch to {ev.to_plan} was not served by the precompile cache")
        say(
            f"warm switch {ev.from_plan} -> {ev.to_plan}: {ev.seconds * 1e3:.2f} ms"
            f"{' (parameters re-stacked)' if ev.restacked else ''}"
        )
        _run_steps(rt, data, done, steps)
        done += steps
    check(any(e.restacked for e in rt.switch_events), "no switch re-stacked the parameters")
    say(f"spmd trainer done: peak {peak_bytes()} (device 0), cache {rt.cache.stats}")
    rt.cache.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the four-chip GPT-XL spmd phase",
    )
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r}); nothing was run",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs.gpt import GPT_CONFIGS
    from repro.runtime import enable_persistent_cache

    say(f"smoke run, not a benchmark; compile cache {enable_persistent_cache()}")
    say(f"device {devices[0].device_kind} x {len(devices)}")
    t_start = time.perf_counter()
    if args.chips == 4:
        from repro.pipeline import stage_mesh

        mesh = stage_mesh(4)
        # M=4, the least interleaved_zb allows on four stages: its unrolled
        # tick program compiles in minutes, and grows with M
        run_spmd_trainer(
            GPT_CONFIGS["GPT-XL"], mesh, microbatches=4, micro_batch=1, seq_len=1024, steps=2
        )
    else:
        # M=4 x b=1 x T=1024: with the state donated and each layer
        # rematerialized in the backward pass, a 1F1B or 2F2B step needs
        # 14.97 GiB on one v5e (compile rehearsal); without remat, 17.2 GiB
        run_trainer(
            GPT_CONFIGS["GPT-Medium"].replace(remat_blocks=True), stages=4,
            microbatches=4, micro_batch=1, seq_len=1024, steps=3,
        )
        run_server(
            GPT_CONFIGS["GPT-Medium"], stages=4, slots=8, groups=4, prompt_len=128,
            new_tokens=16, requests=12,
        )
    say(f"all phases passed in {time.perf_counter() - t_start:.1f} s")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
