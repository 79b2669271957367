"""Readings that the limits of ``correct`` are set from, for one cell, in one
process on the chip.

    python chipbench/calibrate.py --workload <cell> --seeds 12 --control 3 [--seconds 20]
        [--witness-stages 4 --witness-microbatches 4]

For every seed it reads what a run compares: the program against the f32
reference (the lower reading). For the first ``--control`` seeds it also
reads the control, the reference computed in fp8 put in the program's
place, and, for training, the reference with half of each batch left out
and the mean taken over the rest. ``--witness-stages`` reads the training
program again with that many pipeline stages on one chip, twice on each
of the first ``--control`` seeds, with ``--witness-microbatches`` if given
(``--seeds 0`` reads only that). Prints one JSON object per reading.
"""

from __future__ import annotations

import argparse
import gc
import json
from pathlib import Path
import sys
import time

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def say(obj) -> None:
    print(json.dumps(obj), flush=True)


def half_batch(ctx, train):
    """The reference fed each step's first half of micro-batches twice: the
    mean over half the batch."""
    from chipbench import generate, program
    from chipbench.reference import gpt as ref

    V, M = ctx.config["vocab_size"], ctx.traffic["microbatches"]
    batches = []
    for s in range(train.CHECK_STEPS):
        t, lab = generate.train_batch(ctx.traffic, V, ctx.seed, s)
        batches.append(tuple(x[: M // 2].repeat(2, axis=0) for x in (t, lab)))
    return ref.train_readings(program.seed_key(ctx.seed, 0), batches, ctx.config, ctx.config["optimizer"])


def calibrate_train(ctx, seeds, control, witness_stages, witness_seeds, witness_microbatches):
    from chipbench import compare, program
    from chipbench.drivers import train

    rt, make_state = train.build(ctx) if seeds else (None, None)
    for i, seed in enumerate(seeds):
        ctx.seed = seed
        rt.state = None
        rt.state = make_state(program.seed_key(seed, 0))
        prog = train.check_steps(ctx, rt)
        rt.state = rt.last_grads = None
        gc.collect()
        f32 = train.reference_readings(ctx)
        say({"seed": seed, "who": "program", **compare.train_gaps(prog, f32), "losses": prog["losses"]})
        if i < control:
            say({"seed": seed, "who": "control_fp8", **compare.train_gaps(train.reference_readings(ctx, "fp8"), f32)})
            say({"seed": seed, "who": "fault_half_batch", **compare.train_gaps(half_batch(ctx, train), f32)})
    if rt is not None:
        rt.cache.shutdown()
    del rt
    gc.collect()
    if witness_stages:
        ctx.traffic = dict(ctx.traffic, stages=witness_stages, microbatches=witness_microbatches)
        rt, make_state = train.build(ctx)
        for seed in witness_seeds:
            ctx.seed = seed
            for repeat in range(2):
                rt.state = None
                rt.state = make_state(program.seed_key(seed, 0))
                prog = train.check_steps(ctx, rt)
                rt.state = rt.last_grads = None
                gc.collect()
                gaps = compare.train_gaps(prog, train.reference_readings(ctx))
                say({"seed": seed, "who": f"program_{witness_stages}_stages", "repeat": repeat, **gaps})


def calibrate_serve(ctx, seeds, control):
    from chipbench import program
    from chipbench.common import CompileCounter, Profile
    from chipbench.drivers import serve

    engine, make = serve.build(ctx)
    counter = CompileCounter()
    for i, seed in enumerate(seeds):
        ctx.seed = seed
        engine.params = None
        engine.params = make(program.seed_key(seed, 0))
        engine.outputs.clear()
        out = serve.window(ctx, engine, Profile(ctx), counter)
        reqs = serve.sample(ctx, out["finished"])
        gap = serve.logit_gap(ctx, reqs, engine.outputs)
        say({"seed": seed, "who": "program", "logit_gap": gap, "attempted": out["attempted"],
             "failed": out["failed"], "checked_tokens": sum(r.max_new_tokens for r in reqs)})
        if i < control:
            ctl = serve.logit_gap(ctx, reqs, engine.outputs, mode="fp8", pick="own")
            say({"seed": seed, "who": "control_fp8", "logit_gap": ctl})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=9_000_000_001)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--witness-stages", type=int, default=0)
    ap.add_argument("--witness-microbatches", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from chipbench.run import init_jax

    init_jax()
    import jax

    from chipbench.common import Context
    from chipbench.run import find_cell

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic = find_cell(bench, args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(max(args.seeds, args.control))]
    ctx = Context(
        workload=args.workload, config=config, traffic=traffic, limits={}, seed=seeds[0],
        seconds=args.seconds, trace=False, trace_dir=ROOT / ".chipbench_traces", chips=cell["chips"],
        t_start=time.perf_counter(),
    )
    say({"device": jax.devices()[0].device_kind, "count": len(jax.devices())})
    if traffic["driver"] == "train":
        calibrate_train(ctx, seeds[: args.seeds], args.control, args.witness_stages,
                        seeds[: args.control], args.witness_microbatches or traffic["microbatches"])
    else:
        calibrate_serve(ctx, seeds, args.control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
