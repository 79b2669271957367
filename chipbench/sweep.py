"""Find the highest request rate a serving cell sustains, once, on the chip.

    python chipbench/sweep.py --workload <cell> --rates 2,3,4,5 [--seconds 30] [--seed n]

Builds the cell's engine once and serves the cell's traffic mix at each
rate in turn, printing one JSON line per rate: the window's end-to-end
numbers, the median time to first token, how long the requests due in
the window took to drain after it closed, and the mean slot occupancy. A
rate is sustained while the median time to first token stays near one
tick plus one prefill (no queue builds behind full slots). The cell's
rate is then written into its traffic file by hand: cells offer a fixed
load.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path
import sys
import time

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=8_000_000_017)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from chipbench.run import init_jax

    init_jax()
    from chipbench.common import CompileCounter, Context, Profile
    from chipbench.drivers import serve
    from chipbench.run import find_cell

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic = find_cell(bench, args.workload)
    ctx = Context(
        workload=args.workload, config=config, traffic=traffic, limits={}, seed=args.seed,
        seconds=args.seconds, trace=False, trace_dir=ROOT / ".chipbench_traces", chips=cell["chips"],
        t_start=time.perf_counter(),
    )
    engine, _ = serve.build(ctx)
    counter = CompileCounter()
    for rate in (float(r) for r in args.rates.split(",")):
        ctx.traffic = dict(traffic, rate=rate)
        engine.outputs.clear()
        out = serve.window(ctx, engine, Profile(ctx), counter)
        out.pop("finished")
        ticks = out.pop("ticks")
        prefills = out.pop("prefills")
        out["drain_s"] = out["served_s"] - args.seconds
        out["ticks"] = len(ticks)
        out["tick_ms_median"] = sorted(t for t, _, _ in ticks)[len(ticks) // 2] * 1e3 if ticks else None
        out["mean_occupancy"] = sum(o for _, o, _ in ticks) / len(ticks) if ticks else 0
        out["prefill_s"] = sum(t for t, _ in prefills)
        print(json.dumps({"rate": rate, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
