"""Run one benchmark cell on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are read by name: the cell
from ``BENCHMARK.json``, the configuration from its ``file``, the traffic
mix from ``chipbench/traffic/<traffic>.json`` (which names its driver,
``chipbench/drivers/<driver>.py``), the limits of the comparison from
``chipbench/limits/<cell>.json``, and each per-layer metric from
``chipbench/metrics/<metric>.py``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of a slice
of the window and from the run's own host-clock readings. The last line of
standard output is the result, one JSON object; the numbers the comparison
read, each beside its limit, are the last lines of standard error and the
result's last key. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
from pathlib import Path  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of a workload, found by name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metrics_for(bench: dict, workload: str, key: str) -> list[dict]:
    """The metrics of ``key`` (``end_to_end`` or ``per_layer``) this cell
    reports: those that list it, and those with no list."""
    return [m for m in bench[key] if workload in m.get("workloads", [workload])]


def init_jax() -> None:
    """Put the program on the path and JAX's persistent compilation cache
    at one fixed directory inside the checkout, so that a cell's later runs
    load what its first compiled; every program is cached, however quick."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log under /tmp
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def per_layer(bench, ctx, out, peaks, trace) -> dict:
    from chipbench import flops

    found = {}
    for m in metrics_for(bench, ctx.workload, "per_layer"):
        reader = load_module(HERE / "metrics" / f"{m['name']}.py", f"chipbench_metric_{len(found)}")
        value = reader.read(
            summary=out, trace=trace, peaks=peaks, flops=flops,
            config=ctx.config, traffic=ctx.traffic, chips=ctx.chips,
        )
        if value is not None:
            found[m["name"]] = {"value": number(value), "unit": m["unit"]}
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the program under test is missing ({ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic = find_cell(bench, args.workload)
    limits_path = HERE / "limits" / f"{args.workload}.json"
    limits = json.loads(limits_path.read_text()) if limits_path.exists() else {}

    init_jax()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chipbench: no TPU (JAX platform {dev['platform']!r}); nothing was run", file=sys.stderr)
        return 3
    if dev["count"] < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, JAX sees {dev['count']}",
              file=sys.stderr)
        return 3
    peaks_all = json.loads((HERE / "peaks.json").read_text())["devices"]
    if dev["kind"] not in peaks_all:
        print(f"chipbench: no peaks for device kind {dev['kind']!r} in peaks.json", file=sys.stderr)
        return 3

    from chipbench.common import Context

    trace_dir = ROOT / ".chipbench_traces" / f"{args.workload}.{args.seed}"
    ctx = Context(
        workload=args.workload, config=config, traffic=traffic, limits=limits, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), trace_dir=trace_dir, chips=cell["chips"],
        t_start=T_START,
    )
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py", "chipbench_driver")
    out = driver.run(ctx)
    try:
        return report(bench, ctx, out, dev, peaks_all[dev["kind"]])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def number(x):
    """A metric or check value as JSON can carry it: non-finite is null."""
    return float(x) if x is not None and math.isfinite(x) else None


def report(bench, ctx, out, dev, peaks) -> int:
    device = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": bool(out["correct"]) and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"]}
    if ctx.trace:
        from chipbench import trace_reduce

        trace = trace_reduce.load(ctx.trace_dir)
        result["metrics"] = per_layer(bench, ctx, out, peaks, trace)
        if trace is not None:
            device["busy_s"], device["window_s"] = trace.busy_s(), trace.window_s()
            result["breakdown"] = trace.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": number(out[m["name"]]), "unit": m["unit"]}
            for m in metrics_for(bench, ctx.workload, "end_to_end")
        }
    result["device"] = device
    result["checks"] = {name: {"value": number(v), "limit": number(lim)} for name, v, lim in out["checks"]}
    extra = {k: out[k] for k in ("compiles_in_window", "reference_s", "setup_s") if k in out}
    for k in ("step_s", "traced_step_s", "traced_tick_s"):
        if out.get(k):
            extra[f"median_{k}"] = sorted(out[k])[len(out[k]) // 2]
    if out.get("ticks"):
        extra["median_tick_s"] = sorted(t for t, _, _ in out["ticks"])[len(out["ticks"]) // 2]
    print(f"[chipbench] {json.dumps(extra)}", file=sys.stderr)
    for name, v, lim in out["checks"]:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
