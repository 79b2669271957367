import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=256 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Production-mesh dry-run of the kFkB PIPELINE ENGINE itself.

The SPMD dry-run (dryrun.py) covers the 40 (arch × shape) pairs; this one
proves the paper's execution engine lowers at production scale: 16 pipeline
stages on the mesh's "stage" axis × 16-way data parallelism (= one full
16×16 pod), driving a real tick table for the requested k.

For each (config, k) it lowers + compiles ``make_pipeline_step`` with
ShapeDtypeStruct inputs, reports the roofline terms and — the part unique
to the engine — the per-tick ppermute schedule (count == 2 ticks·permutes,
wire bytes == the activation/gradient stream the paper's Send/Recv nodes
carry).

``--calibrate`` additionally runs :mod:`repro.core.calibrate` against the
config's real stage bodies: per-stage fwd / BWD_INPUT / BWD_WEIGHT roofline
times and activation bytes (the heterogeneous ``StageCosts`` the scheduler
stack consumes instead of ``StageCosts.uniform``), the matching per-stage
``MemoryModel``, and the per-stage warmup vector ``w[s]`` the candidate
enumeration admits under a per-stage memory-limit curve derived from the
calibrated profile.

``--calibrate --device-spec specs/<part>.json`` prices the same profile
OFFLINE for a committed device spec (``method="spec"``) and runs the full
enumerate+tune search on the derived costs — schedule selection for
hardware this host doesn't have.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun_pipeline --config qwen2.5-14b \
      --k 2 --microbatches 32 [--calibrate [--device-spec specs/h100-sxm.json]]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.core.kinds import ScheduleSpec
from repro.core.schedule import make_plan, tick_table, tick_table_stats
from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
from repro.models.common import param_count
from repro.pipeline.engine import make_pipeline_step, stage_mesh
from repro.pipeline.stage import StagedModel

ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun_pipeline"
)


def _config(name: str):
    from repro.configs.gpt import GPT_CONFIGS

    if name in GPT_CONFIGS:  # the paper's Table-1 ladder (GPT-Medium .. 2.7B)
        return GPT_CONFIGS[name]
    from repro.configs import get_arch

    return get_arch(name).model


def _tune_on_spec(cal, spec, S: int, b_mb: int) -> dict:
    """The offline adaptive search on a spec-derived calibration: enumerate
    candidates under the part's capacity curve and tune over a stable
    network at its link bandwidth.  Deterministic — the laptop answer to
    "what schedule would this config want on that hardware"."""
    from repro.core import (
        AutoTuner,
        NetworkProfiler,
        SearchSpace,
        StableTrace,
        enumerate_candidates,
        uniform_network,
    )

    M = max(4 * S, 8)
    B = M * b_mb
    cands = enumerate_candidates(
        S, B, cal.memory, cal.limits,
        space=SearchSpace(
            kinds=("kfkb", "zb_h1", "zb_h2", "zbv", "interleaved"),
            virtual_degrees=(2,), max_k=2,
            zb_policies=("double_remat", "saved_residual"),
        ),
    )

    def costs_for(cand):
        return cal.costs.scaled_to_microbatch(b_mb, cand.micro_batch_size)

    net = uniform_network(
        S, lambda: StableTrace(spec.link_bandwidth_bytes_per_s)
    )
    rec = AutoTuner(cands, costs_for, NetworkProfiler(net)).tune(0.0)
    chosen = next(c for c in cands if c.name == rec.chosen)
    return {
        "global_batch": B,
        "candidates": [c.name for c in cands],
        "estimates": rec.estimates,
        "chosen": {
            "name": rec.chosen,
            "kind": rec.chosen_kind,
            "k": rec.chosen_k,
            "b": chosen.micro_batch_size,
            "extra_warmup": list(rec.chosen_extra_warmup),
            "zb_policy": list(rec.chosen_zb_policy),
        },
    }


def calibrate(
    config: str, S: int, b_mb: int, seq: int, out_dir: str,
    device_spec: str | None = None,
) -> dict:
    """Calibrated per-stage profile of the config's REAL stage bodies.

    Reports the heterogeneous StageCosts (per-stage fwd/B/W roofline times,
    activation wire bytes), the per-stage memory footprint, and the warmup
    vector ``w[s]`` a per-stage limit curve with 25% activation headroom
    admits — the end-to-end input of the vector-w scheduling stack.

    With ``device_spec`` (a ``specs/*.json`` path) the profile is priced
    OFFLINE for that part (``method="spec"``): the limit curve becomes the
    part's capacity, and the full adaptive search runs on the derived
    costs — candidate enumeration + tuner over a stable network at the
    spec's link bandwidth — answering "what schedule would this config
    want on that hardware" without running on it.
    """
    from repro.core.calibrate import calibrate_stage_costs
    from repro.core.candidates import largest_admissible_warmup

    cfg = _config(config)
    staged = StagedModel.build(cfg, S)
    spec = None
    if device_spec is not None:
        from repro.core.devicespec import load_device_spec

        spec = load_device_spec(device_spec)
        cal = calibrate_stage_costs(
            staged, micro_batch_size=b_mb, seq_len=seq,
            method="spec", device_spec=spec,
        )
    else:
        cal = calibrate_stage_costs(staged, micro_batch_size=b_mb, seq_len=seq)
    costs, mm = cal.costs, cal.memory
    device_tag = f" on {spec.name}" if spec else ""
    print(f"{config}: calibrated {S} stages at b={b_mb}, seq={seq}{device_tag}")
    print("stage |  fwd ms |  B ms |  W ms | W(SR) ms | wire MB")
    for row in cal.summary_rows():
        print("  ".join(f"{c:>7s}" for c in row))
    M = max(4 * S, 8)
    h1 = make_plan(S, M, spec=ScheduleSpec(kind="zb_h1"))
    base = mm.peak_bytes_per_stage(h1)
    if spec is not None:
        # the part's own capacity is the limit curve for offline pricing
        limits = list(cal.limits)
    else:
        # a per-stage limit curve: each stage's H1 peak plus 25% of its own
        # activation working set — heterogeneity makes the admitted w[s] differ
        limits = [
            p + 0.25 * mm.slot_bytes(s, b_mb, True) * S for s, p in enumerate(base)
        ]
    w_vec = largest_admissible_warmup(S, M, 1, b_mb, 1, True, mm, limits, S - 1)
    print(f"admitted warmup vector w[s] under the limit curve: {w_vec}")
    record = {
        "config": config,
        "stages": S,
        "micro_batch_size": b_mb,
        "seq": seq,
        "device": cal.device,
        "dtype": cal.dtype,
        "fwd_time": costs.fwd_time,
        "bwd_input_time": costs.bwd_input_time,
        "bwd_weight_time": costs.bwd_weight_time,
        "bwd_weight_saved_time": costs.bwd_weight_saved_time,
        "fwd_bytes": costs.fwd_bytes,
        "param_bytes_per_stage": [sp.param_bytes for sp in mm.stages],
        "peak_bytes_h1": base,
        "limit_curve": limits,
        "admitted_warmup_vector": list(w_vec),
    }
    if spec is not None:
        record["tuned"] = _tune_on_spec(cal, spec, S, b_mb)
        chosen = record["tuned"]["chosen"]
        print(
            f"on {spec.name}, the tuner picks {chosen['name']} "
            f"(kind={chosen['kind']} k={chosen['k']} b={chosen['b']})"
        )
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{config}__S{S}_calibration.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    print(f"[ok] calibration written to {path}")
    return record


def run(config: str, S: int, M: int, k: int, batch: int, seq: int, out_dir: str):
    cfg = _config(config)
    staged = StagedModel.build(cfg, S)
    plan = make_plan(S, M, k)
    stats = tick_table_stats(tick_table(plan))
    mesh = stage_mesh(S, jax.device_count() // S)
    b_mb = batch // M
    print(f"{config}: {cfg.num_layers}L over {S} stages x {mesh.shape['data']} DP, "
          f"{plan.name}, ticks={stats['ticks']:.0f} "
          f"(bubble {stats['bubble_fraction']:.1%} at unit cost)")

    params_specs = jax.eval_shape(lambda: staged.init_all_stages(jax.random.PRNGKey(0)))
    tok_spec = jax.ShapeDtypeStruct((M, b_mb, seq), jnp.int32)
    step = make_pipeline_step(staged, plan, mesh, data_axis="data")
    t0 = time.time()
    with mesh:
        lowered = jax.jit(step).lower(params_specs, tok_spec, tok_spec)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    ana = analyze_hlo(compiled.as_text())
    terms = roofline_terms(ana.flops, ana.hbm_bytes, ana.wire_bytes)
    record = {
        "config": config,
        "plan": plan.name,
        "stages": S,
        "microbatches": M,
        "k": k,
        "batch": batch,
        "seq": seq,
        "params_total": param_count(cfg),
        "ticks": stats["ticks"],
        "unit_bubble_fraction": stats["bubble_fraction"],
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": ana.flops,
        "bytes_accessed_per_device": ana.hbm_bytes,
        "collective_wire_bytes_per_device": ana.wire_bytes,
        "collective_counts": ana.collective_counts,
        "collective_bytes_by_kind": ana.collective_bytes_by_kind,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "roofline": terms,
    }
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{config}__S{S}_M{M}_k{k}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    print(f"[ok] {tag}: lower {t_lower:.0f}s compile {t_compile:.0f}s  "
          f"compute {terms['compute_s']*1e3:.0f}ms mem {terms['memory_s']*1e3:.0f}ms "
          f"coll {terms['collective_s']*1e3:.0f}ms -> {terms['bottleneck']}  "
          f"permutes={round(ana.collective_counts.get('collective-permute', 0))} "
          f"temp {record['memory']['temp_bytes']/1e9:.1f}GB")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="qwen2.5-14b")
    ap.add_argument("--stages", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=32)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    ap.add_argument(
        "--calibrate", action="store_true",
        help="profile the config's real stage bodies into heterogeneous "
             "StageCosts + per-stage MemoryModel instead of the engine dry-run",
    )
    ap.add_argument(
        "--device-spec", default=None, metavar="SPECS_JSON",
        help="with --calibrate: price the profile offline for this "
             "specs/*.json part (method='spec') and run the full "
             "enumerate+tune search on the derived costs",
    )
    args = ap.parse_args()
    if args.device_spec and not args.calibrate:
        ap.error("--device-spec requires --calibrate")
    if args.calibrate:
        calibrate(args.config, args.stages, args.batch // args.microbatches,
                  args.seq, args.out, device_spec=args.device_spec)
        return
    run(args.config, args.stages, args.microbatches, args.k, args.batch,
        args.seq, args.out)


if __name__ == "__main__":
    main()
