"""Hardware-matrix conformance: the offline tuner slice per committed device spec.

For each ``specs/*.json`` part this derives the pinned workload's
(`specs/workloads/pinned-4stage.json`) per-stage :class:`StageCosts` and
:class:`MemoryModel` through :mod:`repro.core.devicespec` — pure float
arithmetic, no accelerator, no XLA — then runs the REAL adaptive search on
them: candidate enumeration against the part's capacity curve, the
:class:`AutoTuner` over a stable network at the part's link bandwidth, and
deterministic makespan simulation of the winner vs the 1F1B baseline.  The
resulting slice (derived seconds, candidate set with admitted ``w[s]`` and
``zb_policy[s]`` vectors, estimates, chosen ``ScheduleSpec`` coordinates,
makespan ratios) is compared field-for-field against a golden fixture in
``specs/golden/<spec>.json``.

This is the CI ``hardware-matrix`` job: any cost-model / enumeration /
tuner change that silently alters what the system would do on an H100, an
A100, a TPU v5e, or the two synthetic stress regimes (extreme
compute/memory skew, slow interconnect) fails the matrix — on hardware
nobody in CI owns.  Floats are rounded to 6 significant digits on both
sides, so the comparison is exact-by-construction for deterministic
arithmetic while immune to sub-ppm libm differences.

Usage:
  python benchmarks/hardware_matrix.py                     # check all specs
  python benchmarks/hardware_matrix.py --spec specs/h100-sxm.json --check
  python benchmarks/hardware_matrix.py --update            # regenerate goldens
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.core import (  # noqa: E402
    AutoTuner,
    NetworkProfiler,
    SearchSpace,
    StableTrace,
    enumerate_candidates,
    simulate_plan,
    uniform_network,
)
from repro.core.devicespec import (  # noqa: E402
    TASK_PROGRAMS,
    derive_memory_model,
    derive_stage_costs,
    load_device_spec,
    load_workload_profile,
)

SLICE_SCHEMA_VERSION = 1
GLOBAL_BATCH = 32
PINNED_WORKLOAD = os.path.join(_ROOT, "specs", "workloads", "pinned-4stage.json")
GOLDEN_DIR = os.path.join(_ROOT, "specs", "golden")

#: the matrix's pinned search space — every kind family plus both W
#: policies, capped at k=2 like the trajectory's seeded scenario
SPACE = SearchSpace(
    kinds=("kfkb", "zb_h1", "zb_h2", "zbv", "interleaved"),
    virtual_degrees=(2,),
    max_k=2,
    zb_policies=("double_remat", "saved_residual"),
)


def _round(value, sig: int = 6):
    """Round every float in a JSON-shaped value to ``sig`` significant digits."""
    if isinstance(value, bool):
        return value
    if isinstance(value, float):
        return float(f"{value:.{sig}g}")
    if isinstance(value, dict):
        return {k: _round(v, sig) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_round(v, sig) for v in value]
    return value


def conformance_slice(spec_path: str, workload_path: str = PINNED_WORKLOAD) -> dict:
    """Derive + enumerate + tune + simulate one part; fully deterministic."""
    spec = load_device_spec(spec_path)
    workload = load_workload_profile(workload_path)
    S = workload.num_stages
    base_costs = derive_stage_costs(workload, spec)
    mm = derive_memory_model(workload)
    limits = spec.limit_curve(S)
    cands = enumerate_candidates(S, GLOBAL_BATCH, mm, limits, space=SPACE)

    costs_by_b = {workload.micro_batch_size: base_costs}

    def costs_for(cand):
        b = cand.micro_batch_size
        if b not in costs_by_b:
            costs_by_b[b] = base_costs.scaled_to_microbatch(
                workload.micro_batch_size, b
            )
        return costs_by_b[b]

    def net():
        return uniform_network(
            S, lambda: StableTrace(spec.link_bandwidth_bytes_per_s)
        )

    tuner = AutoTuner(cands, costs_for, NetworkProfiler(net()))
    rec = tuner.tune(0.0)
    chosen = next(c for c in cands if c.name == rec.chosen)
    one_f1b = min(
        (c for c in cands if c.kind == "kfkb" and c.k == 1),
        key=lambda c: c.num_microbatches,
    )
    makespan_chosen = simulate_plan(
        chosen.plan, costs_for(chosen), net()
    ).pipeline_length
    makespan_1f1b = simulate_plan(
        one_f1b.plan, costs_for(one_f1b), net()
    ).pipeline_length

    return _round(
        {
            "schema_version": SLICE_SCHEMA_VERSION,
            "spec": spec.name,
            "workload": workload.name,
            "dtype": workload.dtype,
            "global_batch": GLOBAL_BATCH,
            "stage_seconds": {
                p: list(getattr(base_costs, f"{p}_time")) for p in TASK_PROGRAMS
            },
            "limit_curve_bytes": list(limits),
            "candidates": [
                {
                    "name": c.name,
                    "kind": c.kind,
                    "k": c.k,
                    "b": c.micro_batch_size,
                    "M": c.num_microbatches,
                    "num_virtual": c.plan.num_virtual,
                    "extra_warmup": list(c.plan.extra_warmup),
                    "zb_policy": list(c.plan.zb_policy),
                    "est_peak_bytes": c.est_peak_bytes,
                }
                for c in cands
            ],
            "estimates": dict(rec.estimates),
            "chosen": {
                "name": rec.chosen,
                "kind": rec.chosen_spec.kind,
                "k": rec.chosen_spec.k,
                "b": chosen.micro_batch_size,
                "num_virtual": rec.chosen_spec.num_virtual,
                "extra_warmup": list(rec.chosen_spec.extra_warmup),
                "zb_policy": list(rec.chosen_spec.zb_policy),
            },
            "makespan_s": {"chosen": makespan_chosen, "one_f1b": makespan_1f1b},
            "makespan_ratio_vs_1f1b": makespan_1f1b / makespan_chosen,
        }
    )


def golden_path(spec_path: str) -> str:
    stem = os.path.splitext(os.path.basename(spec_path))[0]
    return os.path.join(GOLDEN_DIR, f"{stem}.json")


def _diff(prefix: str, got, want, out: list[str]) -> None:
    if isinstance(want, dict) and isinstance(got, dict):
        for key in sorted(set(want) | set(got)):
            if key not in got:
                out.append(f"{prefix}.{key}: missing (golden has {want[key]!r})")
            elif key not in want:
                out.append(f"{prefix}.{key}: unexpected {got[key]!r}")
            else:
                _diff(f"{prefix}.{key}", got[key], want[key], out)
    elif isinstance(want, list) and isinstance(got, list):
        if len(got) != len(want):
            out.append(f"{prefix}: length {len(got)} != golden {len(want)}")
        for i, (g, w) in enumerate(zip(got, want)):
            _diff(f"{prefix}[{i}]", g, w, out)
    elif got != want:
        out.append(f"{prefix}: {got!r} != golden {want!r}")


def check_spec(spec_path: str) -> list[str]:
    """Diff the live slice against the committed golden (empty = conformant)."""
    record = conformance_slice(spec_path)
    gp = golden_path(spec_path)
    if not os.path.exists(gp):
        return [f"{gp}: golden fixture missing — run with --update and commit it"]
    with open(gp) as f:
        golden = json.load(f)
    out: list[str] = []
    _diff(os.path.basename(spec_path), record, golden, out)
    return out


def all_spec_paths() -> list[str]:
    return sorted(glob.glob(os.path.join(_ROOT, "specs", "*.json")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", action="append", default=None,
                    help="spec file(s) to run (default: all of specs/*.json)")
    ap.add_argument("--check", action="store_true",
                    help="fail on any drift vs specs/golden/<spec>.json")
    ap.add_argument("--update", action="store_true",
                    help="(re)write the golden fixtures from the live slices")
    args = ap.parse_args(argv)

    paths = args.spec or all_spec_paths()
    failures: list[str] = []
    for path in paths:
        if args.update:
            os.makedirs(GOLDEN_DIR, exist_ok=True)
            record = conformance_slice(path)
            with open(golden_path(path), "w") as f:
                json.dump(record, f, indent=1)
                f.write("\n")
            print(f"[hardware-matrix] wrote {golden_path(path)}")
            continue
        record = conformance_slice(path)
        chosen = record["chosen"]
        print(
            f"[hardware-matrix] {record['spec']}: chose {chosen['name']} "
            f"(kind={chosen['kind']} k={chosen['k']} b={chosen['b']} "
            f"zb={','.join(sorted(set(chosen['zb_policy'])))}) "
            f"ratio_vs_1f1b={record['makespan_ratio_vs_1f1b']}"
        )
        if args.check:
            diffs = check_spec(path)
            if diffs:
                failures.extend(diffs)
                print(f"[hardware-matrix] DRIFT on {os.path.basename(path)}:")
                for d in diffs[:20]:
                    print("  -", d)
                if len(diffs) > 20:
                    print(f"  ... and {len(diffs) - 20} more")
    if failures:
        print(f"[hardware-matrix] {len(failures)} drift(s) — if intentional, "
              f"regenerate with --update and commit the goldens")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
