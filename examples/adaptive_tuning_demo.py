"""Ada-Grouper online tuning demo (the paper's Fig-10 scenario, condensed).

A GPT-Medium 8-stage pipeline trains on a cluster whose links pass through
three network regimes (preempted -> exclusive -> preempted).  The
coordinator re-profiles every "interval" and switches among the kFkB
candidate plans; we print the choice trail and the realized throughput vs
a fixed 1F1B run.

Run:  PYTHONPATH=src python examples/adaptive_tuning_demo.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import efficiency
from repro.configs.gpt import GPT_CONFIGS, gpt_stage_costs
from repro.core import (
    AutoTuner,
    BurstyTrace,
    Candidate,
    Coordinator,
    Network,
    NetworkProfiler,
    RegimeTrace,
    ScheduleSpec,
    make_plan,
)

S, GB, SEQ = 8, 192, 1024


def costs_for(cand):
    c = gpt_stage_costs(GPT_CONFIGS["GPT-Medium"], S, cand.micro_batch_size, SEQ)
    eff = efficiency(cand.micro_batch_size) / efficiency(6)
    c.fwd_time = [t / eff for t in c.fwd_time]
    c.bwd_time = [t / eff for t in c.bwd_time]
    return c


def main():
    cands = []
    for k in (1, 2, 3, 4, 6):
        b = max(6 // k, 1)
        spec = ScheduleSpec(kind="kfkb", k=k, micro_batch_size=b)
        cands.append(Candidate(k, b, GB // b, make_plan(S, GB // b, spec=spec), 0.0))

    def link(a, b):
        seed = 31 * a + b
        heavy = lambda s: BurstyTrace(12.5e9, contended_frac=0.12,
                                      mean_free=0.3, mean_contended=0.9, seed=s)
        free = lambda s: BurstyTrace(12.5e9, contended_frac=0.7,
                                     mean_free=3.0, mean_contended=0.1, seed=s)
        return RegimeTrace([10.0, 22.0], [heavy(seed), free(seed + 5), heavy(seed + 9)])

    net = Network.build(S, link)
    tuner = AutoTuner(cands, costs_for, NetworkProfiler(net, window=4))

    class TrailHook:
        """Typed IterationHook: collect (start_s, plan, samples/s) rows."""

        def __init__(self):
            self.rows = []

        def on_iteration(self, rec):
            self.rows.append((round(rec.start, 1), rec.plan_name,
                              round(rec.samples_per_s, 1)))

    trail = TrailHook()
    coord = Coordinator(tuner, net, GB, tuning_interval=4.0, hooks=[trail])
    summary = coord.run(40)
    print("iteration trail (start_s, plan, samples/s):")
    last = None
    for t, plan, sps in trail.rows:
        if plan != last:
            print(f"  t={t:8.1f}s  -> switched to {plan}  ({sps} sps)")
            last = plan
    print(f"\nAda-Grouper overall: {summary.throughput:.1f} samples/s "
          f"({len(summary.tuning)} tuning rounds)")

    fixed = Coordinator(
        AutoTuner(cands[:1], costs_for, NetworkProfiler(net, window=4)),
        net, GB, tuning_interval=1e9,
    ).run(40)
    print(f"fixed 1F1B overall:  {fixed.throughput:.1f} samples/s")
    gain = summary.throughput / fixed.throughput - 1
    print(f"adaptive gain: {gain:+.1%}  (paper band: +4%..+30%)")
    assert gain > 0.0
    assert all(rec.chosen_spec.k > 1 for rec in summary.tuning), (
        "grouping should win under this cluster's traffic"
    )
    ks = {rec.chosen_spec.k for rec in summary.tuning}
    if len(ks) >= 2:
        print(f"plan switches observed across regimes: k in {sorted(ks)}")
    print("adaptive tuning demo OK")


if __name__ == "__main__":
    main()
