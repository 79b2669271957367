"""Median, over the traced training steps (the program's
``repro.runtime.iteration`` spans), of the time inside a step in which no
operation ran on a device: the runtime's batch feed, launch and loss read
that leave the chip waiting."""

import statistics

from chipbench import program_spans


def read(*, trace, **_):
    if trace is None:
        return None
    steps = program_spans.named(trace, "repro.runtime.iteration")
    if not steps:
        return None
    return statistics.median(program_spans.idle_inside_ns(trace, [sp[:2] for sp in steps])) / 1e6
