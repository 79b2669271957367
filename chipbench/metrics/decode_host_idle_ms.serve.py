"""Median, over the traced decode ticks (the program's
``repro.serve.decode_tick`` spans), of the time inside a tick in which no
operation ran on a device: the tick's host work that leaves the chip
waiting."""

import statistics

from chipbench import program_spans


def read(*, trace, **_):
    if trace is None:
        return None
    ticks = program_spans.named(trace, "repro.serve.decode_tick")
    if not ticks:
        return None
    return statistics.median(program_spans.idle_inside_ns(trace, [sp[:2] for sp in ticks])) / 1e6
