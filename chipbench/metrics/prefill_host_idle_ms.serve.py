"""Median, over the traced prefilled requests (the program's
``repro.serve.prefill.request`` spans), of the time inside one in which
no operation ran on a device: the prompt draw, the row insertion and the
first-token read that leave the chip waiting."""

import statistics

from chipbench import program_spans


def read(*, trace, **_):
    if trace is None:
        return None
    requests = program_spans.named(trace, "repro.serve.prefill.request")
    if not requests:
        return None
    return statistics.median(program_spans.idle_inside_ns(trace, [sp[:2] for sp in requests])) / 1e6
