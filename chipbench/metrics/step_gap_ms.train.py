"""Median device-idle time between the end of one training step's program
and the start of the next, from the trace, over every device."""

import statistics


def read(*, trace, **_):
    if trace is None:
        return None
    gaps = [g for dev in trace.devices for g in trace.step_gaps_s(dev)]
    return statistics.median(gaps) * 1e3 if gaps else None
