"""Share of the slots the traced decode ticks computed that served a
request: the sum of the ``occupied`` counter of the program's
``repro.serve.decode_tick`` spans over the sum of their ``max_slots``."""

from chipbench import program_spans


def read(*, trace, **_):
    if trace is None:
        return None
    ticks = program_spans.named(trace, "repro.serve.decode_tick")
    slots = sum(sp[3].get("max_slots", 0) for sp in ticks)
    return 100.0 * sum(sp[3].get("occupied", 0) for sp in ticks) / slots if slots else None
