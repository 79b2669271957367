"""Median host time of one decode tick behind its device sync, over the
window's untraced ticks."""

import statistics


def read(*, summary, **_):
    ticks = summary.get("ticks") or []
    return statistics.median(t for t, _, _ in ticks) * 1e3 if ticks else None
