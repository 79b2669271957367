"""The decode ticks' share of the roofline: the least time the chip could
take for each untraced tick (the larger of its model FLOPs at peak and its
bytes at peak bandwidth: the parameters once, and each occupied slot's keys
and values up to its position), summed, over the ticks' host time."""


def read(*, summary, flops, config, peaks, **_):
    ticks = summary.get("ticks") or []
    if not ticks:
        return None
    least = sum(
        flops.least_seconds(
            flops.decode_flops(config, occ, pos),
            flops.decode_bytes(config, summary["param_bytes"], summary["kv_itemsize"], pos),
            peaks,
        )
        for _, occ, pos in ticks
    )
    return 100.0 * least / sum(t for t, _, _ in ticks)
