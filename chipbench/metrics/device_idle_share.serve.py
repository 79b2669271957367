"""Share of the traced window in which no operation ran on a device,
averaged over the cell's devices."""


def read(*, trace, **_):
    return None if trace is None else 100.0 * trace.idle_share()
