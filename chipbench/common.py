"""Pieces both drivers use: the run context, the profiler slice, the compile
counter and the device memory peak."""

from __future__ import annotations

import dataclasses
from pathlib import Path
import sys
import time
from typing import Any, Callable

import jax

# A traced run profiles this many seconds of its window, starting halfway
# through it; host-clock readings leave those seconds out.
TRACE_SECONDS = 3.0


@dataclasses.dataclass
class Context:
    """Everything a driver needs to run one cell once."""

    workload: str
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    trace_dir: Path
    chips: int
    t_start: float  # perf_counter at process start
    log: Callable[[str], None] = lambda msg: print(f"[chipbench] {msg}", file=sys.stderr, flush=True)
    # a test replaces the program's timed path through this hook
    patch: Callable[[Any], None] | None = None


class Profile:
    """Starts the profiler once the window has run ``start`` seconds and
    stops it ``TRACE_SECONDS`` later; ``covers(a, b)`` says whether an
    interval of the window touched the traced slice."""

    def __init__(self, ctx: Context):
        self.on = ctx.trace
        self.dir = ctx.trace_dir
        self.start = ctx.seconds / 2.0
        self.end = self.start + TRACE_SECONDS
        self.state = "before"
        self._span = None
        self.t_on = self.t_stop = self.t_off = None

    def tick(self, now: float) -> None:
        if not self.on:
            return
        if self.state == "before" and now >= self.start:
            jax.profiler.start_trace(str(self.dir))
            self._span = jax.profiler.TraceAnnotation("chipbench.window")
            self._span.__enter__()
            self.state, self.t_on = "tracing", now
        elif self.state == "tracing" and now >= self.end:
            self.stop(now)

    def stop(self, now: float) -> None:
        """Stop tracing; the slice ends once the trace is written, which
        takes seconds."""
        if self.state == "tracing":
            self._span.__exit__(None, None, None)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.state, self.t_stop, self.t_off = "after", now, now + time.perf_counter() - t

    def inside(self, a: float, b: float) -> bool:
        """Whether ``[a, b]`` lies wholly in the traced slice (for the
        tracing overhead)."""
        return self.t_stop is not None and a >= self.t_on and b <= self.t_stop

    def covers(self, a: float, b: float) -> bool:
        if self.t_on is None:
            return False
        off = self.t_off if self.t_off is not None else float("inf")
        return a < off and b > self.t_on


class CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def clock() -> float:
    return time.perf_counter()
