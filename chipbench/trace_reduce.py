"""From a profiler trace to device intervals, idle gaps and their labels.

``load`` reads the newest ``*.xplane.pb`` under a directory with
``jax.profiler.ProfileData`` and keeps three things:

* per device (planes ``/device:TPU:<n>``): the intervals of its operations
  (line ``XLA Ops``) and of its program executions (line ``XLA Modules``);
* the host spans the benchmark opens around each call into the program,
  ``jax.profiler.TraceAnnotation`` events whose name starts ``chipbench.``;
* the traced window: the ``chipbench.window`` span.

Everything after that is arithmetic on ``(start_ns, end_ns, name)`` tuples,
so a hand-built :class:`Trace` exercises it as a recorded one does.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
import re

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|send|recv|"
    r"collective-broadcast|ragged-all-to-all)",
    re.IGNORECASE,
)
TOP = 10

Interval = tuple[int, int, str]


@dataclasses.dataclass
class Device:
    ops: list[Interval]
    modules: list[Interval]


@dataclasses.dataclass
class Trace:
    devices: dict[str, Device]
    host: list[Interval]  # chipbench.* spans

    # -- the window ------------------------------------------------------------

    def window(self) -> tuple[int, int]:
        spans = [(s, e) for s, e, n in self.host if n == WINDOW]
        if spans:
            return spans[0]
        ops = [iv for d in self.devices.values() for iv in d.ops]
        return min(s for s, _, _ in ops), max(e for _, e, _ in ops)

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) / 1e9

    # -- busy time -----------------------------------------------------------------

    def busy_intervals(self, dev: str, kind: str = "all") -> list[tuple[int, int]]:
        """Merged intervals, clipped to the window, in which an operation of
        ``kind`` (``all``, ``compute`` or ``collective``) ran on ``dev``."""
        a, b = self.window()
        ops = [
            (max(s, a), min(e, b))
            for s, e, n in self.devices[dev].ops
            if e > a and s < b and (kind == "all" or (kind == "collective") == is_collective(n))
        ]
        return merge(ops)

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        per = [total(self.busy_intervals(d)) for d in self.devices]
        return sum(per) / len(per) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def exposed_collective_s(self) -> float:
        """Seconds in which a collective runs on a device and no compute
        operation does, averaged over the devices."""
        per = []
        for d in self.devices:
            comp = self.busy_intervals(d, "compute")
            coll = self.busy_intervals(d, "collective")
            per.append(total(coll) - total(intersect(coll, comp)))
        return sum(per) / len(per) / 1e9

    # -- gaps and what the host did in them ----------------------------------------------

    def gaps(self, dev: str) -> list[tuple[int, int]]:
        a, b = self.window()
        busy = self.busy_intervals(dev)
        out, t = [], a
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if b > t:
            out.append((t, b))
        return out

    def label(self, s: int, e: int) -> str:
        """The innermost benchmark span that covers most of ``[s, e)``;
        ``host`` where none does."""
        best, best_key = "host", (0, 0)
        for hs, he, n in self.host:
            if n == WINDOW:
                continue
            overlap = min(e, he) - max(s, hs)
            if overlap > 0:
                key = (overlap, -(he - hs))
                if key > best_key:
                    best, best_key = n, key
        return best

    def labelled_gaps(self) -> list[tuple[str, float]]:
        """Every idle gap of every device, longest first, as (what the host
        was doing, seconds)."""
        out = [(self.label(s, e), (e - s) / 1e9) for d in self.devices for s, e in self.gaps(d)]
        return sorted(out, key=lambda x: -x[1])

    # -- programs (steps) ------------------------------------------------------------

    def module_runs(self, dev: str) -> list[Interval]:
        """Executions of the program that took most device time on ``dev``,
        in order: the step of a training cell, the decode tick of a serving
        one."""
        runs = self.devices[dev].modules
        if not runs:
            return []
        time_of: dict[str, int] = {}
        for s, e, n in runs:
            time_of[n] = time_of.get(n, 0) + (e - s)
        top = max(time_of, key=time_of.get)
        return sorted(r for r in runs if r[2] == top)

    def step_gaps_s(self, dev: str) -> list[float]:
        """Device-idle time between consecutive executions of the main
        program (from the end of one to the start of the next)."""
        runs = self.module_runs(dev)
        busy = self.busy_intervals(dev)
        out = []
        for (_, e0, _), (s1, _, _) in zip(runs, runs[1:]):
            out.append((s1 - e0 - total(intersect(busy, [(e0, s1)]))) / 1e9)
        return out

    def breakdown(self) -> dict:
        a, b = self.window()
        n = len(self.devices)
        by_op: dict[str, float] = {}
        for d in self.devices.values():
            for s, e, name in d.ops:
                if e > a and s < b:
                    by_op[name] = by_op.get(name, 0.0) + (min(e, b) - max(s, a)) / 1e9 / n
        ops = sorted(by_op.items(), key=lambda x: -x[1])[:TOP]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in self.labelled_gaps()[:TOP]],
        }


# -- interval arithmetic ----------------------------------------------------------------


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def merge(ivs) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def intersect(xs, ys) -> list[tuple[int, int]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    xs, ys = merge(xs), merge(ys)
    while i < len(xs) and j < len(ys):
        s, e = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if s < e:
            out.append((s, e))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(ivs) -> int:
    return sum(e - s for s, e in ivs)


# -- reading a recorded trace -------------------------------------------------------------


def newest_xplane(trace_dir: Path) -> Path | None:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def load(trace_dir: Path) -> Trace | None:
    """The trace under ``trace_dir``, or None where there is none or it has
    no device operation."""
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(str(path)))


def from_profile(data) -> Trace | None:
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = _events(lines.get("XLA Ops"))
            if ops:
                devices[plane.name] = Device(ops, _events(lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [iv for iv in _events(line) if iv[2].startswith("chipbench.")]
    if not devices:
        return None
    return Trace(devices, sorted(host))


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` (an operation's HLO text, as
    the TPU trace names it) -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> list[Interval]:
    if line is None:
        return []
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), short_name(e.name)) for e in line.events]
