"""The program's own spans and op scopes in a traced run.

The program opens ``repro.*`` profiler spans around its host steps
(``repro.obs.span``), with counters as their arguments, and names parts of
its step programs with ``jax.named_scope``. :func:`of` gives the spans of
a :class:`~chipbench.trace_reduce.Trace` as ``(start_ns, end_ns, name,
args)`` on the trace's clock; :func:`idle_inside_ns` the device-idle time
inside each of a list of intervals; :func:`leaf_ops` the device's leaf
operations with the scope path XLA gave each (its ``tf_op``).

A trace loaded by ``trace_reduce.load`` does not carry these: they are read
from the newest ``*.xplane.pb`` under ``.chipbench_traces/``, and only if
its ``chipbench.window`` span is the trace's window. A hand-built trace
carries them as attributes ``program`` and ``ops``. A program without such
spans or scopes gives an empty list, and the readers then report nothing.
"""

from __future__ import annotations

import functools
from pathlib import Path
import re

from chipbench import trace_reduce

ROOT = Path(__file__).resolve().parent.parent
TRACES = ROOT / ".chipbench_traces"
PREFIX = "repro."

Span = tuple[int, int, str, dict]


def of(trace) -> list[Span]:
    """The ``repro.*`` host spans of ``trace``, in start order; [] where
    there are none or the file the trace came from cannot be told."""
    if not hasattr(trace, "program"):
        trace.program, trace.ops = _read(trace)
    return trace.program


def named(trace, name: str) -> list[Span]:
    """The spans called ``name`` that lie wholly inside the window."""
    a, b = trace.window()
    return [sp for sp in of(trace) if sp[2] == name and sp[0] >= a and sp[1] <= b]


def idle_inside_ns(trace, intervals) -> list[float]:
    """For each ``(start_ns, end_ns)``, clipped to the window, the time in
    which no operation ran on a device, averaged over the devices."""
    a, b = trace.window()
    busy = {d: trace.busy_intervals(d) for d in trace.devices}
    out = []
    for s, e in intervals:
        s, e = max(s, a), min(e, b)
        if e <= s:
            out.append(0.0)
            continue
        per = [(e - s) - trace_reduce.total(trace_reduce.intersect(iv, [(s, e)]))
               for iv in busy.values()]
        out.append(sum(per) / len(per))
    return out


def leaf_ops(trace) -> list[tuple[int, str]]:
    """``(duration_ps, tf_op)`` of every device operation that holds no
    other (a loop's body operations count, the loop does not)."""
    of(trace)
    return trace.ops


def in_scope(tf_op: str, scope: str) -> bool:
    """Whether ``scope`` is one of the names on ``tf_op``'s path
    (``jit(step)/while/body/attention/dot_general:dot``), bare or inside a
    transformation (``transpose(jvp(attention))``)."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/):]|$)", tf_op) is not None


# -- reading the recorded trace -------------------------------------------------------------


def _read(trace) -> tuple[list[Span], list[tuple[int, str]]]:
    path = trace_reduce.newest_xplane(TRACES) if TRACES.is_dir() else None
    if path is None:
        return [], []
    from jax.profiler import ProfileData

    spans, window = [], None
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace_reduce.WINDOW:
                    window = (int(e.start_ns), int(e.start_ns + e.duration_ns))
                elif e.name.startswith(PREFIX):
                    spans.append((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name,
                                  dict(e.stats)))
    if window != trace.window():
        return [], []
    return sorted(spans, key=lambda sp: (sp[0], -sp[1])), _leaf_ops(path.read_bytes())


def _leaf_ops(raw: bytes) -> list[tuple[int, str]]:
    """Leaf operations of the ``XLA Ops`` lines of every TPU plane, with the
    ``tf_op`` stat of their event metadata (which ``ProfileData`` does not
    expose)."""
    space = _xspace_class()()
    space.ParseFromString(raw)
    out = []
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {m.key: m.value.name for m in plane.stat_metadata}
        scope_of = {}
        for m in plane.event_metadata:
            for st in m.value.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    scope_of[m.key] = st.str_value or stat_names.get(st.ref_value, "")
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            events = sorted(
                ((ev.offset_ps, ev.offset_ps + ev.duration_ps, ev.metadata_id) for ev in line.events),
                key=lambda x: (x[0], -x[1]),
            )
            leaf = [True] * len(events)
            stack: list[int] = []
            for i, (s, e, _) in enumerate(events):
                while stack and events[stack[-1]][1] <= s:
                    stack.pop()
                if stack and e <= events[stack[-1]][1]:
                    leaf[stack[-1]] = False
                stack.append(i)
            out += [(e - s, scope_of.get(m, "")) for (s, e, m), keep in zip(events, leaf) if keep]
    return out


@functools.cache
def _xspace_class():
    """A message class for the parts of ``tsl.profiler.XSpace`` read here,
    built from its field numbers (``xplane.proto``); other fields are
    skipped as unknown."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto", package="chipbench_xplane")
    # a message type's name is a nested message; "[X]" a repeated one
    schema = {
        "XSpace": [("planes", 1, "[XPlane]")],
        "XPlane": [("name", 2, F.TYPE_STRING), ("lines", 3, "[XLine]"),
                   ("event_metadata", 4, "[EventMetadataEntry]"),
                   ("stat_metadata", 5, "[StatMetadataEntry]")],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64), ("value", 2, "XStatMetadata")],
        "XLine": [("name", 2, F.TYPE_STRING), ("events", 4, "[XEvent]")],
        "XEvent": [("metadata_id", 1, F.TYPE_INT64), ("offset_ps", 2, F.TYPE_INT64),
                   ("duration_ps", 3, F.TYPE_INT64)],
        "XEventMetadata": [("stats", 5, "[XStat]")],
        "XStat": [("metadata_id", 1, F.TYPE_INT64), ("str_value", 5, F.TYPE_STRING),
                  ("ref_value", 7, F.TYPE_UINT64)],
        "XStatMetadata": [("name", 2, F.TYPE_STRING)],
    }
    for name, fields in schema.items():
        msg = f.message_type.add(name=name)
        for fname, number, typ in fields:
            field = msg.field.add(name=fname, number=number, label=F.LABEL_OPTIONAL)
            if isinstance(typ, str):
                field.type = F.TYPE_MESSAGE
                field.type_name = f".chipbench_xplane.{typ.strip('[]')}"
                if typ.startswith("["):
                    field.label = F.LABEL_REPEATED
            else:
                field.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("chipbench_xplane.XSpace"))
