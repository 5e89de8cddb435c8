"""The op-name scopes of a profiler trace, and the program's own spans.

``bench.lib.xplane`` reduces a trace with ``jax.profiler.ProfileData``,
which shows event stats only. An operation's ``tf_op`` stat, the op-name
path of the JAX function that made it, named scopes included
(``jit(_drain)/while/body/bfs_topdown/cond/branch_1_fun/gather:``), sits in
the event's metadata. This module reads the ``.xplane.pb`` itself, with a
minimal copy of the XPlane schema over ``google.protobuf``, and gives the
same ``xplane.Trace`` as ``xplane.load`` (the same events, in whole
nanoseconds as ``ProfileData`` gives them) with each operation's path, and
with the program's host spans (``repro:``, made by ``repro.obs.span``)
beside the benchmark's, so that ``idle_gaps`` names the innermost of
either.

No per-layer metric reads it yet: the harness reduces its traces with
``xplane.load``.
"""
from __future__ import annotations

from dataclasses import dataclass

from bench.lib import xplane

PROGRAM_SPAN_PREFIX = "repro:"
TF_OP = "tf_op"

# the fields of tsl/profiler/protobuf/xplane.proto read here; a map field
# is a repeated entry message on the wire
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "XLine"),
               ("event_metadata", 4, "EventMetadataEntry"),
               ("stat_metadata", 5, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XEventMetadata": [("name", 2, "string"), ("stats", 5, "XStat")],
    "XStatMetadata": [("name", 2, "string")],
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string"),
              ("ref_value", 7, "uint64")],
}


@dataclass
class OpEvent(xplane.Event):
    path: str | None = None     # the ``tf_op`` path without its ``:type``


class ScopedTrace(xplane.Trace):
    """An ``xplane.Trace`` whose operations carry their op-name paths."""

    def scope_seconds(self, name: str) -> float:
        """Device seconds of the leaf operations whose path has a
        component equal to ``name`` (a ``jax.named_scope``), averaged over
        devices. The leaves are the operations with a path: the loop and
        conditional events that enclose them on the ops line carry none,
        so nothing counts twice."""
        if not self.ops:
            return 0.0
        return sum(sum(e.end - e.start for e in dev
                       if e.path and name in e.path.split("/"))
                   for dev in self.ops) / len(self.ops)

    def span_seconds(self, name: str) -> tuple[float, int]:
        """Host seconds of the spans named ``name`` in the window, and
        their count."""
        hits = [e.end - e.start for e in self.spans if e.name == name]
        return sum(hits), len(hits)


def _space_class():
    """The ``XSpace`` message class of ``_SCHEMA``, in a private pool."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    field = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": field.TYPE_STRING, "int64": field.TYPE_INT64,
              "uint64": field.TYPE_UINT64}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number)
            if kind in scalar:
                f.type, f.label = scalar[kind], field.LABEL_OPTIONAL
            else:
                f.type = field.TYPE_MESSAGE
                f.type_name = ".bench_xplane." + kind
                f.label = (field.LABEL_OPTIONAL if msg.endswith("Entry")
                           else field.LABEL_REPEATED)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _read_space(path: str):
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _paths(plane) -> dict[int, str]:
    """Event metadata id -> the ``tf_op`` path of that operation."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    tf_op = next((k for k, v in stat_names.items() if v == TF_OP), None)
    out = {}
    for entry in plane.event_metadata:
        for st in entry.value.stats:
            if st.metadata_id == tf_op:
                value = st.str_value or stat_names.get(st.ref_value, "")
                if value:
                    out[entry.key] = value.rpartition(":")[0] or value
    return out


def _events(line, names: dict, paths: dict | None = None) -> list[OpEvent]:
    """The events of one line; start and duration in whole nanoseconds,
    as ``ProfileData`` gives them."""
    paths = paths or {}
    out = []
    for e in line.events:
        start = line.timestamp_ns + e.offset_ps // 1000
        out.append(OpEvent(names.get(e.metadata_id, ""), start * 1e-9,
                           (start + e.duration_ps // 1000) * 1e-9,
                           paths.get(e.metadata_id)))
    return out


def _clip(evs, lo, hi):
    return [OpEvent(e.name, max(e.start, lo), min(e.end, hi), e.path)
            for e in evs if e.end > lo and e.start < hi]


def load(path: str) -> ScopedTrace:
    """Read an ``.xplane.pb`` and clip it to its ``bench:window`` span."""
    spans, ops, modules = [], [], []
    prefixes = (xplane.SPAN_PREFIX, PROGRAM_SPAN_PREFIX)
    for plane in _read_space(path).planes:
        host = plane.name == xplane.HOST_PLANE
        if not host and not xplane.DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        lines = {line.name: line for line in plane.lines}
        if host:
            for line in plane.lines:
                spans.extend(e for e in _events(line, names)
                             if e.name.startswith(prefixes))
            continue
        ops.append(_events(lines[xplane.OPS_LINE], names, _paths(plane))
                   if xplane.OPS_LINE in lines else [])
        modules.append(_events(lines[xplane.MODULES_LINE], names)
                       if xplane.MODULES_LINE in lines else [])
    windows = [s for s in spans if s.name == xplane.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {xplane.WINDOW_SPAN} span in the trace")
    lo, hi = windows[-1].start, windows[-1].end
    return ScopedTrace(window=(lo, hi),
                       ops=[_clip(d, lo, hi) for d in ops],
                       modules=[_clip(d, lo, hi) for d in modules],
                       spans=_clip(spans, lo, hi))
