"""The program's own names in a profiler trace: host spans and op scopes.

Host spans are the events that ``jax.profiler.TraceAnnotation`` writes on
the host planes, on the device trace's clock: the serve loop's phases
(``repro/serve/<phase>``), the monitor's stages (``repro/ingest/<stage>``)
and the ``Tracer``'s Chimbuko events (``serve/decode_step``).  They come
from ``lib.trace.load``'s ``host`` list.

Op scopes are the ``jax.named_scope`` names of the op that ran, which
``lib.trace.load`` does not keep; ``read_xspace`` and ``scope_share``
read them.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import List, Optional, Sequence, Tuple

from lib import trace as T


def host_spans(trace: dict, name: str) -> List[Tuple[float, float]]:
    """(start, end) of every host event named ``name``."""
    return [(s, s + d) for _thread, n, s, d in trace["host"] if n == name]


def overlap(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under_s(dev: dict, spans: Sequence[Tuple[float, float]]) -> float:
    """Seconds inside ``spans`` in which the device ran no op."""
    u = T.union(spans)
    return (sum(e - s for s, e in u) - overlap(u, T.busy_intervals(dev))) / 1e9


def per_step_ms(trace: dict, name: str):
    """Summed duration of the host spans ``name`` per ``serve/decode_step``
    span, in ms; None where the trace has neither."""
    steps = len(host_spans(trace, "serve/decode_step"))
    spans = host_spans(trace, name)
    if not steps or not spans:
        return None
    return sum(e - s for s, e in spans) / 1e6 / steps


# ------------------------------------------------------------------ op scopes
# A profiler trace keeps an op's name stack (its ``jax.named_scope``s, as
# in ``jit(decode_step)/decode/cast_params/convert_element_type:``) in the
# stat ``tf_op`` of the op's event *metadata*, and its program in the stat
# ``program_id``; ``ProfileData`` shows only an event's own stats, so the
# ``.xplane.pb`` is read here with a message class of the part of
# tsl/profiler/protobuf/xplane.proto that is used (field numbers as there).
_XPLANE_FIELDS = {
    "XStat": [("metadata_id", 1, "int64"), ("uint64_value", 3, "uint64"),
              ("int64_value", 4, "int64"), ("str_value", 5, "string"),
              ("ref_value", 7, "uint64")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "*XEvent")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"), ("stats", 5, "*XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "*XLine"),
               ("event_metadata", 4, "*EventMetadataEntry"),
               ("stat_metadata", 5, "*StatMetadataEntry")],
    "XSpace": [("planes", 1, "*XPlane")],
}


@functools.cache
def xspace_class():
    """The message class of an ``XSpace`` holding the fields above."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    pool = descriptor_pool.DescriptorPool()
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane",
                                           syntax="proto3")
    for msg, fields in _XPLANE_FIELDS.items():
        m = f.message_type.add(name=msg)
        for name, number, kind in fields:
            repeated, kind = kind.startswith("*"), kind.lstrip("*")
            fld = m.field.add(name=name, number=number,
                              label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if kind in _XPLANE_FIELDS:
                fld.type, fld.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
            else:
                fld.type = getattr(F, f"TYPE_{kind.upper()}")
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_xspace(log_dir: str):
    """The newest ``.xplane.pb`` under ``log_dir``, parsed; None if none."""
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return None
    space = xspace_class()()
    with open(paths[-1], "rb") as f:
        space.ParseFromString(f.read())
    return space


def _program_of(module_name: str) -> str:
    """``jit_decode_step(1435...)`` -> ``1435...``, the ops' ``program_id``."""
    return module_name[module_name.rfind("(") + 1 : -1]


def scope_share(space, program: str, scope: str) -> Optional[float]:
    """Device time of the ops whose name stack holds ``scope``, in the
    executions of the programs whose name holds ``program``, over those
    executions' device time, in %, over the trace's devices; None where no
    op of those programs carries ``scope`` (a program without the scope, or
    one loaded from a compilation cache that ignores op metadata)."""
    scoped_ps = program_ps = 0
    for plane in space.planes:
        if not T.DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        lines = {line.name: line for line in plane.lines}
        programs = set()
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines else ():
            name = meta[ev.metadata_id].name
            if program in name:
                programs.add(_program_of(name))
                program_ps += ev.duration_ps
        scoped = set()
        for mid, md in meta.items():
            stats = {names.get(s.metadata_id): s for s in md.stats}
            prog, stack = stats.get("program_id"), stats.get("tf_op")
            if prog is None or stack is None:
                continue
            if str(prog.uint64_value or prog.int64_value) not in programs:
                continue
            text = stack.str_value or names.get(stack.ref_value, "")
            if scope in text.split("/"):
                scoped.add(mid)
        if scoped and "XLA Ops" in lines:
            scoped_ps += sum(ev.duration_ps for ev in lines["XLA Ops"].events
                             if ev.metadata_id in scoped)
    if not scoped_ps or not program_ps:
        return None
    return 100.0 * scoped_ps / program_ps
