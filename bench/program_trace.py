"""The program's own marks in a profiler trace: its host spans and the
named scopes of its device ops.

The program names its host work ``repro.<layer>.<what>`` (spans opened with
``jax.profiler.TraceAnnotation``) and its device phases with
``jax.named_scope`` (``pt.sweep``, ``pt.rng``, ``pt.exchange``, ``pt.stats``,
``pt.observe``), which XLA keeps in each op's ``op_name`` metadata.
``jax.profiler.ProfileData`` shows the spans but not the metadata, so this
module decodes the ``.xplane.pb`` itself, with a schema of the few XSpace
fields it reads (the file is a ``tensorflow.profiler.XSpace`` protobuf).

It reads the same ops and window as `bench.trace`: the ``XLA Ops`` line of
each TPU plane, clipped to the ``bench.window`` span.  Busy time is the
union of all those ops; a scope's time is the union of its ops with control
flow (``while``, ``conditional``, ``call``) left out, since their bodies'
ops are events of their own.  Every share is a union of intervals over a
union that holds it, so none can pass 100%.  It imports nothing of the
program, and returns None for a mark the program does not make.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
from pathlib import Path

from bench.trace import CONTROL_FLOW, DEVICE_PLANE, OP_LINES, WINDOW_SPAN, \
    length, op_family, op_name, subtract, union

TRACE_ROOT = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"
SPAN_PREFIX = "repro."
SCOPE_PREFIX = "pt."


def _schema():
    """Message classes for the XSpace fields read here (field numbers as in
    ``tsl/profiler/protobuf/xplane.proto``); the parser skips the rest."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                           package="bench_xplane", syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, kind, repeated, type_name in fields:
            field = m.field.add(name=fname, number=number, type=kind,
                                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if type_name:
                field.type_name = ".bench_xplane." + type_name

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("double_value", 2, F.TYPE_DOUBLE, False, None),
            ("uint64_value", 3, F.TYPE_UINT64, False, None),
            ("int64_value", 4, F.TYPE_INT64, False, None),
            ("str_value", 5, F.TYPE_STRING, False, None),
            ("ref_value", 7, F.TYPE_UINT64, False, None))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("offset_ps", 2, F.TYPE_INT64, False, None),
            ("duration_ps", 3, F.TYPE_INT64, False, None),
            ("stats", 4, F.TYPE_MESSAGE, True, "XStat"))
    message("XLine", ("name", 2, F.TYPE_STRING, False, None),
            ("timestamp_ns", 3, F.TYPE_INT64, False, None),
            ("events", 4, F.TYPE_MESSAGE, True, "XEvent"))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, False, None),
            ("name", 2, F.TYPE_STRING, False, None),
            ("stats", 5, F.TYPE_MESSAGE, True, "XStat"))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, False, None),
            ("name", 2, F.TYPE_STRING, False, None))
    # a proto map is a repeated entry message on the wire
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False, None),
            ("value", 2, F.TYPE_MESSAGE, False, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False, None),
            ("value", 2, F.TYPE_MESSAGE, False, "XStatMetadata"))
    message("XPlane", ("name", 2, F.TYPE_STRING, False, None),
            ("lines", 3, F.TYPE_MESSAGE, True, "XLine"),
            ("event_metadata", 4, F.TYPE_MESSAGE, True, "EventMetadataEntry"),
            ("stat_metadata", 5, F.TYPE_MESSAGE, True, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, True, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


@dataclasses.dataclass
class ProgramTrace:
    """Device ops with their scopes, and host spans, in ns on one clock."""

    # chip -> [(hlo name, op_name scope path, start, end)]
    devices: dict[int, list[tuple[str, str, float, float]]]
    # [(span name, host line, start, end)], program and harness spans
    host: list[tuple[str, str, float, float]]


def find_newest(root: Path | None = None) -> str | None:
    """The newest ``.xplane.pb`` under ``root``, by default the benchmark's
    trace directory (each traced run clears its cell's directory first), or
    None."""
    root = TRACE_ROOT if root is None else root
    found = glob.glob(os.path.join(str(root), "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=4)
def load(path: str) -> ProgramTrace:
    space = _schema()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    devices: dict[int, list] = {}
    host: list = []
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            scope_of = {}
            for entry in plane.event_metadata:
                scope_of[entry.key] = next(
                    (_stat_str(s, stat_names) for s in entry.value.stats
                     if stat_names.get(s.metadata_id) == "tf_op"), "")
            names = {e.key: e.value.name for e in plane.event_metadata}
            lines = {line.name: line for line in plane.lines}
            for line_name in OP_LINES:
                if line_name in lines:
                    line = lines[line_name]
                    devices[int(m.group(1))] = [
                        (op_name(names.get(e.metadata_id, "")),
                         scope_of.get(e.metadata_id, ""),
                         *_interval(line, e)) for e in line.events]
                    break
        elif plane.name.startswith("/host:"):
            names = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for e in line.events:
                    name = names.get(e.metadata_id, "")
                    if name.startswith((SPAN_PREFIX, "bench.")):
                        host.append((name, line.name, *_interval(line, e)))
    return ProgramTrace(devices=devices, host=host)


def _stat_str(stat, stat_names) -> str:
    return stat.str_value or stat_names.get(stat.ref_value, "")


def _interval(line, event) -> tuple[float, float]:
    start = line.timestamp_ns + event.offset_ps * 1e-3
    return start, start + event.duration_ps * 1e-3


def scopes(path: str) -> tuple[str, ...]:
    """The program's scopes in an op's ``op_name`` path, outermost first
    (the profiler writes the path as ``<op_name>:<op type>``)."""
    head, colon, tail = path.rpartition(":")
    if colon and "/" not in tail:
        path = head
    return tuple(p for p in path.split("/") if p.startswith(SCOPE_PREFIX))


def window(trace: ProgramTrace) -> tuple[float, float]:
    spans = [(a, b) for n, _, a, b in trace.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return min(a for a, _ in spans), max(b for _, b in spans)


def _clipped_ops(trace: ProgramTrace, lo: float, hi: float):
    for chip, ops in sorted(trace.devices.items()):
        yield chip, [(n, s, max(a, lo), min(b, hi)) for n, s, a, b in ops
                     if b > lo and a < hi]


def scope_split(trace: ProgramTrace) -> dict[str, float] | None:
    """Per cent of busy device time under each innermost program scope, and
    ``""`` for ops under none; None when no op carries a program scope.
    Busy time is summed over chips."""
    lo, hi = window(trace)
    busy = 0.0
    split: dict[str, float] = {}
    for _, ops in _clipped_ops(trace, lo, hi):
        busy += length(union((a, b) for _, _, a, b in ops))
        parts: dict[str, list] = {}
        for n, s, a, b in ops:
            if op_family(n) not in CONTROL_FLOW:
                parts.setdefault((scopes(s) or ("",))[-1], []).append((a, b))
        for k, v in parts.items():
            split[k] = split.get(k, 0.0) + length(union(v))
    if busy <= 0 or not any(split):
        return None
    return {k: 100.0 * v / busy for k, v in split.items()}


def device_share(trace: ProgramTrace, scope: str) -> float | None:
    """Per cent of busy device time (summed over chips) in ops whose scope
    path holds ``scope``; None when no op carries a program scope."""
    lo, hi = window(trace)
    busy = inside = 0.0
    scoped = False
    for _, ops in _clipped_ops(trace, lo, hi):
        busy += length(union((a, b) for _, _, a, b in ops))
        hits = []
        for n, s, a, b in ops:
            found = scopes(s)
            scoped = scoped or bool(found)
            if scope in found and op_family(n) not in CONTROL_FLOW:
                hits.append((a, b))
        inside += length(union(hits))
    if not scoped or busy <= 0:
        return None
    return 100.0 * inside / busy


def idle_in_spans(trace: ProgramTrace, prefix: str, leave_out: str) -> float | None:
    """Per cent of the window in which a chip idles while the host's window
    thread is in a span named ``prefix...`` but not in ``leave_out`` (which
    nests inside them); mean over chips.  None without such spans."""
    lo, hi = window(trace)
    thread = next(t for n, t, _, _ in trace.host if n == WINDOW_SPAN)
    on_thread = [(n, max(a, lo), min(b, hi)) for n, t, a, b in trace.host
                 if t == thread and b > lo and a < hi]
    inside = union((a, b) for n, a, b in on_thread
                   if n.startswith(prefix) and n != leave_out)
    if not inside:
        return None
    cover = subtract(inside, union((a, b) for n, a, b in on_thread if n == leave_out))
    shares = []
    for _, ops in _clipped_ops(trace, lo, hi):
        idle = subtract([(lo, hi)], union((a, b) for _, _, a, b in ops))
        shares.append(length(_intersect(idle, cover)))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares) / (hi - lo)


def _intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def read_newest(reduce, *args):
    """``reduce(trace, *args)`` on the run's own profile (the newest under
    ``.bench_out/trace``); None where there is none."""
    path = find_newest()
    return None if path is None else reduce(load(path), *args)
