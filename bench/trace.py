"""From a profiler trace (``.xplane.pb``) to busy, idle, collective and
breakdown figures.

`load` reads the file through ``jax.profiler.ProfileData`` into plain
tuples: the device operations of each TPU plane and the harness's own host
spans (names starting ``bench.``, written with
``jax.profiler.TraceAnnotation``).  `reduce` clips them to the traced window
and returns a `Summary`.  Everything here is arithmetic on intervals; it
imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

# Lines of a device plane that hold one event per executed operation, in
# the order they are looked for.
OP_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all", re.IGNORECASE)
# Control flow whose body's ops are events of their own: left out of the
# breakdown, which would otherwise count the same time twice.
CONTROL_FLOW = frozenset({"while", "conditional", "call"})
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Events:
    """Device operations per chip and the harness's host spans, in ns."""

    devices: dict[int, list[tuple[str, float, float]]]  # chip -> (name, start, end)
    host: list[tuple[str, float, float]]


@dataclasses.dataclass
class Summary:
    """What a traced window reads, averaged over the chips that ran."""

    window_s: float
    busy_s: float  # union of operation intervals, mean over chips
    collective_s: float  # time in collective operations, mean over chips
    exposed_collective_s: float  # collective time with no other op running
    chips: int
    device_ops: list  # [[name, seconds per chip], ...], most time first
    idle_gaps: list  # [[host activity, seconds], ...], longest first

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s


def find_xplane(directory: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            for name in OP_LINES:
                if name in lines:
                    devices[int(m.group(1))] = [
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in lines[name].events
                    ]
                    break
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return Events(devices=devices, host=host)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover) -> list[tuple[float, float]]:
    """Parts of ``intervals`` (merged) that ``cover`` (merged) leaves open."""
    out = []
    j = 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]


def op_name(event_name: str) -> str:
    """The HLO name of a device event; TPU traces name an op by its whole
    HLO line (``%fusion.12 = f32[...] fusion(...), ...``)."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def op_family(name: str) -> str:
    """An operation's name without its instance number (``fusion.12`` ->
    ``fusion``), so that the breakdown groups one kind of operation."""
    return re.sub(r"[.\-_]\d+$", "", name)


def host_activity(spans, at: float) -> str:
    """The innermost harness span that covers ``at``; ``program`` where the
    host was in the program, outside every span of the harness."""
    best = None
    for name, a, b in spans:
        if a <= at <= b and name != WINDOW_SPAN and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "program"


def reduce(events: Events, window: tuple[float, float] | None = None) -> Summary:
    """Figures of the window: ``window`` in ns, else the ``bench.window`` span."""
    if window is None:
        spans = [(a, b) for n, a, b in events.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError("the trace holds no bench.window span")
        window = (min(a for a, _ in spans), max(b for _, b in spans))
    lo, hi = window
    if not events.devices:
        raise ValueError("the trace holds no device operations")
    busy = coll = exposed = 0.0
    per_op: dict[str, float] = {}
    gaps = []
    for chip, ops in sorted(events.devices.items()):
        ops = _clip(ops, lo, hi)
        merged = union((a, b) for _, a, b in ops)
        busy += length(merged)
        c_ops = union((a, b) for n, a, b in ops if COLLECTIVE.search(n))
        other = union((a, b) for n, a, b in ops if not COLLECTIVE.search(n))
        coll += length(c_ops)
        exposed += length(subtract(c_ops, other))
        for n, a, b in ops:
            fam = op_family(n)
            if fam not in CONTROL_FLOW:
                per_op[fam] = per_op.get(fam, 0.0) + (b - a)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    n = len(events.devices)
    gaps.sort(reverse=True)
    spans = _clip(events.host, lo, hi)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy / n * 1e-9,
        collective_s=coll / n * 1e-9,
        exposed_collective_s=exposed / n * 1e-9,
        chips=n,
        device_ops=[[k, v / n * 1e-9] for k, v in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[host_activity(spans, (a + b) / 2), d * 1e-9]
                   for d, a, b in gaps[:TOP]],
    )


def idle_share_pct(summary: Summary) -> float:
    """Per cent of the window in which no operation ran, mean over chips."""
    return 100.0 * summary.idle_s / summary.window_s
