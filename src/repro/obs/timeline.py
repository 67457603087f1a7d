"""Span recorder emitting Chrome trace-event JSON (DESIGN.md §Observability).

`Timeline` records spans, instants, counter series and flow arrows in the
Trace Event Format consumed by Perfetto (ui.perfetto.dev) and
``chrome://tracing``.  Design points:

* **tracks** — every event lands on a named track.  By default the track is
  the *current thread* (so the engine host loop, the serve scheduler thread
  and client threads separate naturally); an explicit ``track=`` gives
  virtual lanes (one per serve bucket, one per scheduler phase) that render
  as their own rows.  Tracks map to stable small ``tid``s with
  ``thread_name`` metadata events, which is all Perfetto needs.
* **complete events** — spans are single ``"ph": "X"`` records (timestamp +
  duration) rather than begin/end pairs: half the events, and a crashed run
  still yields a loadable file of everything that *finished*.
* **flow events** — ``"ph": "s"/"t"/"f"`` arrows stitch one logical object
  (a serve job: PENDING → RUNNING → DONE) across tracks.
* **one clock with the profiler** — every span also opens a
  `jax.profiler.TraceAnnotation` of the same name, so a running profiler
  (``jax.profiler.start_trace``, TensorBoard, xprof) holds the program's
  host spans beside the device's ops.  Timestamps are `time.time_ns()`, the
  clock the profiler stamps host events with, so a span's ``ts`` and its
  profiler event start at the same instant.  `now()` reads that clock for
  `Timeline.complete`, the one entry that records a span after the fact
  (and so cannot reach the profiler).
* **recording cost** — one dict append under a lock per event.  With
  observability off, components hold `NULL`, whose spans go to the
  profiler alone: they record nothing when no profiler runs.

`write()` lands atomically (tmp + rename).  The file passes
`repro.obs.check_trace` — the schema gate CI runs on the smoke timeline.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Timeline", "NullTimeline", "NULL", "ProfilerSpan", "now", "gc_spans"]


def now() -> float:
    """Seconds on the profiler's host clock (`time.time_ns`), the start that
    `Timeline.complete` takes."""
    return time.time_ns() * 1e-9


class ProfilerSpan(TraceAnnotation):
    """A span that only a running profiler sees (`NullTimeline.span`)."""

    __slots__ = ()

    def annotate(self, **kv) -> "ProfilerSpan":
        self.set_metadata(**kv)
        return self


class _Span:
    """Context manager recording one complete ("X") event on exit, and the
    same span in a running profiler."""

    __slots__ = ("_tl", "name", "cat", "track", "args", "_t0", "_prof")

    def __init__(self, tl: "Timeline", name, cat, track, args):
        self._tl = tl
        self.name = name
        self.cat = cat
        self.track = track
        self.args = args

    def __enter__(self):
        self._prof = ProfilerSpan(self.name, **(self.args or {}))
        self._t0 = now()
        self._prof.__enter__()
        return self

    def annotate(self, **kv) -> "_Span":
        """Attach extra args to the span before it closes."""
        if self.args is None:
            self.args = {}
        self.args.update(kv)
        self._prof.set_metadata(**kv)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.annotate(error=exc_type.__name__)
        self._prof.__exit__(exc_type, exc, tb)
        t1 = now()
        self._tl.complete(
            self.name, self._t0, t1 - self._t0,
            cat=self.cat, track=self.track, args=self.args,
        )
        return False


class Timeline:
    """Accumulates trace events; `write()` emits Perfetto-loadable JSON."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._pid = os.getpid()
        self._tracks: dict[str, int] = {}  # track name -> tid
        self.enabled = True

    # -- track bookkeeping -----------------------------------------------------
    def _tid(self, track: str | None) -> int:
        if track is None:
            track = threading.current_thread().name
        tid = self._tracks.get(track)
        if tid is None:
            with self._lock:
                tid = self._tracks.get(track)
                if tid is None:
                    tid = self._tracks[track] = len(self._tracks) + 1
                    self._events.append({
                        "name": "thread_name", "ph": "M", "pid": self._pid,
                        "tid": tid, "args": {"name": track},
                    })
        return tid

    def _ts(self, t: float | None = None) -> float:
        """Microseconds on the profiler's clock (``t`` in `now()` seconds)."""
        return (now() if t is None else t) * 1e6

    def _emit(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    # -- recording API ---------------------------------------------------------
    def span(self, name: str, cat: str = "engine", track: str | None = None,
             **args) -> _Span:
        """``with timeline.span("chunk", index=3): ...`` — one X event."""
        return _Span(self, name, cat, track, args or None)

    def complete(self, name: str, start: float, duration: float, *,
                 cat: str = "engine", track: str | None = None,
                 args: dict | None = None) -> None:
        """Record a finished span from an explicit start (`now()` seconds)
        and duration.  It reaches no profiler: use `span` wherever the work
        can sit inside a ``with`` block (the serve scheduler's bucket lanes
        and recoveries cannot)."""
        ev = {
            "name": name, "ph": "X", "cat": cat, "pid": self._pid,
            "tid": self._tid(track), "ts": self._ts(start),
            "dur": max(duration, 0.0) * 1e6,
        }
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name: str, cat: str = "engine",
                track: str | None = None, **args) -> None:
        ev = {
            "name": name, "ph": "i", "s": "t", "cat": cat, "pid": self._pid,
            "tid": self._tid(track), "ts": self._ts(),
        }
        if args:
            ev["args"] = args
        self._emit(ev)

    def counter(self, name: str, values: dict, track: str | None = None,
                cat: str = "engine") -> None:
        """A counter ("C") sample — renders as a stacked area chart."""
        self._emit({
            "name": name, "ph": "C", "cat": cat, "pid": self._pid,
            "tid": self._tid(track), "ts": self._ts(),
            "args": {k: float(v) for k, v in values.items()},
        })

    def _flow(self, ph: str, name: str, flow_id, track, args) -> None:
        ev = {
            "name": name, "ph": ph, "cat": "flow", "pid": self._pid,
            "tid": self._tid(track), "ts": self._ts(), "id": str(flow_id),
        }
        if ph == "f":
            ev["bp"] = "e"  # bind to the enclosing slice's end
        if args:
            ev["args"] = args
        self._emit(ev)

    def flow_start(self, name: str, flow_id, track: str | None = None, **args):
        self._flow("s", name, flow_id, track, args)

    def flow_step(self, name: str, flow_id, track: str | None = None, **args):
        self._flow("t", name, flow_id, track, args)

    def flow_end(self, name: str, flow_id, track: str | None = None, **args):
        self._flow("f", name, flow_id, track, args)

    # -- output ----------------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_dict(self) -> dict:
        return {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs.timeline"},
        }

    def write(self, path: str) -> str:
        """Atomically write the Chrome-trace JSON; returns the path.

        Safe to call repeatedly mid-run (each call rewrites the full file),
        which is how `ObsCallback` keeps a loadable timeline on disk even if
        the process dies between phases.
        """
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = f"{path}.{self._pid}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f)
        os.replace(tmp, path)
        return path


class NullTimeline:
    """API-compatible recorder that keeps nothing.

    Components that *sometimes* record hold this instead of branching on
    None at every site.  Every method returns immediately, except `span()`:
    it hands back a `ProfilerSpan`, so the span still reaches a running
    profiler and costs one small object when none runs.
    """

    enabled = False

    def span(self, name, cat="engine", track=None, **args):
        return ProfilerSpan(name, **args)

    def complete(self, *a, **k):
        pass

    def instant(self, *a, **k):
        pass

    def counter(self, *a, **k):
        pass

    def flow_start(self, *a, **k):
        pass

    def flow_step(self, *a, **k):
        pass

    def flow_end(self, *a, **k):
        pass

    def events(self):
        return []

    def __len__(self):
        return 0

    def to_dict(self):
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def write(self, path):
        raise RuntimeError(
            "NullTimeline records nothing; construct the Observability with "
            "timeline=True to write a trace file"
        )


NULL = NullTimeline()


class _GcSpans:
    """``repro.python.gc`` profiler spans from a `gc.callbacks` hook.

    Entered by every `Engine.run`; the hook is installed by the first
    entry and removed when the last one leaves.  A collection holds the
    GIL from its start to its stop, so one open span at a time suffices.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._users = 0
        self._open: ProfilerSpan | None = None

    def _hook(self, phase, info):
        if phase == "start":
            self._open = ProfilerSpan("repro.python.gc",
                                      generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __enter__(self):
        with self._lock:
            if self._users == 0:
                gc.callbacks.append(self._hook)
            self._users += 1
        return self

    def __exit__(self, *exc):
        with self._lock:
            self._users -= 1
            if self._users == 0:
                gc.callbacks.remove(self._hook)
        return False


gc_spans = _GcSpans()
