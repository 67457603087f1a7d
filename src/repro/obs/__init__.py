"""Unified observability: metrics registry + Perfetto timelines + exporters.

The subsystem behind "where does a PT round spend its time" (DESIGN.md
§Observability).  Three pieces:

* `repro.obs.metrics`  — thread-safe labeled counters/gauges/histograms with
  cheap snapshot semantics;
* `repro.obs.timeline` — span recorder emitting Chrome trace-event JSON
  (load in ui.perfetto.dev), per-thread + virtual tracks, flow arrows; every
  span also lands in a running `jax.profiler` trace, on its clock;
* `repro.obs.export`   — Prometheus text / canonical JSON exposition and
  the snapshot digest benchmarks stamp into their records.

`Observability` bundles one registry + one timeline into the single handle
instrumented components accept (`Engine(obs=...)`, `Scheduler(obs=...)`,
`ObsCallback`).  The overhead contract every consumer relies on:

* **off is structurally free** — components hold ``obs=None`` and guard
  every metric site with one `is None` check, and hold the `NULL` timeline,
  whose spans reach only a running profiler: no recorder objects, no dict
  churn, no extra device traffic (pinned by ``tests/test_obs.py``);
* **on is cheap and never waits for the device** — spans are one dict
  append, metrics one locked float op; the engine's per-chunk obs work is
  <5% of chunk wall time at smoke size (gated by
  ``benchmarks/obs_overhead.py`` in CI);
* **compiled computations are untouched** — instrumentation lives entirely
  in host loops; the mega-step jaxpr is byte-identical with obs on or off
  (pinned by ``tests/test_obs.py``).
"""
from __future__ import annotations

import dataclasses

from repro.obs.export import (
    snapshot_digest,
    to_json,
    to_prometheus,
    write_json,
    write_prometheus,
)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.timeline import NULL, NullTimeline, Timeline

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Timeline",
    "NullTimeline",
    "NULL",
    "Observability",
    "to_prometheus",
    "to_json",
    "snapshot_digest",
    "write_prometheus",
    "write_json",
]


@dataclasses.dataclass
class Observability:
    """One registry + one timeline: the handle instrumented code accepts.

    ``jax_profile_dir`` arms a one-shot `jax.profiler` window: the first
    `Engine.run` after arming runs under ``start_trace``/``stop_trace`` and
    lands a TensorBoard-loadable profile in the directory.  The window
    closes once the run has read its last chunk's statistics back, so it
    holds all of the run's device work; one run only — the profiler's own
    overhead must not pollute the rest of the timeline.
    """

    metrics: MetricsRegistry
    timeline: Timeline | NullTimeline
    jax_profile_dir: str | None = None
    _jax_profiling: bool = dataclasses.field(default=False, repr=False)

    @classmethod
    def create(cls, timeline: bool = True,
               jax_profile_dir: str | None = None) -> "Observability":
        return cls(
            metrics=MetricsRegistry(),
            timeline=Timeline() if timeline else NULL,
            jax_profile_dir=jax_profile_dir,
        )

    # -- one-shot jax.profiler window ------------------------------------------
    def start_jax_profile(self) -> bool:
        """Open the profiler window if armed and unused; True if opened.

        A profile that was asked for and cannot start fails the run: a run
        that silently carried on would report a profile that does not exist.
        """
        if self.jax_profile_dir is None or self._jax_profiling:
            return False
        import jax

        jax.profiler.start_trace(self.jax_profile_dir)
        self._jax_profiling = True
        self.timeline.instant("jax_profile_start", dir=self.jax_profile_dir)
        return True

    def stop_jax_profile(self) -> None:
        if not self._jax_profiling:
            return
        import jax

        try:
            jax.profiler.stop_trace()
        finally:
            self._jax_profiling = False
            # disarm: the window is one chunk, ever
            self.jax_profile_dir = None
        self.timeline.instant("jax_profile_stop")
