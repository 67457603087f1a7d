"""Shared benchmark utilities: timing, CSV rows, machine-readable records.

``emit`` keeps the human-readable ``name,us_per_call,derived`` CSV contract
every suite prints, and — when a ``group`` is given — also accumulates the
row as a structured record.  ``write_bench_json`` then lands the group as
``BENCH_<group>.json`` (name, seconds, derived string, parsed metrics, jax
backend/version), which is what lets the perf trajectory accumulate across
PRs: CI runs the suites at smoke sizes and uploads the JSONs as artifacts.

Benchmark timers ride the same telemetry layer as the engine
(`repro.obs`): with ``BENCH_TIMELINE`` set (or `enable_obs()` called),
every ``time_call`` iteration lands as a span on a shared timeline,
``write_bench_json`` drops a ``BENCH_<group>.trace.json`` next to the
record file, and each record is stamped with the metrics-snapshot digest
(``obs_digest``) so a bench row is traceable to the telemetry captured in
the same process.  Obs off (the default) records nothing and stamps
nothing — baselines are digest-free and unaffected.
"""
from __future__ import annotations

import json
import os
import time

import jax

from repro.obs.timeline import now as timeline_now

# group -> list of record dicts, accumulated by `emit(..., group=...)`
_RECORDS: dict[str, list] = {}

# process-wide bench telemetry bundle (None = off, the default)
_OBS = None


def enable_obs(obs=None):
    """Attach a `repro.obs.Observability` to this bench process.

    Timers span onto its timeline and records are stamped with its metrics
    digest.  Called implicitly when ``$BENCH_TIMELINE`` is set.
    """
    global _OBS
    if obs is None:
        from repro.obs import Observability

        obs = Observability.create(timeline=True)
    _OBS = obs
    return obs


def get_obs():
    """The active bench bundle, auto-enabled from ``$BENCH_TIMELINE``."""
    if _OBS is None and os.environ.get("BENCH_TIMELINE"):
        enable_obs()
    return _OBS


def time_call(fn, *args, warmup: int = 1, iters: int = 3, span: str | None = None):
    """Median wall time of fn(*args) in seconds (blocks on results).

    With bench telemetry enabled every timed iteration is recorded as a
    span named ``span`` (default: the callable's name) on a ``bench``
    track — the same timeline engine spans land on, so a bench run's
    timing and its engine activity line up in one Perfetto view.
    """
    obs = get_obs()
    tl = obs.timeline if obs is not None else None
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    name = span or getattr(fn, "__name__", "call")
    for _ in range(iters):
        start = timeline_now()
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        times.append(dt)
        if tl is not None:
            tl.complete(name, start, dt, cat="bench", track="bench")
    times.sort()
    return times[len(times) // 2]


def emit(
    name: str,
    seconds: float,
    derived: str = "",
    group: str | None = None,
    metrics: dict | None = None,
):
    """One CSV row: name,us_per_call,derived.

    With ``group``, the row is also accumulated as a machine-readable record
    (plus any ``metrics`` — numeric derived values that would otherwise only
    exist inside the ``derived`` display string) for `write_bench_json`.
    """
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)
    if group is not None:
        record = {"name": name, "seconds": seconds, "derived": derived}
        if metrics:
            record["metrics"] = {k: float(v) for k, v in metrics.items()}
        obs = get_obs()
        if obs is not None:
            # provenance stamp, NOT a metric: top-level record keys are
            # invisible to check_regression, so digest churn can never
            # trip the baseline gate
            from repro.obs import snapshot_digest

            record["obs_digest"] = snapshot_digest(obs.metrics.snapshot())
        _RECORDS.setdefault(group, []).append(record)


def write_bench_json(group: str, out_dir: str | None = None) -> str:
    """Write the group's accumulated records to ``BENCH_<group>.json``.

    ``out_dir`` defaults to ``$BENCH_OUT_DIR`` or the working directory.
    Returns the written path; the write is atomic (tmp + rename) so a
    crashed suite never leaves a truncated record file behind.  Writing
    drains the group's accumulator, so a suite run twice in one process
    produces two clean files instead of one with duplicated records.
    """
    out_dir = out_dir or os.environ.get("BENCH_OUT_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    payload = {
        "group": group,
        "jax_backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "created_unix": time.time(),
        # drained only after the rename lands (below): a failed write leaves
        # the accumulator intact, so the caller can retry without losing rows
        "records": list(_RECORDS.get(group, [])),
    }
    obs = get_obs()
    if obs is not None:
        from repro.obs import snapshot_digest

        payload["obs_digest"] = snapshot_digest(obs.metrics.snapshot())
    path = os.path.join(out_dir, f"BENCH_{group}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    _RECORDS.pop(group, None)
    if obs is not None and getattr(obs.timeline, "enabled", False):
        obs.timeline.write(os.path.join(out_dir, f"BENCH_{group}.trace.json"))
    return path
