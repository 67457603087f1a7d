"""Observability overhead benchmark: the <5%-on / zero-cost-off contract.

The telemetry layer (`repro.obs`) promises that attaching an
`Observability` to the engine costs under 5% of chunk wall time at smoke
size, and that the compiled computation is untouched either way.  This
suite runs the *same* engine workload twice — obs off, then obs on (full
timeline + metrics) — and records:

* ``obs_overhead_ratio`` — a *normalized verdict*, checked MODEL-class
  (rtol 1%) in `benchmarks.check_regression`: exactly ``1.0`` whenever the
  measured on/off wall ratio is within the 1.05 budget, else the raw ratio.
  Encoding the contract this way keeps the gate deterministic while the
  contract holds, yet any breach surfaces as a hard MODEL failure with the
  offending ratio in the diff;
* ``obs_overhead_raw`` + ``overhead_pct`` — the actual measured ratio,
  advisory (timing class) so the trend stays visible without flaking CI;
* ``timeline_events_per_chunk`` — events the instrumented host loop emits
  per chunk (deterministic: spans are structural), checked EXACT;
* ``n_compiles_on`` / ``n_compiles_off`` — both must be 1 (EXACT via
  ``n_jobs``-style structural check): obs must never force a recompile.

``--assert-overhead X`` turns the measured ratio into a hard local/CI
failure; the bench-smoke CI job runs with ``--assert-overhead 1.05``.
"""
from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.core.ising import IsingSystem
from repro.engine.driver import Engine, EngineConfig
from repro.obs import Observability

GROUP = "obs"


def _build(length: int, r: int, chunk_intervals: int, obs):
    system = IsingSystem(length=length, update="checkerboard")
    config = EngineConfig(
        # chunks must carry real device work (64 sweeps here) for the
        # ratio to measure the contract rather than dispatch noise: the
        # obs cost is per-chunk-constant (two spans and a few counters),
        # so microscopic chunks would inflate the ratio meaninglessly
        n_replicas=r, swap_interval=8, chunk_intervals=chunk_intervals,
        # donation off so the same state object can be re-run for repeats
        donate=False,
    )
    return Engine(system, config, obs=obs)


def _run_once(engine, state, sweeps: int) -> float:
    import time

    t0 = time.perf_counter()
    out_state, _ = engine.run(state, sweeps)
    jax.block_until_ready(out_state.pt)
    return time.perf_counter() - t0


def measure(length: int = 32, r: int = 8, chunk_intervals: int = 8,
            sweeps: int = 512, repeats: int = 15) -> dict:
    temps = np.geomspace(1.5, 4.5, r)
    key = jax.random.key(0)

    obs = Observability.create(timeline=True)
    eng_off = _build(length, r, chunk_intervals, None)
    eng_on = _build(length, r, chunk_intervals, obs)
    st_off = eng_off.init(key, temps)
    st_on = eng_on.init(key, temps)
    # warm both (pays the compile outside the timed region)
    _run_once(eng_off, st_off, sweeps)
    _run_once(eng_on, st_on, sweeps)
    # Interleave the timed runs and compare *minima*: contention (co-tenants,
    # frequency drift, GC) only ever adds time, so the minimum of each series
    # is its least-noisy estimate of true wall time, and interleaving makes
    # slow machine-state drift hit both series alike.  Sampling deep (15
    # repeats by default) is what makes a <5% effect measurable on a noisy
    # CI runner where single-run wall time swings +-5%.
    off, on = [], []
    for _ in range(repeats):
        off.append(_run_once(eng_off, st_off, sweeps))
        on.append(_run_once(eng_on, st_on, sweeps))
    wall_off, wall_on = min(off), min(on)
    ratio = wall_on / wall_off if wall_off > 0 else float("inf")
    n_chunks = float(obs.metrics.snapshot()
                     ["engine_chunks_total"]["samples"][0]["value"])
    # spans only — metadata/instant bookkeeping events are one-time, and
    # span count per chunk is structural (repro.engine.chunk and
    # repro.engine.dispatch per chunk, repro.engine.compile once), so the
    # per-chunk rate is deterministic at fixed config
    n_spans = sum(1 for ev in obs.timeline.events() if ev["ph"] == "X")
    return {
        "wall_off": wall_off,
        "wall_on": wall_on,
        "ratio": ratio,
        "events_per_chunk": round(n_spans / n_chunks, 6),
        "n_compiles_off": eng_off.n_compiles,
        "n_compiles_on": eng_on.n_compiles,
    }


def run(budget: float = 1.0, assert_overhead: float = 0.0) -> None:
    length, sweeps = (32, 512) if budget <= 1.0 else (48, 1024)
    m = measure(length=length, sweeps=sweeps)
    ratio = m["ratio"]
    # the MODEL-gated verdict: 1.0 while the contract holds, the raw ratio
    # (a guaranteed >1% drift) the moment it does not
    verdict = 1.0 if ratio <= 1.05 else ratio
    emit(
        f"obs_overhead_L{length}",
        m["wall_on"],
        derived=(
            f"off={m['wall_off'] * 1e3:.1f}ms on={m['wall_on'] * 1e3:.1f}ms "
            f"ratio={ratio:.3f}"
        ),
        group=GROUP,
        metrics={
            "obs_overhead_ratio": verdict,
            "obs_overhead_raw": ratio,
            "overhead_pct": (ratio - 1.0) * 100.0,
            "timeline_events_per_chunk": m["events_per_chunk"],
            "n_compiles_obs_off": m["n_compiles_off"],
            "n_compiles_obs_on": m["n_compiles_on"],
        },
    )
    write_bench_json(GROUP)
    if assert_overhead and ratio > assert_overhead:
        sys.exit(
            f"obs overhead ratio {ratio:.3f} exceeds the "
            f"--assert-overhead {assert_overhead} budget"
        )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--budget", type=float, default=1.0,
                    help=">1 runs the larger configuration")
    ap.add_argument("--assert-overhead", type=float, default=0.0,
                    help="fail (exit 1) if on/off wall ratio exceeds this")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(budget=args.budget, assert_overhead=args.assert_overhead)


if __name__ == "__main__":
    main()
