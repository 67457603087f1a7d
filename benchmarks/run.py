# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure (DESIGN.md §8):

  fig3a/fig3b   convergence.py      magnetization & iterations-vs-size
  fig4/fig5     speedup.py          replica-parallel speed-up
  fig6          tile_sweep.py       block-size + sweeps-per-launch tile sweep
  fig7          swap_overhead.py    swap-interval cost + acceptance
  zoo           systems_bench.py    per-system sweep throughput (system zoo)
  ptlm          ptlm_bench.py       paper technique on the LM pool
  serve         serve_load.py       multi-tenant packed scheduler vs naive
                                    one-Session-per-job (jobs/sec, latency,
                                    jobs-packed-per-compile)
  roofline      roofline_report.py  §Roofline tables from the dry-run JSONs
  shard         shard_scaling.py    multi-device weak/strong scaling +
                                    collective bytes (invoke the module
                                    directly with --devices N for a
                                    simulated multi-device mesh)
  obs           obs_overhead.py     telemetry overhead: obs-on vs obs-off
                                    wall ratio (<5% contract) + per-chunk
                                    timeline event count
  faults        fault_recovery.py   injected-fault recovery/quarantine/
                                    degradation (deterministic counts EXACT,
                                    recovery wall-clock advisory)

Run: PYTHONPATH=src python -m benchmarks.run [--only fig7,...]
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import convergence, fault_recovery, obs_overhead
    from benchmarks import ptlm_bench, roofline_report, serve_load
    from benchmarks import shard_scaling, speedup, swap_overhead
    from benchmarks import systems_bench, tile_sweep

    suites = {
        "fig3": convergence.run,
        "fig45": speedup.run,
        "fig6": tile_sweep.run,
        "fig7": swap_overhead.run,
        "zoo": systems_bench.run,
        "ptlm": ptlm_bench.run,
        "roofline": roofline_report.run,
        "shard": shard_scaling.run,
        "serve": serve_load.run,
        "obs": obs_overhead.run,
        "faults": fault_recovery.run,
    }
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn()
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            print(f"{name}_FAILED,0,{type(e).__name__}")
        print(f"# suite {name} finished in {time.time()-t0:.1f}s", flush=True)
    if failed:
        sys.exit(f"benchmark suites failed: {failed}")


if __name__ == "__main__":
    main()
