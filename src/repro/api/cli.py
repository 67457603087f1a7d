"""``python -m repro`` — run, resume and validate PT runs from spec JSONs.

Subcommands (DESIGN.md §API):

  run SPEC.json [--out DIR]     execute a `RunSpec` end-to-end; write
                                ``manifest.json`` (+ spec copy, checkpoints)
  resume DIR                    continue a checkpointed run from
                                ``(spec.json, newest checkpoint)`` alone
  validate SYSTEM [...]         conformance-run a system-zoo entry against
                                its exact reference (exit 1 on failure);
                                --exchange gates a non-default strategy,
                                --fused the interval-fused kernel path
  serve SPEC.json [...]         multi-tenant scheduler: submit --jobs seed
                                variants of each spec, pack same-shaped jobs
                                into one compiled mega-step (`repro.serve`),
                                write per-job results + service counters
  list-systems                  registered systems, params and observables
  list-strategies               registered replica-exchange strategies

The CLI is a thin shell over `repro.api.Session` — a spec executes
identically from here, a script, a test, or a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

from repro.api.session import (
    CheckpointCallback,
    ObsCallback,
    ProgressCallback,
    Session,
)
from repro.api.spec import RunSpec
from repro.compile_cache import enable_compile_cache

__all__ = ["main"]


def _cmd_run(args) -> int:
    with open(args.spec) as f:
        spec = RunSpec.from_json(f.read())
    if args.mesh_chains > 0 or args.mesh_replicas > 0:
        # command-line mesh override: run the same spec sharded without
        # editing the JSON (simulate devices on CPU with
        # XLA_FLAGS=--xla_force_host_platform_device_count=N)
        from repro.core.distributed import MeshSpec

        mesh = MeshSpec(
            ensemble=max(args.mesh_chains, 1),
            replica=max(args.mesh_replicas, 1),
        )
        spec = dataclasses.replace(
            spec, engine=dataclasses.replace(spec.engine, mesh=mesh)
        )
    out = args.out or os.path.join(
        "runs", os.path.splitext(os.path.basename(args.spec))[0]
    )
    os.makedirs(out, exist_ok=True)
    callbacks = []
    if not args.quiet:
        callbacks.append(ProgressCallback(every=args.progress_every))
    ckpt = CheckpointCallback(
        os.path.join(out, "checkpoints"), every_chunks=args.checkpoint_every
    )
    callbacks.append(ckpt)
    obs_cb = None
    if args.timeline or args.metrics_out or args.jax_profile:
        obs_cb = ObsCallback(
            timeline_path=args.timeline,
            metrics_path=args.metrics_out,
            jax_profile_dir=args.jax_profile,
        )
        callbacks.append(obs_cb)
    session = Session(
        spec, callbacks=callbacks, strict_kernels=args.strict_kernels
    )
    result = session.run()
    path = result.write_manifest(os.path.join(out, "manifest.json"))
    if obs_cb is not None:
        for kind, p in sorted(obs_cb.write().items()):
            if not args.quiet:
                print(f"{kind}: {p}", file=sys.stderr)
    if not args.quiet:
        temps = 1.0 / np.asarray(result.state.betas, np.float64)
        print(f"final ladder: {np.round(temps, 4).tolist()}", file=sys.stderr)
    print(path)
    return 0


def _cmd_resume(args) -> int:
    ckdir = os.path.join(args.dir, "checkpoints")
    callbacks = [] if args.quiet else [ProgressCallback(every=args.progress_every)]
    callbacks.append(
        CheckpointCallback(ckdir, every_chunks=args.checkpoint_every)
    )
    session = Session.from_checkpoint(ckdir, callbacks=callbacks)
    if session.remaining_sweeps == 0:
        print(
            f"nothing to resume: the checkpointed run already covers all "
            f"{session.spec.schedule.total_sweeps} scheduled sweeps",
            file=sys.stderr,
        )
        return 0
    result = session.run()
    path = result.write_manifest(os.path.join(args.dir, "manifest.json"))
    print(path)
    return 0


def _cmd_validate(args) -> int:
    # Lazy import: validate builds on the api layer (conformance compiles
    # zoo entries to RunSpecs), so importing it at module scope would cycle.
    from repro.core import systems
    from repro.validate import assert_conforms, run_conformance

    if args.system not in systems.REGISTRY:
        print(
            f"unknown system {args.system!r}; registered: "
            f"{sorted(systems.REGISTRY)}",
            file=sys.stderr,
        )
        return 2
    from repro.exchange import available_strategies

    if args.exchange not in available_strategies():
        print(
            f"unknown exchange strategy {args.exchange!r}; registered: "
            f"{available_strategies()}",
            file=sys.stderr,
        )
        return 2
    entry = systems.REGISTRY[args.system]
    # use_pallas rides along so the gate exercises the fused *kernel* (its
    # interpret path off-TPU), not just the pure-JAX fused reference
    system_params = (
        {"use_fused": True, "use_pallas": True} if args.fused else None
    )
    if args.fused:
        try:
            systems.make_system(
                entry.name, {**entry.params, **system_params}
            )
        except TypeError:
            print(
                f"system {args.system!r} has no fused kernel path "
                "(no use_fused constructor option)",
                file=sys.stderr,
            )
            return 2
    report = run_conformance(
        entry, seed=args.seed, exchange=args.exchange,
        system_params=system_params,
    )
    worst_series, worst_z = report.worst()
    kernel = " fused" if args.fused else ""
    print(
        f"{args.system} [{args.exchange}{kernel}]: {report.n_batches} batch means, "
        f"ladder retuned {report.n_retunes}x, "
        f"worst |z| = {worst_z:.2f} ({worst_series})"
    )
    for k in sorted(report.means):
        for r, t in enumerate(report.temps):
            print(
                f"  T={t:7.3f}  <{k}> = {report.means[k][r]: .5f} "
                f"(exact {report.exact[k][r]: .5f}, |z|={abs(report.z[k][r]):.2f})"
            )
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        path = os.path.join(args.out, f"validate_{args.system}.json")
        payload = {"system": args.system, "seed": args.seed,
                   "exchange": args.exchange, "fused": bool(args.fused)}
        for f in dataclasses.fields(report):
            v = getattr(report, f.name)
            if isinstance(v, dict):
                v = {k: np.asarray(a, np.float64).tolist() for k, a in v.items()}
            elif isinstance(v, np.ndarray):
                v = v.tolist()
            payload[f.name] = v
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(path)
    try:
        assert_conforms(report)
    except AssertionError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print("PASS: all observables within tolerance of the exact reference")
    return 0


def _cmd_serve(args) -> int:
    # Lazy import: the serve layer builds on api (Session-equivalent packing),
    # importing it at module scope would cycle through repro.api.
    from repro.serve import JobFailedError, Scheduler

    out = args.out or "runs/serve"
    obs = None
    if args.timeline:
        from repro.obs import Observability

        obs = Observability.create(timeline=True)
    metrics_path = args.metrics_out or os.path.join(out, "metrics.prom")
    sched = Scheduler(
        checkpoint_dir=args.checkpoint_dir,
        quantum_chunks=args.quantum_chunks,
        pack_window=args.pack_window,
        checkpoint_every_quanta=args.checkpoint_every,
        obs=obs,
        metrics_every=args.metrics_every,
        metrics_path=metrics_path if args.metrics_every else None,
        max_attempts=args.max_attempts,
        watchdog_s=args.watchdog_s,
        queue_depth=args.queue_depth,
    )
    handles = []
    for path in args.specs:
        with open(path) as f:
            spec = RunSpec.from_json(f.read())
        stem = os.path.splitext(os.path.basename(path))[0]
        for i in range(args.jobs):
            tenant = dataclasses.replace(spec, seed=args.seed0 + i)
            handles.append(sched.submit(
                tenant, job_id=f"{stem}-seed{args.seed0 + i}"
            ))
    sched.run_until_idle()
    stats = sched.stats()
    results, failed = {}, {}
    for job in handles:
        try:
            results[job.id] = sched.result(job, timeout=0).manifest()
        except JobFailedError as e:
            failed[job.id] = repr(e)
    os.makedirs(out, exist_ok=True)
    sched.write_metrics(metrics_path)
    if obs is not None:
        obs.timeline.write(args.timeline)
        if not args.quiet:
            print(f"timeline: {args.timeline}", file=sys.stderr)
    if not args.quiet:
        print(f"metrics: {metrics_path}", file=sys.stderr)
    path = os.path.join(out, "serve_results.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(
            {"scheduler": stats, "results": results, "failed": failed},
            f, indent=2, sort_keys=True,
        )
    os.replace(tmp, path)
    if not args.quiet:
        print(
            f"{stats['n_jobs']} jobs, {stats['n_engines']} packed engine(s), "
            f"{stats['n_compiles']} compile(s), {stats['n_quanta']} quanta",
            file=sys.stderr,
        )
    print(path)
    return 1 if failed else 0


def _cmd_list_systems(args) -> int:
    from repro.core import systems

    for name in sorted(systems.CONSTRUCTORS):
        entry = systems.CONSTRUCTORS[name]
        zoo = systems.REGISTRY.get(name)
        obs = ", ".join(sorted(entry.observables)) or "-"
        print(f"{name}")
        print(f"  observables: {obs}")
        if zoo is not None:
            print(f"  validation instance: {dict(zoo.params)}")
    return 0


def _cmd_list_strategies(args) -> int:
    from repro import exchange

    for name in exchange.available_strategies():
        print(f"{name}")
        print(f"  {exchange.strategy_help(name)}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="Declarative PT runs: execute serializable RunSpec JSONs.",
    )
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("run", help="execute a RunSpec JSON end-to-end")
    p.add_argument("spec", help="path to the spec JSON")
    p.add_argument("--out", default=None, help="output dir (default runs/<spec stem>)")
    p.add_argument("--checkpoint-every", type=int, default=10,
                   help="chunks between checkpoints")
    p.add_argument("--progress-every", type=int, default=10,
                   help="chunks between progress lines")
    p.add_argument("--mesh-chains", type=int, default=0, metavar="E",
                   help="shard whole chains over E devices (MeshSpec "
                        "ensemble axis; overrides the spec's engine.mesh)")
    p.add_argument("--mesh-replicas", type=int, default=0, metavar="D",
                   help="shard the replica axis over D devices (MeshSpec "
                        "replica axis; overrides the spec's engine.mesh)")
    p.add_argument("--timeline", default=None, metavar="OUT.trace.json",
                   help="record a Perfetto/Chrome trace of the run "
                        "(compile, chunk, device-wait, adapt, checkpoint "
                        "spans) to this path")
    p.add_argument("--metrics-out", default=None, metavar="OUT.prom",
                   help="write the run's metrics (Prometheus text format) "
                        "to this path")
    p.add_argument("--jax-profile", default=None, metavar="DIR",
                   help="wrap the first engine run in jax.profiler and write "
                        "the device profile under DIR")
    p.add_argument("--strict-kernels", action="store_true",
                   help="fail loudly if a fused/Pallas mega-step compile "
                        "errors instead of degrading to the per-sweep path")
    p.add_argument("--quiet", action="store_true")
    p.set_defaults(fn=_cmd_run)

    p = sub.add_parser("resume", help="continue a checkpointed run directory")
    p.add_argument("dir", help="a previous `run` output dir")
    p.add_argument("--checkpoint-every", type=int, default=10,
                   help="chunks between checkpoints")
    p.add_argument("--progress-every", type=int, default=10)
    p.add_argument("--quiet", action="store_true")
    p.set_defaults(fn=_cmd_resume)

    p = sub.add_parser(
        "validate", help="conformance-run a zoo system vs its exact reference"
    )
    p.add_argument("system", help="registry name (see list-systems)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--exchange", default="deo",
                   help="replica-exchange strategy (see list-strategies)")
    p.add_argument("--fused", action="store_true",
                   help="run the interval-fused kernel path (use_fused=True; "
                        "its counter-PRNG stream is gated statistically)")
    p.add_argument("--out", default=None, help="also write the report JSON here")
    p.set_defaults(fn=_cmd_validate)

    p = sub.add_parser(
        "serve", help="pack seed-variant jobs of each spec into shared "
                      "mega-steps (repro.serve scheduler)"
    )
    p.add_argument("specs", nargs="+", help="spec JSONs; same-shaped specs "
                                            "share one compiled engine")
    p.add_argument("--jobs", type=int, default=4,
                   help="seed variants submitted per spec (default 4)")
    p.add_argument("--seed0", type=int, default=0, help="first tenant seed")
    p.add_argument("--out", default=None,
                   help="output dir for serve_results.json (default runs/serve)")
    p.add_argument("--quantum-chunks", type=int, default=1,
                   help="compiled chunks per scheduler time-slice")
    p.add_argument("--pack-window", type=float, default=0.0,
                   help="seconds to hold a new shape open for bucket-mates")
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable preemption persistence under this root")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="quanta between bucket checkpoints (0 = seal/finish only)")
    p.add_argument("--metrics-every", type=int, default=0, metavar="N",
                   help="rewrite the Prometheus metrics file every N quanta "
                        "(0 = only once at the end)")
    p.add_argument("--metrics-out", default=None, metavar="OUT.prom",
                   help="metrics destination (default <out>/metrics.prom)")
    p.add_argument("--timeline", default=None, metavar="OUT.trace.json",
                   help="record a Perfetto trace of the scheduler (quantum "
                        "lanes, job flows, engine spans)")
    p.add_argument("--max-attempts", type=int, default=3,
                   help="supervised retries per quantum before the bucket "
                        "is quarantined (DESIGN.md §Resilience)")
    p.add_argument("--watchdog-s", type=float, default=0.0,
                   help="wall-clock budget per quantum/compile; 0 disables "
                        "the watchdog threads")
    p.add_argument("--queue-depth", type=int, default=0,
                   help="bound the intake queue (QueueFull backpressure); "
                        "0 = unbounded")
    p.add_argument("--quiet", action="store_true")
    p.set_defaults(fn=_cmd_serve)

    p = sub.add_parser("list-systems", help="registered systems + observables")
    p.set_defaults(fn=_cmd_list_systems)

    p = sub.add_parser(
        "list-strategies", help="registered replica-exchange strategies"
    )
    p.set_defaults(fn=_cmd_list_strategies)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
