"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``bench/workloads/<cell>.json``,
its configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the generator of that mix's kind in
``bench/traffic/<kind>.py``, the configuration's plain reference in
``bench/reference/<reference>.py`` and each per-layer metric in
``bench/metrics/<metric>.py``.  ``BENCHMARK.json`` says which metrics a cell
reports.  Every kind that drives `repro.api.Session` times it with the one
window of ``bench/traffic/timed_window.py``.

The run needs as many TPU chips as the cell asks for and exits non-zero,
with no result, without them.  It keeps JAX's compilation cache in
``.jax_cache/`` of the checkout (or where ``JAX_COMPILATION_CACHE_DIR``
says), warms every shape its traffic uses, measures for ``--seconds``, and
then compares what the timed path produced with the plain reference.  With
``--trace 1`` the window runs under the profiler and the per-layer metrics
are printed instead of the end-to-end ones.

One further option is for measuring the benchmark itself, never for its
runs: ``--control 1`` puts the reference, computed in bfloat16, in the
program's place (the comparison has to fail it).

A kind declares the checks it reports in ``CHECKS``, and its cells give
each a limit; a record that reports other checks is refused.  A run is
correct when every check is within its cell's limit and the engine did not
degrade (``failed``).
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

from bench.device import NoChip, device_info  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_out" / "trace"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def deep_update(base: dict, extra: dict) -> dict:
    out = dict(base)
    for k, v in extra.items():
        out[k] = deep_update(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Cell:
    """One workload with everything it names, resolved from files."""

    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def load_cell(name: str, overrides: dict | None = None) -> Cell:
    """Resolve a cell; ``overrides`` (tests only) patch its three files."""
    overrides = overrides or {}
    workload = deep_update(load_json(BENCH / "workloads" / f"{name}.json"),
                           overrides.get("workload", {}))
    config = deep_update(load_json(BENCH / "configs" / f"{workload['config']}.json"),
                         overrides.get("config", {}))
    traffic = deep_update(load_json(BENCH / "traffic" / f"{workload['traffic']}.json"),
                          overrides.get("traffic", {}))
    bench = load_json(ROOT / "BENCHMARK.json")
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or name in m.get("workloads", ())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in reported)]
    return Cell(name, workload, config, traffic, e2e, per_layer)


def use_program():
    """Put the checkout's ``src`` first on the path and import the program."""
    src = ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro

    where = [Path(p).resolve() for p in repro.__path__]
    if (src / "repro").resolve() not in where:
        raise ImportError(f"repro imported from {where}, not from {src}")


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Tracer:
    """The profiler around the window, and the harness's host spans."""

    def __init__(self, directory: Path | None):
        self.directory = directory
        self._window = None

    @property
    def on(self) -> bool:
        return self.directory is not None

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        """Start the profiler, then open the ``bench.window`` span."""
        if not self.on:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self) -> None:
        """Close the ``bench.window`` span, then stop and write the profile."""
        if not self.on:
            return
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Context:
    """What a traffic kind needs to run a cell."""

    cell: Cell
    seed: int
    seconds: float
    tracer: Tracer
    reference: object  # the configuration's plain reference module
    control: bool = False
    process_t0: float = PROCESS_T0


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, control: bool = False,
             overrides: dict | None = None) -> dict:
    """One run of one cell; returns the result object that is printed."""
    cell = load_cell(name, overrides)
    device = device_info(cell.chips, require_chip)
    use_program()
    reference = load_module(BENCH / "reference" / f"{cell.config['reference']}.py")
    kind = load_module(BENCH / "traffic" / f"{cell.traffic['kind']}.py")
    tracer = Tracer(TRACE_DIR / name if trace else None)
    ctx = Context(cell, int(seed), float(seconds), tracer, reference, control=control)
    record = kind.run(ctx)
    if set(record["checks"]) != set(kind.CHECKS):
        raise ValueError(f"kind {cell.traffic['kind']!r} reported the checks "
                         f"{sorted(record['checks'])}, but declares {sorted(kind.CHECKS)}")
    device["memory_peak_bytes"] = record["memory_peak_bytes"]

    metrics = {}
    summary = None
    if trace:
        from bench import trace as trace_lib

        summary = trace_lib.reduce(trace_lib.load(trace_lib.find_xplane(str(tracer.directory))))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for m in cell.per_layer:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(
                summary, record, device)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = record["setup_s"] if m["name"] == "setup_s" else record["end_to_end"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    limits = cell.workload["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in record["checks"].items()}
    correct = record["failed"] == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    out = {
        "correct": bool(correct),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       control=bool(args.control))
    except NoChip as err:
        print(f"bench: {err}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
