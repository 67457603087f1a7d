"""Smoke test of the PT main path on a TPU: ``python chip_smoke.py``.

One process, one chip, the entry points a user calls.  Phases, each
printing its own line:

  (a) device   — JAX must find a TPU; there is no CPU fallback.
  (b) kernels  — each Pallas kernel (Ising and Potts; per-sweep, interval-
                 fused, whole-round) compiled by Mosaic at R=16, L=64 and
                 compared on the chip with its pure-JAX reference: lattices,
                 rung maps and acceptance counts exactly, ΔE and energies to
                 the kernel tests' tolerance.  The fused Ising case runs in
                 a field (b=0.4), the others at b=0.
  (c) session  — the paper's Ising configuration (L=300, R=1536, swap
                 interval 100, paper ladder) through `repro.api.Session`, on
                 the default XLA path and on the fused Pallas path, with
                 ``strict_kernels=True``.  Prints compile seconds, sweeps/s
                 and the cold rung's |m|, and checks the running energy
                 against the energy recomputed from the final spins.
  (d) serve    — a `repro.serve.Scheduler` serves a few same-shaped jobs to
                 completion.

``--four-chips`` runs only the replica-sharded form of (c): the same spec
on ``MeshSpec(ensemble=1, replica=4)``, compared bit for bit (spins, rungs,
energies) with the spec on one device at the same seed.

The last line printed is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# kernel and reference ΔE agree to f32 rounding (tests/test_kernels.py)
DE_TOL = dict(rtol=1e-6, atol=1e-3)
# the paper's deployment (launch/sample.py): 300x300 spins, 1536 replicas
PAPER_L, PAPER_R = 300, 1536


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def exact(name, got, want):
    got, want = np.asarray(got), np.asarray(want)
    n_bad = int(np.sum(got != want))
    check(got.shape == want.shape and n_bad == 0,
          f"{name}: {n_bad} of {want.size} entries differ from the reference")


def close(name, got, want, tol=DE_TOL):
    got, want = np.asarray(got), np.asarray(want)
    check(np.allclose(got, want, **tol),
          f"{name}: max |diff| {np.max(np.abs(got - want))} exceeds {tol}")
    return float(np.max(np.abs(got - want))) if got.size else 0.0


# -- (a) ---------------------------------------------------------------------------


def device_check(count: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu",
          f"JAX found no TPU (default device: {dev}); this smoke test never "
          "falls back to the CPU")
    check(dev["count"] >= count, f"needs {count} chips, JAX sees {dev['count']}")
    say("a", f"device {dev['kind']} x{dev['count']} ({dev['platform']})")
    return dev


# -- (b) ---------------------------------------------------------------------------


def compiled_text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


def kernels_phase():
    from repro.core.ising import lattice_energy
    from repro.core.potts import potts_energy
    from repro.kernels import ops, ref

    r, l, q = 16, 64, 3
    key = jax.random.key(7)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    spins = jnp.where(jax.random.uniform(k1, (r, l, l)) < 0.5, 1, -1).astype(jnp.int8)
    states = jax.random.randint(k1, (r, l, l), 0, q).astype(jnp.int8)
    betas = jax.random.uniform(k2, (r,), minval=0.1, maxval=1.5)
    ladder = jnp.sort(betas)[::-1]
    rung = jax.random.permutation(k3, jnp.arange(r, dtype=jnp.int32))
    t0, ph0 = jnp.int32(17), jnp.int32(3)
    ref_sweep = {
        "ising": jax.jit(lambda s, u, b: ref.ising_sweep(s, u, b, j=1.0, b=0.0)),
        "potts": jax.jit(lambda s, u, b: ref.potts_sweep(s, u, b, q=q, j=1.0)),
    }

    cases = []
    u = jax.random.uniform(k4, (r, 2, l, l), jnp.float32)
    cases.append(("ising_sweep", ops.ising_sweep, (spins, u, betas),
                  dict(j=1.0, b=0.0, r_blk=8), ref_sweep["ising"], (spins, u, betas)))
    u = jax.random.uniform(k4, (r, 2, 2, l, l), jnp.float32)
    cases.append(("potts_sweep", ops.potts_sweep, (states, u, betas),
                  dict(q=q, j=1.0, r_blk=4), ref_sweep["potts"], (states, u, betas)))
    for name, fn, lat, kw in (
        ("ising", ops.ising_sweep_fused, spins, dict(b=0.4, r_blk=8)),
        ("potts", ops.potts_sweep_fused, states, dict(q=q, r_blk=4)),
    ):
        args = (lat, key, t0, betas)
        kw = dict(kw, n_sweeps=4)
        cases.append((f"{name}_fused", fn, args, kw,
                       jax.jit(lambda *a, _f=fn, _k=kw: _f(*a, use_pallas=False, **_k)),
                       args))
    for name, fn, lat, kw, energy in (
        ("ising", ops.ising_round_fused, spins, {}, lattice_energy(spins, 1.0, 0.0)),
        ("potts", ops.potts_round_fused, states, dict(q=q), potts_energy(states, q, 1.0)),
    ):
        args = (lat, key, t0, ph0, rung, energy, ladder)
        kw = dict(kw, n_sweeps=4, n_rounds=2)
        cases.append((f"{name}_round", fn, args, kw,
                      jax.jit(lambda *a, _f=fn, _k=kw: _f(*a, use_pallas=False, **_k)),
                      args))

    for name, fn, args, kw, ref_fn, ref_args in cases:
        t = time.perf_counter()
        text = compiled_text(fn, *args, use_pallas=True, **kw)
        check("tpu_custom_call" in text, f"{name}: no Mosaic kernel in the compiled step")
        got = jax.block_until_ready(fn(*args, use_pallas=True, **kw))
        want = jax.block_until_ready(ref_fn(*ref_args))
        exact(f"{name} lattice", got[0], want[0])
        if name.endswith("_round"):
            # (lattice, rung, energy, n_acc, accept, prob, attempt)
            exact(f"{name} rung", got[1], want[1])
            d_e = close(f"{name} energy", got[2], want[2])
            exact(f"{name} n_accepted", got[3], want[3])
            exact(f"{name} swap accept", got[4], want[4])
            close(f"{name} swap prob", got[5], want[5])
            exact(f"{name} swap attempt", got[6], want[6])
        else:
            d_e = close(f"{name} dE", got[1], want[1])
            exact(f"{name} n_accepted", got[2], want[2])
        say("b", f"{name}: compiled by Mosaic, matches the reference on the "
                 f"chip (lattice and counts exact, max |dE diff| {d_e}) "
                 f"in {time.perf_counter() - t:.1f}s")


# -- (c) ---------------------------------------------------------------------------


def paper_spec(fused: bool, mesh=None):
    """The paper's Ising deployment (launch/sample.py): L=300, R=1536,
    swap interval 100, paper ladder; three chunks of two intervals."""
    from repro.api import (
        EngineSpec, LadderSpec, PhaseSpec, RunSpec, ScheduleSpec, SystemSpec,
    )

    params = {"length": PAPER_L}
    if fused:
        params.update(use_fused=True, use_pallas=True)
    return RunSpec(
        system=SystemSpec("ising", params),
        ladder=LadderSpec(kind="paper", n_replicas=PAPER_R, t_min=1.0, t_max=4.0),
        engine=EngineSpec(swap_interval=100, chunk_intervals=2, mesh=mesh),
        schedule=ScheduleSpec(phases=(
            PhaseSpec("warm", 200), PhaseSpec("measure", 400),
        )),
        observables=("absmag",),
        seed=0,
    )


def run_session(spec):
    """Run ``spec`` through `Session` with ``strict_kernels=True``.

    Returns ``(result, seconds per phase, compile seconds, compiled step
    texts)``; fails if the engine degraded to another kernel path.
    """
    from repro.api import ObsCallback, Session
    from repro.api.session import Callback

    class PhaseClock(Callback):
        """Wall seconds per phase, device work included."""

        seconds = {}

        def on_phase_start(self, session, phase):
            self.t0 = time.perf_counter()

        def on_phase_end(self, session, phase, result):
            jax.block_until_ready(session.state)
            self.seconds[phase.name] = time.perf_counter() - self.t0

    clock, obs_cb = PhaseClock(), ObsCallback()
    session = Session(spec, callbacks=[clock, obs_cb], strict_kernels=True)
    result = session.run()
    check(not session.engine._degraded, "the engine degraded to another kernel path")
    snap = obs_cb.obs.metrics.snapshot()
    compile_s = snap["engine_compile_seconds_total"]["samples"][0]["value"]
    texts = [exe.as_text() for exe in session.engine._executables.values()]
    return result, clock.seconds, compile_s, texts


def session_phase():
    from repro.core.ising import IsingSystem

    for fused in (False, True):
        spec = paper_spec(fused)
        result, secs, compile_s, texts = run_session(spec)
        has_kernel = any("tpu_custom_call" in t for t in texts)
        check(has_kernel == fused,
              f"compiled step {'lacks' if fused else 'has'} a Mosaic kernel")
        st = result.state
        e_run = np.asarray(st.pt.energy)
        system = IsingSystem(length=PAPER_L)
        e_true = np.asarray(jax.jit(jax.vmap(system.energy))(st.pt.states))
        d_e = close("running vs recomputed energy", e_run, e_true,
                    tol=dict(rtol=0, atol=1e-3))
        cold_m = float(result.phases["measure"].summary["mean_absmag"][0])
        check(np.all(np.isfinite(e_run)) and 0.0 <= cold_m <= 1.0,
              f"non-finite energies or |m| out of range ({cold_m})")
        sweeps_s = 400 / secs["measure"]
        path = "fused Pallas" if fused else "XLA"
        say("c", f"paper Ising L={PAPER_L} R={PAPER_R} on the {path} path: "
                 f"compile {compile_s:.1f}s, {sweeps_s:.2f} sweeps/s "
                 f"({sweeps_s * PAPER_R * PAPER_L**2:.3e} spin-flip attempts/s), "
                 f"cold-rung |m| {cold_m:.4f}, running energy = recomputed "
                 f"(max |diff| {d_e}), strict_kernels, not degraded")
        del result, st


# -- (d) ---------------------------------------------------------------------------


def serve_phase():
    from repro.api import RunSpec
    from repro.serve import Scheduler

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "examples", "specs", "ising_serve.json")) as f:
        spec = RunSpec.from_json(f.read())
    sched = Scheduler(strict_kernels=True)
    jobs = [sched.submit(dataclasses.replace(spec, seed=s)) for s in range(4)]
    t = time.perf_counter()
    sched.run_until_idle()
    results = [sched.result(j, timeout=0) for j in jobs]
    for res in results:
        check(np.all(np.isfinite(np.asarray(res.final_energy))),
              "a served job returned non-finite energies")
    stats = sched.stats()
    say("d", f"served {len(results)} same-shaped jobs to completion in "
             f"{time.perf_counter() - t:.1f}s ({stats['n_compiles']} compile(s), "
             f"{stats['n_quanta']} quanta)")


# -- --four-chips -----------------------------------------------------------------


def four_chip_phase():
    from repro.core.distributed import MeshSpec

    for fused in (False, True):
        runs = {}
        for label, mesh in (("1 device", None),
                            ("mesh 1x4", MeshSpec(ensemble=1, replica=4))):
            result, secs, compile_s, _ = run_session(paper_spec(fused, mesh))
            st = result.state
            runs[label] = (np.asarray(st.pt.states), np.asarray(st.pt.rung),
                           np.asarray(st.pt.energy))
            say("4", f"{'fused Pallas' if fused else 'XLA'} path, {label}: "
                     f"compile {compile_s:.1f}s, "
                     f"{400 / secs['measure']:.2f} sweeps/s")
        for name, a, b in zip(("spins", "rungs", "energies"),
                              runs["1 device"], runs["mesh 1x4"]):
            exact(f"{'fused' if fused else 'XLA'} {name} on 4 chips", b, a)
        say("4", f"{'fused Pallas' if fused else 'XLA'} path: replica-sharded "
                 "run on 4 chips is bit-equal to 1 device (spins, rungs, "
                 "energies)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the replica-sharded session on 4 chips "
                         "against the same spec on one device")
    args = ap.parse_args(argv)
    try:
        dev = device_check(4 if args.four_chips else 1)
        from repro.compile_cache import enable_compile_cache

        cache = enable_compile_cache()
        n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        say("a", f"compile cache {cache} ({n_cached} entries at start)")
        if args.four_chips:
            four_chip_phase()
        else:
            kernels_phase()
            session_phase()
            serve_phase()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    dev["count"] = len(jax.devices())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
