"""Traffic kind ``session_window``: one long run through `repro.api.Session`.

The schedule is a warm-up phase of one chunk and a window phase longer than
any run, which a callback ends once ``--seconds`` have passed.  The window
opens when the warm-up chunk has finished on the device and closes when the
chunk that was running at the deadline has finished: it is a whole number
of chunks, ended by ``block_until_ready``.  The host is held at most one
chunk ahead of the device, so the device always has the next chunk queued.

At each chunk boundary the callback copies the lattice, energies, rungs and
counters (the engine donates its state to the next chunk), so that the last
chunk of the window can be replayed by the plain reference from the state
it started from.  The initial state is copied too, before the warm-up.
The mix file gives ``system``, the kernel flags the window's `Session`
passes to the system, and may give ``mesh``, the ``MeshSpec`` that splits
the replicas over the cell's chips.  Kernels are strict: a kernel that
cannot run on the chip fails the run instead of degrading.

After the window, with the program's state freed, the reference checks:

* ``replicas_off`` -- replicas whose lattice differs from the reference's,
  at the start (the lattice drawn from the seed) or after the last chunk;
* ``rungs_off`` -- slots whose temperature label differs after the chunk's
  exchange;
* ``energy_off`` -- the largest gap between the energy the engine carries
  and the energy of its own lattice, at the start and after the chunk.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import flips
from bench.device import free_device_memory, memory_peak_bytes

WINDOW_SWEEPS = 100 * 10**9  # longer than any run; the callback ends it
# Replicas the reference sweeps at a time, so that at the paper's size it
# fits beside the copies the window kept.
REFERENCE_BLOCK = 256


def _spec(ctx, warm_sweeps: int):
    from repro.api import (EngineSpec, LadderSpec, PhaseSpec, RunSpec,
                           ScheduleSpec, SystemSpec)
    from repro.core.distributed import MeshSpec

    dep = ctx.cell.config
    mix = ctx.cell.traffic
    mesh = mix.get("mesh")
    system = {"length": dep["length"], "j": dep["j"], "b": dep["b"],
              "accept_rule": dep["accept_rule"], **mix["system"]}
    return RunSpec(
        system=SystemSpec(dep["system"], system),
        ladder=LadderSpec(n_replicas=dep["n_replicas"], **dep["ladder"]),
        engine=EngineSpec(swap_interval=dep["swap_interval"],
                          chunk_intervals=dep["chunk_intervals"],
                          criterion=dep["criterion"],
                          mesh=MeshSpec(**mesh) if mesh else None),
        schedule=ScheduleSpec(phases=(PhaseSpec("warm", warm_sweeps),
                                      PhaseSpec("window", WINDOW_SWEEPS))),
        observables=(),
        seed=ctx.seed,
    )


def _copy(pt):
    """Device copies of the leaves a replay needs (the engine donates them)."""
    import jax.numpy as jnp

    return {k: jnp.copy(getattr(pt, k))
            for k in ("states", "energy", "rung", "t", "phase")}


def _host(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


def run(ctx) -> dict:
    import jax

    from repro.api import Session
    from repro.api.session import Callback

    dep = ctx.cell.config
    chunk_sweeps = dep["swap_interval"] * dep["chunk_intervals"]
    tracer = ctx.tracer

    class Window(Callback):
        start = before = pending = None
        t0 = sweep0 = None

        def on_phase_start(self, session, phase):
            if phase.name == "warm":
                self.start = _copy(session.state.pt)
                return
            jax.block_until_ready(session.state)
            self.sweep0 = int(np.asarray(session.state.pt.t).reshape(-1)[0])
            self.pending = _copy(session.state.pt)
            jax.block_until_ready(self.pending)
            tracer.start()
            self.t0 = time.perf_counter()

        def on_chunk(self, session, info):
            if session.current_phase.name != "window":
                return False
            with tracer.span("bench.chunk_boundary"):
                self.before = self.pending  # the state this chunk started from
                jax.block_until_ready(self.before["t"])  # the previous chunk is done
                if time.perf_counter() - self.t0 >= ctx.seconds:
                    return True
                self.pending = _copy(info.state.pt)
                return False

    window = Window()
    session = Session(_spec(ctx, chunk_sweeps), callbacks=[window], strict_kernels=True)
    result = session.run()
    final = result.state
    with tracer.span("bench.window_close"):
        jax.block_until_ready(final)
    t_end = time.perf_counter()
    tracer.stop()
    window_s = t_end - window.t0
    setup_s = window.t0 - ctx.process_t0

    devices = sorted({d for leaf in jax.tree_util.tree_leaves(final.pt.states)
                      for d in leaf.devices()}, key=lambda d: d.id)
    peak = memory_peak_bytes(devices)
    start, before = _host(window.start), _host(window.before)
    after = _host({k: getattr(final.pt, k) for k in ("states", "energy", "rung", "t", "phase")})
    degraded = bool(session.engine._degraded)
    sweeps = int(after["t"].reshape(-1)[0]) - window.sweep0
    del session, result, final, window
    free_device_memory()

    checks = check(ctx, start, before, after)
    n_flips = flips.flip_attempts(sweeps, dep["n_replicas"], dep["length"])
    return {
        "setup_s": setup_s,
        "end_to_end": {"flips_per_s": n_flips / window_s},
        "flips": n_flips,
        "chips": ctx.cell.chips,
        "attempted": sweeps // chunk_sweeps,
        "failed": int(degraded),
        "memory_peak_bytes": peak,
        "checks": checks,
    }


def check(ctx, start: dict, before: dict, after: dict) -> dict:
    """Compare the window's last chunk (and the start) with the reference."""
    import jax.numpy as jnp

    ref = ctx.reference
    dep = ctx.cell.config
    r, length = dep["n_replicas"], dep["length"]
    j, b = dep["j"], dep["b"]
    path = "fused" if ctx.cell.traffic["system"].get("use_fused") else "per_sweep"
    betas = jnp.asarray(ref.ladder_betas(dep["ladder"], r))
    s0, key = ref.init_chain(ctx.seed, r, length)
    replay = dict(path=path, n_intervals=dep["chunk_intervals"],
                  sweeps_per_interval=dep["swap_interval"], j=j, b=b,
                  block=math.gcd(r, REFERENCE_BLOCK))
    if ctx.control:
        # the reference in bfloat16, in the program's place
        bf16 = jnp.bfloat16
        s_c, _ = ref.init_chain(ctx.seed, r, length, dtype=bf16)
        start = {"states": np.asarray(s_c),
                 "energy": np.asarray(ref.energy(s_c, j, b, dtype=bf16), np.float32)}
        out = ref.advance(*(jnp.asarray(before[k]) for k in
                            ("states", "energy", "rung", "t", "phase")),
                          key, betas, dtype=bf16, **replay)
        after = {"states": np.asarray(out[0]),
                 "energy": np.asarray(out[1], np.float32),
                 "rung": np.asarray(out[2])}
    want = ref.advance(*(jnp.asarray(before[k]) for k in
                         ("states", "energy", "rung", "t", "phase")),
                       key, betas, **replay)
    want_states, want_rung = np.asarray(want[0]), np.asarray(want[2])
    off_start = np.any(start["states"] != np.asarray(s0), axis=(-2, -1))
    off_after = np.any(after["states"] != want_states, axis=(-2, -1))
    e_start = np.asarray(ref.energy(jnp.asarray(start["states"]), j, b))
    e_after = np.asarray(ref.energy(jnp.asarray(after["states"]), j, b))
    energy_off = max(float(np.max(np.abs(start["energy"] - e_start))),
                     float(np.max(np.abs(after["energy"] - e_after))))
    return {
        "replicas_off": int(np.sum(off_start) + np.sum(off_after)),
        "rungs_off": int(np.sum(after["rung"] != want_rung)),
        "energy_off": energy_off,
    }
