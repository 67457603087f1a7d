"""Traffic kind ``session_window``: one long run of the Ising configuration
through `repro.api.Session`, timed by the shared window of
``bench/traffic/timed_window.py``.

The mix file gives ``system``, the kernel flags the window's `Session`
passes to the system, and may give ``mesh``, the ``MeshSpec`` that splits
the replicas over the cell's chips.  The window copies the lattice,
energies, rungs and counters at each chunk boundary, and the initial state
before the warm-up.

After the window, with the program's state freed, the reference checks
(`CHECKS`, each with a limit in the cell's workload file):

* ``replicas_off`` -- replicas whose lattice differs from the reference's,
  at the start (the lattice drawn from the seed) or after the last chunk;
* ``rungs_off`` -- slots whose temperature label differs after the chunk's
  exchange;
* ``energy_off`` -- the largest gap between the energy the engine carries
  and the energy of its own lattice, at the start and after the chunk.
"""
from __future__ import annotations

import math

import numpy as np

from bench import flips
from bench.traffic import timed_window

CHECKS = ("replicas_off", "rungs_off", "energy_off")
# the PTState fields the reference's replay starts from
COPY_KEYS = ("states", "energy", "rung", "t", "phase")
# Replicas the reference sweeps at a time, so that at the paper's size it
# fits beside the copies the window kept.
REFERENCE_BLOCK = 256


def _spec(ctx, chunk_sweeps: int):
    from repro.api import EngineSpec, LadderSpec, RunSpec, SystemSpec
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
        schedule=timed_window.schedule(chunk_sweeps),
        observables=(),
        seed=ctx.seed,
    )


def run(ctx) -> dict:
    dep = ctx.cell.config
    chunk_sweeps = dep["swap_interval"] * dep["chunk_intervals"]
    w = timed_window.run_window(ctx, _spec(ctx, chunk_sweeps), COPY_KEYS)
    checks = check(ctx, w.start, w.before, w.after)
    n_flips = flips.flip_attempts(w.sweeps, dep["n_replicas"], (dep["length"],) * 2)
    return {
        "setup_s": w.setup_s,
        "end_to_end": {"flips_per_s": n_flips / w.window_s},
        "flips": n_flips,
        "chips": ctx.cell.chips,
        "attempted": w.sweeps // chunk_sweeps,
        "failed": int(w.degraded),
        "memory_peak_bytes": w.memory_peak_bytes,
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
