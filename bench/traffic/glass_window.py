"""Traffic kind ``glass_window``: one long run of a spin-glass configuration
over many disorder samples through `repro.api.Session`, timed by the shared
window of ``bench/traffic/timed_window.py``.

The run holds ``n_samples * copies`` chains of ``n_replicas`` rungs each;
chain ``c`` holds the bonds of sample ``c // copies``, drawn from the run's
``--seed`` (a run's samples are its data).  The window copies the lattice,
energies, rungs, counters and bonds (``PTState.quenched``) at each chunk
boundary, and the initial state before the warm-up.  The program holds a
lattice lane-dense, ``(shape[0], prod(shape[1:]))``, the row-major view of
the plain lattice; the checks reshape it to ``shape``.

After the window, with the program's state freed, the reference checks
(`CHECKS`, each with a limit in the cell's workload file):

* ``replicas_off``, ``rungs_off``, ``energy_off`` -- as ``session_window``
  defines them, with the reference's replay run on the reference's own
  bonds;
* ``disorder_off`` -- chains whose bonds, at the start or after the window,
  differ from the reference's draw for sample ``c // copies``.
"""
from __future__ import annotations

import numpy as np

from bench import flips
from bench.traffic import timed_window

CHECKS = ("replicas_off", "rungs_off", "energy_off", "disorder_off")
# the PTState fields the reference's replay starts from, and the bonds
COPY_KEYS = ("states", "energy", "rung", "t", "phase", "quenched")


def n_chains(dep: dict) -> int:
    return dep["n_samples"] * dep["copies"]


def _spec(ctx, chunk_sweeps: int):
    from repro.api import EngineSpec, LadderSpec, RunSpec, SystemSpec

    dep = ctx.cell.config
    system = {"shape": tuple(dep["shape"]), "j": dep["j"], "copies": dep["copies"],
              "disorder_seed": ctx.seed, "accept_rule": dep["accept_rule"]}
    return RunSpec(
        system=SystemSpec(dep["system"], system),
        ladder=LadderSpec(n_replicas=dep["n_replicas"], **dep["ladder"]),
        engine=EngineSpec(swap_interval=dep["swap_interval"],
                          chunk_intervals=dep["chunk_intervals"],
                          criterion=dep["criterion"], n_chains=n_chains(dep)),
        schedule=timed_window.schedule(chunk_sweeps),
        observables=(),
        seed=ctx.seed,
    )


def run(ctx) -> dict:
    dep = ctx.cell.config
    chunk_sweeps = dep["swap_interval"] * dep["chunk_intervals"]
    w = timed_window.run_window(ctx, _spec(ctx, chunk_sweeps), COPY_KEYS)
    checks = check(ctx, w.start, w.before, w.after)
    n_flips = flips.flip_attempts(w.sweeps, dep["n_replicas"], dep["shape"],
                                  chains=n_chains(dep))
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


def _plain(tree: dict, shape) -> dict:
    """The copies with lattices and bonds in the plain ``shape`` view."""
    out = dict(tree)
    for k in ("states", "quenched"):
        if k in out:
            out[k] = out[k].reshape(*out[k].shape[:-2], *shape)
    return out


def check(ctx, start: dict, before: dict, after: dict) -> dict:
    """Compare the bonds, the start and the window's last chunk with the
    reference."""
    import jax.numpy as jnp

    ref = ctx.reference
    dep = ctx.cell.config
    shape = tuple(dep["shape"])
    c, r, j = n_chains(dep), dep["n_replicas"], dep["j"]
    start, before, after = (_plain(x, shape) for x in (start, before, after))
    betas = jnp.asarray(ref.ladder_betas(dep["ladder"], r))
    bonds = ref.chain_bonds(ctx.seed, c, dep["copies"], shape)
    s0, keys = ref.init_chains(ctx.seed, c, r, shape)
    # one chain's replicas at a time, chains one after another: at the
    # cell's size that fits on the chip beside nothing else
    replay = dict(n_intervals=dep["chunk_intervals"],
                  sweeps_per_interval=dep["swap_interval"], j=j,
                  rule=dep["accept_rule"], criterion=dep["criterion"])
    fields = ("states", "energy", "rung", "t", "phase")
    if ctx.control:
        # the reference in bfloat16, in the program's place
        bf16 = jnp.bfloat16
        b_c = ref.chain_bonds(ctx.seed, c, dep["copies"], shape, dtype=bf16)
        s_c, _ = ref.init_chains(ctx.seed, c, r, shape, dtype=bf16)
        start = {"states": np.asarray(s_c), "quenched": np.asarray(b_c),
                 "energy": np.asarray(ref.energy(s_c, b_c, j, dtype=bf16), np.float32)}
        out = ref.advance(*(jnp.asarray(before[k]) for k in fields), keys, betas,
                          b_c, dtype=bf16, **replay)
        after = {"states": np.asarray(out[0]), "quenched": np.asarray(b_c),
                 "energy": np.asarray(out[1], np.float32), "rung": np.asarray(out[2])}
    want = ref.advance(*(jnp.asarray(before[k]) for k in fields), keys, betas,
                       bonds, **replay)
    want_states, want_rung = np.asarray(want[0]), np.asarray(want[2])
    bonds_np = np.asarray(bonds)
    lattice = tuple(range(2, 2 + len(shape)))
    disorder_off = np.any(start["quenched"] != bonds_np, axis=(1, *lattice)) | np.any(
        after["quenched"] != bonds_np, axis=(1, *lattice))
    off_start = np.any(start["states"] != np.asarray(s0), axis=lattice)
    off_after = np.any(after["states"] != want_states, axis=lattice)
    e_start = np.asarray(ref.energy(jnp.asarray(start["states"]), bonds, j))
    e_after = np.asarray(ref.energy(jnp.asarray(after["states"]), bonds, j))
    energy_off = max(float(np.max(np.abs(start["energy"] - e_start))),
                     float(np.max(np.abs(after["energy"] - e_after))))
    return {
        "replicas_off": int(np.sum(off_start) + np.sum(off_after)),
        "rungs_off": int(np.sum(after["rung"] != want_rung)),
        "energy_off": energy_off,
        "disorder_off": int(np.sum(disorder_off)),
    }
