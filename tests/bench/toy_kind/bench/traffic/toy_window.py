"""Toy traffic kind ``toy_window``: a small 2-D ±J spin glass, two chains of
a few replicas, through the shared timed window.  Its state is a pytree
(``{"spins", "jr", "jd"}``), so the window copies every leaf.

It reports one check, ``toy_off``: the largest gap between the energy the
engine carries and the reference's energy of the lattice, at the start and
after the window.
"""
from __future__ import annotations

import numpy as np

from bench import flips
from bench.traffic import timed_window

CHECKS = ("toy_off",)
COPY_KEYS = ("states", "energy", "t")


def _spec(ctx, chunk_sweeps: int):
    from repro.api import EngineSpec, LadderSpec, RunSpec, SystemSpec

    dep = ctx.cell.config
    return RunSpec(
        system=SystemSpec("ea_spin_glass", {"shape": dep["shape"],
                                            "disorder_seed": dep["disorder_seed"],
                                            "accept_rule": dep["accept_rule"]}),
        ladder=LadderSpec(n_replicas=dep["n_replicas"], **dep["ladder"]),
        engine=EngineSpec(swap_interval=dep["swap_interval"], chunk_intervals=1,
                          n_chains=dep["n_chains"]),
        schedule=timed_window.schedule(chunk_sweeps),
        observables=(),
        seed=ctx.seed,
    )


def run(ctx) -> dict:
    dep = ctx.cell.config
    w = timed_window.run_window(ctx, _spec(ctx, dep["swap_interval"]), COPY_KEYS)
    ref = ctx.reference
    toy_off = max(float(np.max(np.abs(s["energy"] - ref.energy(**s["states"]))))
                  for s in (w.start, w.after))
    n_flips = flips.flip_attempts(w.sweeps, dep["n_replicas"], dep["shape"],
                                  chains=dep["n_chains"])
    return {
        "setup_s": w.setup_s,
        "end_to_end": {"flips_per_s": n_flips / w.window_s},
        "flips": n_flips,
        "chips": ctx.cell.chips,
        "attempted": w.sweeps // dep["swap_interval"],
        "failed": int(w.degraded),
        "memory_peak_bytes": w.memory_peak_bytes,
        "checks": {"toy_off": toy_off},
    }
