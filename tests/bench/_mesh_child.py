"""Child process of ``test_bench_mesh.py``: the ``paper.fused`` cell split
by replica over four virtual CPU devices (``MeshSpec(ensemble=1,
replica=4)``, as a four-chip cell would run it), sound and with each fault
planted in turn.

``--xla_force_host_platform_device_count`` must be set before JAX starts,
so this runs in a process of its own.  Prints one JSON object: fault name
-> whether the run came out ``correct``.
"""
import dataclasses
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.run import run_cell  # noqa: E402
from repro.engine import driver  # noqa: E402

SMALL = {"config": {"length": 16, "n_replicas": 16, "swap_interval": 10},
         "workload": {"chips": 4},
         "traffic": {"mesh": {"ensemble": 1, "replica": 4}}}


def _unchanged(step):
    def broken(st, betas):
        out, rec, rung = step(st, betas)
        return dataclasses.replace(st, t=out.t, phase=out.phase, rung=out.rung), rec, rung
    return broken


def _half_batch(step):
    def broken(st, betas):
        out, rec, rung = step(st, betas)
        half = st.energy.shape[0] // 2  # half of each chip's replicas
        return dataclasses.replace(
            out, states=out.states.at[half:].set(st.states[half:]),
            energy=out.energy.at[half:].set(st.energy[half:])), rec, rung
    return broken


def _altered(step):
    def broken(st, betas):
        out, rec, rung = step(st, betas)
        return dataclasses.replace(out, states=out.states.at[-1, 0, 0].multiply(-1)), rec, rung
    return broken


def main():
    assert len(jax.devices()) == 4
    real_step, real_gather = driver.make_sharded_interval_step, jax.lax.all_gather
    out = {}
    for fault in ("sound", "unchanged", "half_batch", "altered", "no_exchange"):
        driver.make_sharded_interval_step = real_step
        jax.lax.all_gather = real_gather
        if fault in ("unchanged", "half_batch", "altered"):
            wrap = {"unchanged": _unchanged, "half_batch": _half_batch,
                    "altered": _altered}[fault]
            driver.make_sharded_interval_step = (
                lambda *a, _w=wrap, **k: _w(real_step(*a, **k)))
        elif fault == "no_exchange":
            # each chip sees only its own rows, repeated in place of the others'
            jax.lax.all_gather = lambda x, axis_name, *, tiled=False, **k: (
                jnp.tile(x, 4) if tiled else jnp.stack([x] * 4))
        res = run_cell("paper.fused", 2**31 + 7, 1.5, False, require_chip=False,
                       overrides=SMALL)
        out[fault] = res["correct"]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
