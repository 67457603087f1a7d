"""The comparison that decides ``correct`` in the glass cell
(``glass3d.disorder``, kind ``glass_window``): a sound run passes, the
control and each planted fault fail.

Like ``test_bench_correct.py``, these drive the whole harness on the CPU at
a small size, past its look for a chip.  The faults are planted in the
program underneath: in the bonds a chain is given, and in the interval
step.
"""
import dataclasses
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.run import run_cell  # noqa: E402

CELL = "glass3d.disorder"
SEED = 2**31 + 101  # wider than 32 signed bits, as the driver's seeds are
SMALL = {"config": {"shape": [4, 4, 4], "n_samples": 2, "copies": 2, "n_replicas": 4,
                    "swap_interval": 5}}


def run(**kw):
    return run_cell(CELL, SEED, 1.0, False, require_chip=False, overrides=SMALL, **kw)


# -- faults in the bonds a chain is given --------------------------------------

def _sample_zero(real):
    return lambda self, chain: real(self, 0)


def _copies_differ(real):
    # each chain its own sample: the copies of a sample no longer share bonds
    return lambda self, chain: real(dataclasses.replace(self, copies=1), chain)


def _bond_flipped(real):
    def broken(self, chain):
        bonds = real(self, chain)
        last = jnp.asarray(chain) == 3  # one bond of the last chain
        return bonds.at[0, 0, 0].multiply(jnp.where(last, -1, 1).astype(jnp.int8))
    return broken


BOND_FAULTS = {"sample_zero": _sample_zero, "copies_differ": _copies_differ,
               "bond_flipped": _bond_flipped}


def _unchanged(step):
    def broken(st, betas):
        out, rec = step(st, betas)
        # counters advance, the lattice and energies do not
        return dataclasses.replace(st, t=out.t, phase=out.phase, rung=out.rung), rec
    return broken


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"replicas_off", "rungs_off", "energy_off", "disorder_off"}


def test_control_in_bfloat16_is_not_correct():
    out = run(control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(BOND_FAULTS))
def test_planted_bond_fault_is_not_correct(fault, monkeypatch):
    from repro.core.spin_glass import EAGlass

    monkeypatch.setattr(EAGlass, "init_quenched", BOND_FAULTS[fault](EAGlass.init_quenched))
    out = run()
    assert not out["correct"], out["checks"]
    assert out["checks"]["disorder_off"]["value"] > 0, out["checks"]


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.engine import driver

    real = driver.make_interval_step
    monkeypatch.setattr(driver, "make_interval_step",
                        lambda *a, **k: _unchanged(real(*a, **k)))
    out = run()
    assert not out["correct"], out["checks"]
