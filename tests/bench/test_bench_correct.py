"""The comparison that decides ``correct``: sound runs pass, the control and
each planted fault fail.

These drive the whole harness on the CPU at a small size, past its look for
a chip: the timed path, the copies the window keeps, the plain reference and
the cell's own limits.  Faults are planted in the program underneath, where
it produces its answer.  ``tests/bench/test_bench_mesh.py`` plants the one
fault that needs several devices (the exchange between chips left out).
"""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.run import run_cell  # noqa: E402

SEED = 2**31 + 101  # wider than 32 signed bits, as the driver's seeds are
SMALL = {"config": {"length": 32, "n_replicas": 16, "swap_interval": 10}}


def run(cell, **kw):
    return run_cell(cell, SEED, 1.5, False, require_chip=False, overrides=SMALL, **kw)


# -- faults, planted in the interval step every path builds on ----------------

def _unchanged(step):
    def broken(st, betas):
        out, rec = step(st, betas)
        # counters advance, the lattice and energies do not
        return dataclasses.replace(st, t=out.t, phase=out.phase, rung=out.rung), rec
    return broken


def _half_batch(step):
    def broken(st, betas):
        out, rec = step(st, betas)
        half = st.energy.shape[-1] // 2
        states = out.states.at[half:].set(st.states[half:])
        energy = out.energy.at[half:].set(st.energy[half:])
        return dataclasses.replace(out, states=states, energy=energy), rec
    return broken


def _altered(step):
    def broken(st, betas):
        out, rec = step(st, betas)
        states = out.states.at[-1, 0, 0].multiply(-1)  # one spin, energy untouched
        return dataclasses.replace(out, states=states), rec
    return broken


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch, "altered": _altered}


@pytest.fixture
def plant(monkeypatch):
    from repro.engine import driver

    def plant_fault(name):
        real = driver.make_interval_step
        monkeypatch.setattr(driver, "make_interval_step",
                            lambda *a, **k: FAULTS[name](real(*a, **k)))
    return plant_fault


@pytest.mark.parametrize("cell", ["paper.default", "paper.fused"])
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("cell", ["paper.default", "paper.fused"])
def test_control_in_bfloat16_is_not_correct(cell):
    out = run(cell, control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["paper.default", "paper.fused"])
def test_planted_fault_is_not_correct(cell, fault, plant):
    plant(fault)
    out = run(cell)
    assert not out["correct"], out["checks"]


def test_degraded_engine_is_not_correct(monkeypatch):
    from repro.engine import driver

    real_init = driver.Engine.__init__

    def degraded_init(self, *a, **k):
        real_init(self, *a, **k)
        self._degraded = True  # as if a kernel had fallen back to another path

    monkeypatch.setattr(driver.Engine, "__init__", degraded_init)
    out = run("paper.default")
    assert out["failed"] == 1 and not out["correct"], out
