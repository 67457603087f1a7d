"""The plain reference: its cipher against published answers, and its runs
against the program's at a small size on the CPU (the program is imported
here only; the reference module imports nothing of it)."""
import ast
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax.numpy as jnp  # noqa: E402

from bench.reference import ising_pt as ref  # noqa: E402

# Random123 known-answer tests for Threefry-2x32 with 20 rounds
KAT = [
    ((0x00000000, 0x00000000), (0x00000000, 0x00000000), (0x6B200159, 0x99BA4EFE)),
    ((0xFFFFFFFF, 0xFFFFFFFF), (0xFFFFFFFF, 0xFFFFFFFF), (0x1CB996FC, 0xBB002BE7)),
    ((0x13198A2E, 0x03707344), (0x243F6A88, 0x85A308D3), (0xC4923A9C, 0x483DF7A0)),
]


@pytest.mark.parametrize("key,ctr,want", KAT)
def test_threefry_known_answers(key, ctr, want):
    got = ref.threefry2x32(key[0], key[1], ctr[0], ctr[1])
    assert (int(got[0]), int(got[1])) == want


def test_yardstick_imports_nothing_of_the_program():
    """Only run.py and the traffic kinds touch the program; the reference,
    the trace reduction, the arithmetic and the metric readers
    do not."""
    for path in (ROOT / "bench").rglob("*.py"):
        if "traffic" in path.parts or path.name == "run.py":
            continue
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names] if isinstance(node, ast.Import)
                     else [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
            assert not any(n.split(".")[0] == "repro" for n in names), path


@pytest.mark.parametrize("params,ladder,seed", [
    ({}, {"kind": "paper", "t_min": 1.0, "t_max": 4.0}, 3),
    ({"use_fused": True, "use_pallas": True}, {"kind": "paper", "t_min": 1.0, "t_max": 4.0}, 2**31 + 7),
    ({}, {"kind": "paper", "t_min": 1.5, "t_max": 4.5}, 11),
])
def test_reference_matches_the_program_bit_for_bit(params, ladder, seed):
    from repro.api import (EngineSpec, LadderSpec, PhaseSpec, RunSpec,
                           ScheduleSpec, Session, SystemSpec)

    length, r, spi, n = 16, 8, 10, 3
    spec = RunSpec(system=SystemSpec("ising", {"length": length, **params}),
                   ladder=LadderSpec(n_replicas=r, **ladder),
                   engine=EngineSpec(swap_interval=spi, chunk_intervals=1),
                   schedule=ScheduleSpec(phases=(PhaseSpec("a", spi * n),)),
                   observables=(), seed=seed)
    pt = Session(spec).run().state.pt
    s0, key = ref.init_chain(seed, r, length)
    out = ref.advance(s0, ref.energy(s0), jnp.arange(r, dtype=jnp.int32),
                      jnp.int32(0), jnp.int32(0), key,
                      jnp.asarray(ref.ladder_betas(ladder, r)),
                      path="fused" if params else "per_sweep",
                      n_intervals=n, sweeps_per_interval=spi, block=4)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(pt.states))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(pt.energy))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(pt.rung))
