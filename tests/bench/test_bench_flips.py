"""The work a sampling cell counts: a flip attempt at every site of every
replica of every chain, for a lattice of any rank."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.flips import flip_attempts  # noqa: E402


@pytest.mark.parametrize("sweeps,replicas,shape,chains,want", [
    # the paper's 300x300 Ising lattice, 1,536 replicas, one 100-sweep chunk
    (100, 1536, (300, 300), 1, 100 * 1536 * 300 * 300),
    # a 32^3 glass, 34 rungs, 64 disorder samples x 4 copies
    (10, 34, (32, 32, 32), 256, 10 * 256 * 34 * 32 * 32 * 32),
])
def test_flip_attempts_count_every_site_of_any_rank(sweeps, replicas, shape, chains, want):
    got = flip_attempts(sweeps, replicas, shape, chains=chains)
    assert got == want and isinstance(got, int)
