"""Statistical conformance: the engine vs exact ground truth, per system.

One parametrized case per `repro.core.systems.REGISTRY` entry — every system
in the zoo (Ising, Gaussian, Potts, both EA spin glasses, HP protein) runs
through the *production* path (chunked streaming engine, adaptive ladder ON,
``n_chains > 1`` ensemble axis ON) and its sampled ⟨E⟩ + order-parameter
means must match exact enumeration / analytic values within 4x the
batch-means MCSE at every rung of the final adapted ladder
(`repro.validate.conformance`, DESIGN.md §Validate).

Entries whose exact reference costs > ~10 s (`entry.slow`) ride the `slow`
tier so tier-1 latency stays flat; `pytest -m slow` runs them.
"""
import numpy as np
import pytest

from repro.core import systems
from repro.validate import assert_conforms, run_conformance
from repro.validate import exact as exact_lib
from repro.validate.conformance import EXACT

CASES = [
    pytest.param(name, marks=pytest.mark.slow if entry.slow else [])
    for name, entry in sorted(systems.REGISTRY.items())
]


def test_registry_covers_expected_zoo():
    """The zoo the paper motivates: lattice benchmark (Ising), multimodal
    toy (Gaussian), beyond-Ising lattice (Potts), disordered (EA in 2-D, and
    EA over disorder samples in 2-D or 3-D), and the protein-folding
    workload (HP) — each with an exact reference."""
    assert set(systems.REGISTRY) == {
        "ising",
        "gaussian",
        "potts",
        "ea_spin_glass",
        "ea_glass",
        "hp_protein",
    }
    assert set(EXACT) == set(systems.REGISTRY)
    for entry in systems.REGISTRY.values():
        assert entry.n_chains > 1  # ensemble axis always exercised
        assert entry.adapt_rounds > 0  # adaptive ladder always exercised


@pytest.mark.parametrize("name", CASES)
def test_engine_conforms_to_exact_reference(name):
    entry = systems.REGISTRY[name]
    report = run_conformance(entry, seed=0)
    # The adaptive machinery must have actually fired during burn-in.
    assert report.n_retunes == entry.adapt_rounds, report.n_retunes
    # Endpoints stay pinned; interior rungs may move.
    np.testing.assert_allclose(report.temps[0], entry.temps[0], rtol=1e-5)
    np.testing.assert_allclose(report.temps[-1], entry.temps[-1], rtol=1e-4)
    assert np.all(np.diff(report.temps) > 0)
    assert_conforms(report, z_max=4.0, geweke_max=4.0)
    # Batch-means machinery sanity: every series carries real information.
    for k, ess in report.ess.items():
        assert np.all(ess > 10), (k, ess)


def test_hp_move_graph_ergodic_at_registered_length():
    """The HP conformance answer is only exact if end+corner moves reach the
    whole SAW space at the registered chain length — check it, don't assume."""
    n = systems.REGISTRY["hp_protein"].make().n_monomers
    assert exact_lib.hp_move_graph_connected(n)


@pytest.mark.slow
def test_hp_occupancy_chi_square_exact_distribution():
    """Strongest equality-in-distribution check: thinned MH samples of a tiny
    HP chain must occupy the *full* 100-conformation space with Boltzmann
    frequencies (chi-square over every state, not just moment matching)."""
    import jax
    import jax.numpy as jnp

    from repro.core import hp
    from repro.validate import exact as exact_mod

    system = hp.HPChain(sequence="HHPHH", moves_per_step=50)  # 50 ~ >> IAT
    saws = exact_mod.enumerate_saws(4)
    key_of = {tuple(map(tuple, p)): i for i, p in enumerate(saws)}
    e = np.asarray(jax.vmap(system.energy)(jnp.asarray(saws, jnp.int32)))
    w = np.exp(-e / 1.0)
    w /= w.sum()

    walkers, records, burn = 128, 220, 20
    pos = jax.vmap(system.init_state)(jax.random.split(jax.random.key(0), walkers))
    beta = jnp.ones((walkers,))
    step = jax.jit(jax.vmap(system.mcmc_step, in_axes=(0, 0, 0)))
    counts = np.zeros(len(saws))
    key = jax.random.key(1)
    for t in range(records):
        key, sub = jax.random.split(key)
        pos, _, _ = step(jax.random.split(sub, walkers), pos, beta)
        if t >= burn:
            arr = np.asarray(pos)
            arr = arr - arr[:, :1]  # normalize translation
            for i in range(walkers):
                counts[key_of[tuple(map(tuple, arr[i]))]] += 1
    n = counts.sum()
    assert np.all(counts > 0)  # ergodic: every conformation visited
    chi2 = float(((counts - w * n) ** 2 / (w * n)).sum())
    dof = len(saws) - 1
    # ~1e-4 tail of chi2_99 with near-iid (thinned) samples
    assert chi2 < 1.65 * dof, (chi2, dof)


@pytest.mark.parametrize("strategy", ["seo", "windowed", "vmpt"])
@pytest.mark.parametrize("name", ["gaussian", "ising"])
def test_exchange_strategy_conforms_to_exact_reference(name, strategy):
    """The strategy × system gate (DESIGN.md §Exchange): every non-default
    replica-exchange scheme must be *statistically verified* on the Ising +
    Gaussian zoo entries — same adaptive ensemble path, same 4×MCSE
    tolerance — not just run without crashing.  (`deo` is the default the
    rest of this module already gates.)"""
    entry = systems.REGISTRY[name]
    report = run_conformance(entry, seed=0, exchange=strategy)
    assert report.n_retunes == entry.adapt_rounds, report.n_retunes
    np.testing.assert_allclose(report.temps[0], entry.temps[0], rtol=1e-5)
    np.testing.assert_allclose(report.temps[-1], entry.temps[-1], rtol=1e-4)
    assert np.all(np.diff(report.temps) > 0)
    assert_conforms(report, z_max=4.0, geweke_max=4.0)


def test_sharded_engine_conforms_to_exact_reference():
    """The sharded-mega-step entry in the conformance matrix (DESIGN.md
    §Distributed): the same zoo entry, executed through the shard_map path
    via ``mesh=`` (1x1 here — tier-1 has one device; the multi-device mesh
    is bit-equal to it by tests/test_distributed.py), must clear the same
    exact-reference gate as every other sampler variant."""
    from repro.core.distributed import MeshSpec

    entry = systems.REGISTRY["ising"]
    report = run_conformance(
        entry, seed=0, mesh=MeshSpec(ensemble=1, replica=1)
    )
    assert report.n_retunes == entry.adapt_rounds, report.n_retunes
    assert_conforms(report, z_max=4.0, geweke_max=4.0)


@pytest.mark.parametrize("name", [
    "ising",
    # the Potts exact reference enumerates 3^16 configs (~20 s) — same slow
    # tier as the base Potts entry
    pytest.param("potts", marks=pytest.mark.slow),
])
def test_fused_kernel_conforms_to_exact_reference(name):
    """The interval-fused kernel gate (DESIGN.md §6): fusing all
    sweeps-per-interval into one launch replaces the per-sweep `jax.random`
    uniforms with the in-kernel counter PRNG, so the chain *cannot* be
    bit-equal to the per-sweep path — it must instead be statistically
    verified against exact ground truth through the same adaptive ensemble
    path and 4×MCSE tolerance as every other sampler variant."""
    entry = systems.REGISTRY[name]
    report = run_conformance(
        entry, seed=0,
        system_params={"use_fused": True, "use_pallas": True},
    )
    assert report.n_retunes == entry.adapt_rounds, report.n_retunes
    np.testing.assert_allclose(report.temps[0], entry.temps[0], rtol=1e-5)
    np.testing.assert_allclose(report.temps[-1], entry.temps[-1], rtol=1e-4)
    assert np.all(np.diff(report.temps) > 0)
    assert_conforms(report, z_max=4.0, geweke_max=4.0)


def test_round_fused_kernel_conforms_to_exact_reference():
    """The whole-round kernel gate (DESIGN.md §6): folding the exchange into
    the launch replaces the engine's ``fold_in(key, 2t+1)`` swap draw with
    the counter PRNG's swap stream, so like ``use_fused`` it cannot be
    bit-equal to the strategy path and must clear the statistical gate —
    with ``pack_bits=True`` riding along, since packing is pinned bitwise
    elsewhere and this is its end-to-end conformance entry."""
    entry = systems.REGISTRY["ising"]
    report = run_conformance(
        entry, seed=0,
        system_params={"use_fused": True, "use_pallas": True,
                       "use_fused_round": True, "pack_bits": True},
    )
    assert report.n_retunes == entry.adapt_rounds, report.n_retunes
    np.testing.assert_allclose(report.temps[0], entry.temps[0], rtol=1e-5)
    np.testing.assert_allclose(report.temps[-1], entry.temps[-1], rtol=1e-4)
    assert np.all(np.diff(report.temps) > 0)
    assert_conforms(report, z_max=4.0, geweke_max=4.0)


def test_conformance_catches_a_wrong_sampler():
    """Negative control: a deliberately biased reference must fail the gate —
    otherwise the 4xMCSE tolerance is too loose to mean anything."""
    entry = systems.REGISTRY["ising"]

    def biased_exact(system, temps):
        out = exact_lib.ising_exact(system, temps)
        out["energy"] = out["energy"] + 1.0  # ~ >> 4 MCSE at this run length
        return out

    report = run_conformance(entry, seed=0, exact_fn=biased_exact)
    with pytest.raises(AssertionError, match="disagrees"):
        assert_conforms(report)
