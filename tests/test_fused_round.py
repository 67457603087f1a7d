"""Whole-PT-round fused kernels (DESIGN.md §6): the in-kernel exchange vs
the strategy + `accept_pairs` oracle, round kernels vs sweep+exchange
composition, bit-plane/int8 packing bit-equality, launch-split invariance,
and the structural single-launch evidence on the engine's interval step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ising, ladder, pt, swap as core_swap
from repro.core.potts import PottsSystem
from repro.engine import Engine, EngineConfig
from repro.engine.driver import StepSpec, make_interval_step
from repro.exchange import make_strategy
from repro.kernels import exchange as kx, ops, prng
from repro.kernels.potts_sweep import potts_sweep_fused_pallas

R, L = 6, 8
TEMPS = np.asarray(ladder.linear_ladder(R, 1.0, 3.5))
BETAS = jnp.asarray(1.0 / TEMPS, jnp.float32)  # rung order, cold -> hot


def _rand_slots(key, r):
    """Random slot->rung permutation + per-slot energies."""
    k1, k2 = jax.random.split(key)
    rung = jax.random.permutation(k1, jnp.arange(r, dtype=jnp.int32))
    energy = jax.random.normal(k2, (r,), jnp.float32) * 10.0
    return rung, energy


def _rand_ising(key, r, l):
    k1, k2 = jax.random.split(key)
    spins = jnp.where(
        jax.random.uniform(k1, (r, l, l)) < 0.5, 1, -1
    ).astype(jnp.int8)
    betas = jnp.sort(jax.random.uniform(k2, (r,), minval=0.25, maxval=1.0))[::-1]
    return spins, betas


# ---------- in-kernel exchange vs the strategy + accept_pairs oracle ------------
@pytest.mark.parametrize("criterion", ["logistic", "metropolis"])
@pytest.mark.parametrize("pairing", ["deo", "seo"])
@pytest.mark.parametrize("phase", [0, 1, 7])
def test_exchange_step_matches_accept_pairs_oracle(pairing, criterion, phase):
    """`kernels.exchange.exchange_step` must be BIT-equal to the PR 4
    strategy path (`core.swap.pair_partners` + `accept_pairs`) fed the same
    counter-stream uniforms — the Mosaic-safe one-hot forms may not change
    a single bit of the decision."""
    key = jax.random.key(31 + phase)
    rung, energy = _rand_slots(key, R)
    words = prng.key_words(key)
    got_rung, got_acc, got_prob, got_att, got_e = kx.exchange_step(
        rung, energy, BETAS, phase, words, pairing=pairing,
        criterion=criterion,
    )
    # oracle: inversion via argsort, partners from core.swap, decision from
    # accept_pairs with the uniforms injected from the same swap stream
    inv = jnp.argsort(rung)
    e_rung = energy[inv]
    eff_phase = phase if pairing == "deo" else prng.seo_coin(words, phase)
    partner = core_swap.pair_partners(R, eff_phase)
    u = prng.swap_uniforms(words, phase, R)
    perm, acc, prob, att = core_swap.accept_pairs(
        jax.random.key(0), partner, BETAS, e_rung, criterion, uniforms=u
    )
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(e_rung))
    np.testing.assert_array_equal(np.asarray(got_acc), np.asarray(acc))
    np.testing.assert_array_equal(np.asarray(got_prob), np.asarray(prob))
    np.testing.assert_array_equal(np.asarray(got_att), np.asarray(att))
    np.testing.assert_array_equal(np.asarray(got_rung), np.asarray(perm[rung]))


def test_exchange_step_rejects_unknown_pairing():
    rung, energy = _rand_slots(jax.random.key(0), R)
    with pytest.raises(ValueError, match="pairings"):
        kx.exchange_step(
            rung, energy, BETAS, 0, prng.key_words(jax.random.key(0)),
            pairing="windowed", criterion="logistic",
        )


# ---------- round kernels vs sweep + exchange composition -----------------------
def _ising_round_oracle(spins, key, t0, phase0, rung, energy, betas, *,
                        n_sweeps, n_rounds, pairing, criterion):
    """n_rounds x (fused interval at slot betas, then exchange_step)."""
    words = prng.key_words(key)
    na_tot = jnp.zeros((spins.shape[0],), jnp.int32)
    accs, probs, atts = [], [], []
    for k in range(n_rounds):
        spins, de, na = ops.ising_sweep_fused(
            spins, key, jnp.int32(t0 + k * n_sweeps), betas[rung],
            n_sweeps=n_sweeps, use_pallas=False,
        )
        energy = energy + de
        na_tot = na_tot + na
        rung, acc, prob, att, _ = kx.exchange_step(
            rung, energy, betas, phase0 + k, words,
            pairing=pairing, criterion=criterion,
        )
        accs.append(acc); probs.append(prob); atts.append(att)
    return (spins, rung, energy, na_tot,
            jnp.stack(accs), jnp.stack(probs), jnp.stack(atts))


@pytest.mark.parametrize("pack_bits", [False, True])
@pytest.mark.parametrize("pairing", ["deo", "seo"])
def test_ising_round_fused_matches_composition_oracle(pairing, pack_bits):
    """One launch = n_rounds full PT rounds: the round kernel must be
    BIT-equal (spins, rung map, energies, diagnostics) to the composition
    of the interval-fused sweep stream and the in-kernel exchange — and the
    pure-JAX reference path must match the Pallas kernel bit-for-bit."""
    key = jax.random.key(5)
    spins, betas = _rand_ising(key, R, L)
    rung, _ = _rand_slots(key, R)
    energy = ising.lattice_energy(spins, 1.0, 0.0)
    kw = dict(n_sweeps=2, n_rounds=3, pairing=pairing, criterion="logistic")
    want = _ising_round_oracle(
        spins, key, 11, 4, rung, energy, betas, **kw
    )
    got = ops.ising_round_fused(
        spins, key, jnp.int32(11), jnp.int32(4), rung, energy, betas,
        use_pallas=True, pack_bits=pack_bits, **kw
    )
    ref = ops.ising_round_fused(
        spins, key, jnp.int32(11), jnp.int32(4), rung, energy, betas,
        use_pallas=False, pack_bits=pack_bits, **kw
    )
    for g, r_, w in zip(got, ref, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(r_), np.asarray(w))


@pytest.mark.parametrize("pack_bits", [False, True])
def test_potts_round_fused_matches_composition_oracle(pack_bits):
    q, h = 3, 6
    key = jax.random.key(8)
    states = jax.random.randint(key, (5, h, h), 0, q).astype(jnp.int8)
    betas = jnp.sort(
        jax.random.uniform(jax.random.fold_in(key, 1), (5,), minval=0.2,
                           maxval=1.2)
    )[::-1]
    rung, _ = _rand_slots(key, 5)
    from repro.core.potts import potts_energy

    energy = potts_energy(states, q, 1.0)
    words = prng.key_words(key)
    s, e, ru = states, energy, rung
    na_tot = jnp.zeros((5,), jnp.int32)
    accs = []
    for k in range(2):
        s, de, na = ops.potts_sweep_fused(
            s, key, jnp.int32(3 + k * 2), betas[ru], n_sweeps=2, q=q,
            use_pallas=False,
        )
        e = e + de
        na_tot = na_tot + na
        ru, acc, _, _, _ = kx.exchange_step(
            ru, e, betas, 1 + k, words, pairing="seo", criterion="metropolis"
        )
        accs.append(acc)
    got = ops.potts_round_fused(
        states, key, jnp.int32(3), jnp.int32(1), rung, energy, betas,
        n_sweeps=2, q=q, n_rounds=2, pairing="seo", criterion="metropolis",
        pack_bits=pack_bits, use_pallas=True,
    )
    for g, w in zip(got, (s, ru, e, na_tot, jnp.stack(accs))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_round_launch_split_invariance():
    """K rounds in one launch == K single-round launches with the sweep
    counter and swap phase advanced — what makes the engine's one-round-
    per-interval calls the same chain as any benchmark multi-round launch."""
    key = jax.random.key(13)
    spins, betas = _rand_ising(key, R, L)
    rung, _ = _rand_slots(key, R)
    energy = ising.lattice_energy(spins, 1.0, 0.0)
    whole = ops.ising_round_fused(
        spins, key, jnp.int32(0), jnp.int32(0), rung, energy, betas,
        n_sweeps=2, n_rounds=3, use_pallas=True,
    )
    s, ru, e = spins, rung, energy
    for k in range(3):
        s, ru, e, _, _, _, _ = ops.ising_round_fused(
            s, key, jnp.int32(2 * k), jnp.int32(k), ru, e, betas,
            n_sweeps=2, n_rounds=1, use_pallas=True,
        )
    np.testing.assert_array_equal(np.asarray(whole[0]), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(whole[1]), np.asarray(ru))
    np.testing.assert_array_equal(np.asarray(whole[2]), np.asarray(e))


# ---------- packed interval kernels: bitwise-identical storage knob -------------
@pytest.mark.parametrize("r,r_blk", [(3, 8), (6, 4), (8, 8), (33, 64)])
def test_ising_packed_interval_bit_equal(r, r_blk):
    """pack_bits is storage only: bit-plane multispin updates must reproduce
    the unpacked fused kernel bit-for-bit — including pad > R tiles and a
    block wide enough (r_blk=64) to need a second uint32 bit-plane word."""
    key = jax.random.key(60 + r)
    spins, betas = _rand_ising(key, r, L)
    kw = dict(n_sweeps=3, j=1.0, b=0.3, r_blk=r_blk, use_pallas=True)
    plain = ops.ising_sweep_fused(spins, key, jnp.int32(7), betas, **kw)
    packed = ops.ising_sweep_fused(
        spins, key, jnp.int32(7), betas, pack_bits=True, **kw
    )
    for a, b in zip(packed, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("r,r_blk,q", [(3, 8, 3), (5, 2, 5)])
def test_potts_packed_interval_bit_equal(r, r_blk, q):
    key = jax.random.key(70 + r)
    states = jax.random.randint(key, (r, 6, 8), 0, q).astype(jnp.int8)
    betas = jax.random.uniform(
        jax.random.fold_in(key, 1), (r,), minval=0.2, maxval=1.2
    )
    kw = dict(n_sweeps=2, q=q, r_blk=r_blk, use_pallas=True)
    plain = ops.potts_sweep_fused(states, key, jnp.int32(2), betas, **kw)
    packed = ops.potts_sweep_fused(
        states, key, jnp.int32(2), betas, pack_bits=True, **kw
    )
    for a, b in zip(packed, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_potts_pack_bits_rejects_large_q():
    states = jnp.zeros((2, 4, 4), jnp.int8)
    betas = jnp.ones((2,), jnp.float32)
    with pytest.raises(ValueError, match="q <= 64"):
        ops.potts_sweep_fused(
            states, jax.random.key(0), jnp.int32(0), betas, n_sweeps=1,
            q=65, pack_bits=True, use_pallas=True,
        )
    with pytest.raises(ValueError, match="q <= 64"):
        PottsSystem(shape=(4, 4), q=65, pack_bits=True)


def test_potts_pack_bits_refused_where_compiled(monkeypatch):
    """Mosaic cannot compile the int8 lanes: on a TPU the request is
    refused, never left to degrade the run to the per-sweep path."""
    states = jnp.zeros((4, 4, 4), jnp.int8)
    with pytest.raises(ValueError, match="int8 vector lanes"):
        potts_sweep_fused_pallas(
            states, jnp.zeros((2,), jnp.uint32), jnp.zeros((1,), jnp.uint32),
            jnp.ones((4,), jnp.float32), n_sweeps=1, q=3, pack_bits=True,
            interpret=False,
        )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="int8 vector lanes"):
        PottsSystem(shape=(4, 4), use_pallas=True, use_fused=True,
                    pack_bits=True)
    # the reference path ignores the storage knob
    PottsSystem(shape=(4, 4), use_fused=True, pack_bits=True)


# ---------- engine integration: one launch per PT round -------------------------
def _engine_state(**sys_kw):
    system = ising.IsingSystem(length=L, **sys_kw)
    cfg = EngineConfig(
        n_replicas=R, swap_interval=4, chunk_intervals=3, record_trace=True
    )
    eng = Engine(system, cfg, observables={
        "am": lambda s: jnp.abs(ising.magnetization(s))
    })
    st = eng.init(jax.random.key(3), TEMPS)
    return eng, st


def test_engine_round_path_ref_pallas_packed_bit_equal():
    """use_fused_round through the engine: the pure-JAX reference, the Pallas
    round kernel and its bit-packed variant are one chain, bit-for-bit, and
    the carried incremental energy tracks the true lattice energy."""
    results = {}
    for tag, kw in {
        "ref": dict(use_pallas=False),
        "pallas": dict(use_pallas=True),
        "packed": dict(use_pallas=True, pack_bits=True),
    }.items():
        eng, st0 = _engine_state(use_fused=True, use_fused_round=True, **kw)
        results[tag] = eng.run(st0, 36)
    st_ref, res_ref = results["ref"]
    for tag in ("pallas", "packed"):
        st, res = results[tag]
        np.testing.assert_array_equal(
            np.asarray(st.pt.states), np.asarray(st_ref.pt.states), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(st.pt.rung), np.asarray(st_ref.pt.rung), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(st.pt.energy), np.asarray(st_ref.pt.energy), err_msg=tag
        )
        for k in res_ref.trace:
            np.testing.assert_array_equal(
                res.trace[k], res_ref.trace[k], err_msg=f"{tag}/{k}"
            )
    system = ising.IsingSystem(length=L)
    e_true = np.asarray(jax.vmap(system.energy)(st_ref.pt.states))
    np.testing.assert_allclose(
        np.asarray(st_ref.pt.energy), e_true, rtol=0, atol=1e-3
    )
    assert res_ref.trace["swap_attempt"].any()
    assert res_ref.trace["swap_accept"].any()


def test_round_interval_step_is_single_launch():
    """The structural claim of this optimisation: with use_fused_round the
    whole interval (sweeps AND exchange) is ONE pallas_call, and no
    `jax.random` traffic (threefry) remains in the step — the per-interval
    fused path still re-enters `jax.random` for its swap draw."""
    spec = StepSpec(n_replicas=R, sweeps_per_interval=4)
    st = pt.init_replicas(
        ising.IsingSystem(length=L, use_pallas=True, use_fused=True,
                          use_fused_round=True),
        R, jax.random.key(0),
    )
    step = make_interval_step(
        ising.IsingSystem(length=L, use_pallas=True, use_fused=True,
                          use_fused_round=True),
        spec,
    )
    txt = str(jax.make_jaxpr(step)(st, BETAS))
    assert txt.count("pallas_call") == 1
    # no host-side PRNG remains: only random_unwrap (key -> raw words for the
    # in-kernel counter PRNG), never a fold_in or a bits draw
    assert "random_fold_in" not in txt and "random_bits" not in txt
    # contrast: the interval-fused (non-round) path exits the kernel for the
    # swap phase and draws its uniforms from jax.random
    step_fused = make_interval_step(
        ising.IsingSystem(length=L, use_pallas=True, use_fused=True), spec
    )
    txt_fused = str(jax.make_jaxpr(step_fused)(st, BETAS))
    assert "random_fold_in" in txt_fused and "random_bits" in txt_fused


@pytest.mark.parametrize("bad_spec,match", [
    (dict(do_swap=False), "swaps on"),
    (dict(swap_mode="state"), "temp"),
    (dict(exchange=make_strategy("windowed")), "DEO/SEO"),
    (dict(exchange=make_strategy("vmpt")), "DEO/SEO"),
])
def test_round_path_rejects_incompatible_spec(bad_spec, match):
    """An unsupported spec must fail loudly at build time — silently falling
    back to the strategy path would change the random stream underfoot."""
    system = ising.IsingSystem(
        length=L, use_pallas=True, use_fused=True, use_fused_round=True
    )
    spec = StepSpec(n_replicas=R, sweeps_per_interval=4, **bad_spec)
    with pytest.raises(ValueError, match=match):
        make_interval_step(system, spec)


def test_use_fused_round_requires_use_fused():
    with pytest.raises(ValueError, match="use_fused=True"):
        ising.IsingSystem(length=L, use_fused_round=True)
    with pytest.raises(ValueError, match="use_fused=True"):
        PottsSystem(shape=(4, 4), use_fused_round=True)
