"""Pallas-kernel vs oracle sweeps (shapes / dtypes / block sizes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ising_sweep as isk
from repro.kernels import ops, potts_sweep as psk, prng, ref


# ΔE totals are f32 sums over the lattice; spins and counts are exact.
DE_TOL = dict(rtol=1e-6, atol=1e-3)


def _assert_fused_equal(got, want):
    """(lattice, ΔE, n_accepted): exact, to DE_TOL, exact."""
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), **DE_TOL)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


def _rand_ising(key, r, l):
    k1, k2, k3 = jax.random.split(key, 3)
    spins = jnp.where(jax.random.uniform(k1, (r, l, l)) < 0.5, 1, -1).astype(jnp.int8)
    u = jax.random.uniform(k2, (r, 2, l, l), jnp.float32)
    betas = jax.random.uniform(k3, (r,), minval=0.1, maxval=1.5)
    return spins, u, betas


@pytest.mark.parametrize("r,l,r_blk", [
    (1, 4, 1), (2, 8, 2), (8, 16, 4), (8, 16, 8), (5, 12, 2),  # pad path
    (16, 30, 8),   # odd (non-128-aligned) lattice like the paper's 300
    (3, 7, 4),     # odd lattice side AND padded replicas
])
@pytest.mark.parametrize("jb", [(1.0, 0.0), (1.0, 0.4), (-0.7, -0.2)])
def test_ising_kernel_matches_oracle(r, l, r_blk, jb):
    j, b = jb
    spins, u, betas = _rand_ising(jax.random.key(r * 100 + l), r, l)
    got = ops.ising_sweep(spins, u, betas, j=j, b=b, r_blk=r_blk, use_pallas=True)
    want = ref.ising_sweep(spins, u, betas, j=j, b=b)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-6, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


def test_ising_kernel_block_size_invariance():
    """Fig-6 analogue invariant: the tile size must not change the result."""
    spins, u, betas = _rand_ising(jax.random.key(0), 16, 10)
    outs = [
        ops.ising_sweep(spins, u, betas, j=1.0, b=0.0, r_blk=rb, use_pallas=True)[0]
        for rb in (1, 2, 4, 8, 16)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))


def test_ising_vmem_model_monotonic():
    assert isk.vmem_working_set_bytes(8, 300) > isk.vmem_working_set_bytes(4, 300)
    assert isk.vmem_working_set_bytes(8, 300) < 16 * 2**20  # fits v5e VMEM


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None),
])
def test_kernels_interpret_on_cpu_compile_on_tpu_refuse_others(
    monkeypatch, backend, interpret
):
    """No silent fallback: a backend that is neither the CPU nor a TPU is
    refused instead of running the kernels in interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


# ---------- replica-padding path regression (R not a multiple of r_blk) ---------
@pytest.mark.parametrize("r", [1, 2, 3, 5, 7, 9, 11, 15, 17])
def test_ising_padding_path_bit_equal(r):
    """ops.ising_sweep pads R up to r_blk=8 with beta=0 junk replicas; every
    non-multiple R must still be BIT-equal to the unpadded oracle."""
    spins, u, betas = _rand_ising(jax.random.key(1000 + r), r, 6)
    got = ops.ising_sweep(spins, u, betas, j=1.0, b=0.1, r_blk=8, use_pallas=True)
    want = ref.ising_sweep(spins, u, betas, j=1.0, b=0.1)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-6, atol=1e-3)


# ---------- counter PRNG (the fused kernels' random stream) ---------------------
def test_threefry_known_answer_vectors():
    """Threefry-2x32-20 against the published Random123 test vectors — the
    stream contract is the cipher itself, not 'whatever this build computes'."""
    kat = [
        ((0, 0), (0, 0), (0x6B200159, 0x99BA4EFE)),
        ((0xFFFFFFFF, 0xFFFFFFFF), (0xFFFFFFFF, 0xFFFFFFFF),
         (0x1CB996FC, 0xBB002BE7)),
        ((0x13198A2E, 0x03707344), (0x243F6A88, 0x85A308D3),
         (0xC4923A9C, 0x483DF7A0)),
    ]
    for key, ctr, want in kat:
        got = prng.threefry2x32(
            jnp.uint32(key[0]), jnp.uint32(key[1]),
            jnp.uint32(ctr[0]), jnp.uint32(ctr[1]),
        )
        assert (int(got[0]), int(got[1])) == want


def test_prng_uniforms_range_and_moments():
    """[0,1) half-open contract plus crude moment sanity (catches a broken
    rotation/injection far faster than the conformance gate would)."""
    u = np.asarray(prng.plane_uniforms(
        jnp.arange(8, dtype=jnp.uint32).reshape(8, 1, 1),
        jnp.arange(8, 16, dtype=jnp.uint32).reshape(8, 1, 1),
        0, 64, 64,
    ))
    assert u.min() >= 0.0 and u.max() < 1.0
    n = u.size
    assert abs(u.mean() - 0.5) < 4.0 / np.sqrt(12 * n)
    assert abs(u.var() - 1.0 / 12.0) < 0.002


def test_prng_stream_distinct_across_counter_axes():
    """Distinct (sweep, replica, plane) must give distinct lattices — the
    injectivity the counter layout is designed for."""
    words = prng.key_words(jax.random.key(3))
    rep = jnp.arange(4, dtype=jnp.uint32)
    base = np.asarray(prng.ising_sweep_uniforms(words, 5, rep, 8))
    other_t = np.asarray(prng.ising_sweep_uniforms(words, 6, rep, 8))
    assert not np.array_equal(base, other_t)
    for r in range(1, 4):  # replica axis
        assert not np.array_equal(base[0], base[r])
    assert not np.array_equal(base[:, 0], base[:, 1])  # colour planes


# ---------- interval-fused kernels vs the per-sweep oracle stream ---------------
@pytest.mark.parametrize("n_sweeps", [1, 3])
@pytest.mark.parametrize("r,l,r_blk", [
    (1, 4, 1), (8, 10, 4), (5, 12, 2),  # pad path
    (3, 6, 8),   # pad > R (regression: tiled padding)
    (4, 30, 4),  # odd (non-128-aligned) lattice like the paper's 300
])
def test_ising_fused_bit_equals_persweep_oracle_stream(r, l, r_blk, n_sweeps):
    """The fused kernel over S sweeps must be BIT-equal in spins and
    acceptance counts to S applications of the per-sweep oracle fed the same
    counter stream.  ΔE is an f32 sum whose reduction order is the
    backend's own, so it is held to the per-sweep tests' tolerance — the
    contract the chip is held to as well."""
    key = jax.random.key(r * 10 + l)
    spins, _, betas = _rand_ising(key, r, l)
    t0 = 17
    got = ops.ising_sweep_fused(
        spins, key, jnp.int32(t0), betas, n_sweeps=n_sweeps, j=1.0, b=0.3,
        r_blk=r_blk, use_pallas=True,
    )
    words = prng.key_words(key)
    rep = jnp.arange(r, dtype=jnp.uint32)
    s, de, na = spins, jnp.zeros((r,), jnp.float32), jnp.zeros((r,), jnp.int32)
    for i in range(n_sweeps):
        u = prng.ising_sweep_uniforms(words, t0 + i, rep, l)
        s, d, n = ref.ising_sweep(s, u, betas, j=1.0, b=0.3)
        de, na = de + d, na + n
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(s))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(de), **DE_TOL)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(na))
    # and the pure-JAX fused reference is the same stream, bit-for-bit
    rf = ops.ising_sweep_fused(
        spins, key, jnp.int32(t0), betas, n_sweeps=n_sweeps, j=1.0, b=0.3,
        r_blk=r_blk, use_pallas=False,
    )
    _assert_fused_equal(got, rf)


@pytest.mark.parametrize("n_sweeps", [1, 3])
@pytest.mark.parametrize("r,h,w,r_blk,q", [
    (1, 4, 4, 1, 3), (5, 8, 6, 2, 4),  # pad path
    (3, 6, 6, 8, 3),  # pad > R (regression: tiled padding)
])
@pytest.mark.parametrize("rule", ["metropolis", "glauber"])
def test_potts_fused_bit_equals_persweep_oracle_stream(r, h, w, r_blk, q, rule, n_sweeps):
    key = jax.random.key(r * 7 + h + q)
    states, _, betas = _rand_potts(key, r, h, w, q)
    t0 = 5
    got = ops.potts_sweep_fused(
        states, key, jnp.int32(t0), betas, n_sweeps=n_sweeps, q=q, j=0.8,
        rule=rule, r_blk=r_blk, use_pallas=True,
    )
    words = prng.key_words(key)
    rep = jnp.arange(r, dtype=jnp.uint32)
    s, de, na = states, jnp.zeros((r,), jnp.float32), jnp.zeros((r,), jnp.int32)
    for i in range(n_sweeps):
        u = prng.potts_sweep_uniforms(words, t0 + i, rep, h, w)
        s, d, n = ref.potts_sweep(s, u, betas, q=q, j=0.8, rule=rule)
        de, na = de + d, na + n
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(s))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(de), **DE_TOL)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(na))
    rf = ops.potts_sweep_fused(
        states, key, jnp.int32(t0), betas, n_sweeps=n_sweeps, q=q, j=0.8,
        rule=rule, r_blk=r_blk, use_pallas=False,
    )
    _assert_fused_equal(got, rf)


def test_ising_fused_block_size_invariance():
    """Fig-6 invariant extended to the fused kernel: neither the replica
    tile size nor the padding it implies may change the stream (real
    replicas keep counter indices 0..R-1)."""
    key = jax.random.key(2)
    spins, _, betas = _rand_ising(key, 6, 8)
    outs = [
        ops.ising_sweep_fused(
            spins, key, jnp.int32(0), betas, n_sweeps=2, r_blk=rb,
            use_pallas=True,
        )[0]
        for rb in (1, 2, 3, 6, 8)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))


def test_fused_interval_equals_split_intervals():
    """Chunking invariance: one fused 4-sweep interval == two fused 2-sweep
    intervals with the counter advanced — what makes engine chunk/interval
    boundaries invisible to the fused chain."""
    key = jax.random.key(9)
    spins, _, betas = _rand_ising(key, 4, 6)
    whole = ops.ising_sweep_fused(
        spins, key, jnp.int32(10), betas, n_sweeps=4, use_pallas=True
    )
    s1, de1, na1 = ops.ising_sweep_fused(
        spins, key, jnp.int32(10), betas, n_sweeps=2, use_pallas=True
    )
    s2, de2, na2 = ops.ising_sweep_fused(
        s1, key, jnp.int32(12), betas, n_sweeps=2, use_pallas=True
    )
    np.testing.assert_array_equal(np.asarray(whole[0]), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(whole[2]), np.asarray(na1 + na2))
    np.testing.assert_allclose(
        np.asarray(whole[1]), np.asarray(de1 + de2), rtol=1e-6, atol=1e-3
    )


# ---------- per-sweep padding regression: pad > R (e.g. R=3 at r_blk=8) ---------
@pytest.mark.parametrize("r,r_blk", [(3, 8), (2, 8), (1, 4), (5, 16)])
def test_potts_padding_exceeding_r_bit_equal(r, r_blk):
    """`ops` wrappers must tile the replica padding: with pad > R the old
    `x[:pad]` under-padded states/u while betas padded fully, leaving the
    kernel mismatched shapes."""
    states, u, betas = _rand_potts(jax.random.key(40 + r), r, 6, 6, 3)
    got = ops.potts_sweep(states, u, betas, q=3, j=1.0, r_blk=r_blk,
                          use_pallas=True)
    want = ref.potts_sweep(states, u, betas, q=3, j=1.0)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-6, atol=1e-3)


def test_vmem_working_set_documented_budget():
    """The documented v5e budget for the paper's L=300 config must hold: the
    Ising kernel's r_blk=8 working set is the 18 B/cell (~12.4 MiB) modelled
    in its module docstring and stays inside a v5e core's 16 MB VMEM; the
    Potts kernel (30 B/cell) fits the same budget at its documented
    r_blk=4 default."""
    ising_bytes = isk.vmem_working_set_bytes(8, 300)
    assert ising_bytes == 18 * 8 * 300 * 300  # 18 bytes/cell model, ~12.4 MiB
    assert ising_bytes < 16 * 2**20
    potts_bytes = psk.vmem_working_set_bytes(4, 300, 300)
    assert potts_bytes == 30 * 4 * 300 * 300  # 30 bytes/cell (module docstring)
    assert potts_bytes < 16 * 2**20
    # both models are monotone in every argument (sanity of the estimator)
    assert psk.vmem_working_set_bytes(8, 300, 300) > potts_bytes
    assert psk.vmem_working_set_bytes(4, 300, 302) > potts_bytes


def test_vmem_fused_documented_budget():
    """The fused kernels' working sets at the documented blocks must still
    fit a v5e core's 16 MB: 18 B/cell Ising (+O(r_blk) RNG state) and
    22 B/cell Potts — fusion trades the uniforms input block for one
    in-flight plane of PRNG draws, so VMEM stays flat while HBM traffic
    collapses."""
    ising = isk.vmem_working_set_bytes_fused(8, 300)
    assert ising == 18 * 8 * 300 * 300 + 16 * 8
    assert ising < 16 * 2**20
    potts = psk.vmem_working_set_bytes_fused(4, 300, 300)
    assert potts == 22 * 4 * 300 * 300 + 16 * 4
    assert potts < 16 * 2**20
    # fused never exceeds the per-sweep working set by more than the RNG state
    assert ising <= isk.vmem_working_set_bytes(8, 300) + 16 * 8
    assert potts <= psk.vmem_working_set_bytes(4, 300, 300)


def test_vmem_packed_documented_budget():
    """The packed working-set models must match their module docstrings: the
    bit-plane Ising kernel lands at 17.5 B/cell for r_blk=8 (vs 18 unpacked)
    and the int8-lane Potts kernel at 16 B/cell (vs 22) — packing never
    costs VMEM at the documented blocks."""
    ising_packed = isk.vmem_working_set_bytes_packed(8, 300)
    assert ising_packed == 12_600_128  # 17.5 B/cell + RNG state at L=300
    assert ising_packed < isk.vmem_working_set_bytes_fused(8, 300)
    assert ising_packed < 16 * 2**20
    # a second uint32 word only appears past 32 replicas per block
    per_cell_32 = (isk.vmem_working_set_bytes_packed(32, 300) - 16 * 32) / (
        32 * 300 * 300
    )
    assert per_cell_32 == pytest.approx(15.625)
    potts_packed = psk.vmem_working_set_bytes_packed(4, 300, 300)
    assert potts_packed == 16 * 4 * 300 * 300 + 16 * 4
    assert potts_packed < psk.vmem_working_set_bytes_fused(4, 300, 300)
    assert potts_packed < 16 * 2**20


def test_hbm_traffic_model_rounds_axis():
    """Whole-round fusion extends the amortization to S*K sweeps per launch,
    in both kernel modules and the shared `hlo.traffic` source of truth."""
    from repro.hlo import traffic

    assert isk.hbm_bytes_per_cell_sweep(
        fused=True, sweeps_per_interval=4, rounds_per_launch=2
    ) == pytest.approx(0.25)
    for s, k in ((1, 1), (4, 2), (5, 16)):
        want = 2.0 / (s * k)
        for fn in (
            isk.hbm_bytes_per_cell_sweep,
            psk.hbm_bytes_per_cell_sweep,
            lambda **kw: traffic.hbm_bytes_per_cell_sweep(**kw),
        ):
            assert fn(
                fused=True, sweeps_per_interval=s, rounds_per_launch=k
            ) == pytest.approx(want)
    # rounds never change the unfused model, and zero rounds is an error
    assert isk.hbm_bytes_per_cell_sweep(fused=False) == 18.0
    with pytest.raises(ValueError, match="rounds_per_launch"):
        isk.hbm_bytes_per_cell_sweep(
            fused=True, sweeps_per_interval=1, rounds_per_launch=0
        )


def test_hbm_traffic_model_fused_speedup():
    """The acceptance bar for this optimisation: modeled HBM bytes per cell
    per sweep must drop >= 5x on the fused Ising path — already 9x at one
    sweep per interval (18 -> 2 B), scaling linearly with the interval."""
    unfused = isk.hbm_bytes_per_cell_sweep(fused=False)
    assert unfused == 18.0
    assert unfused >= 5 * isk.hbm_bytes_per_cell_sweep(
        fused=True, sweeps_per_interval=1
    )
    assert isk.hbm_bytes_per_cell_sweep(fused=True, sweeps_per_interval=100) == (
        pytest.approx(0.02)
    )
    # Potts: 34 -> 2/S B per cell per sweep
    assert psk.hbm_bytes_per_cell_sweep(fused=False) == 34.0
    assert psk.hbm_bytes_per_cell_sweep(fused=False) >= 5 * (
        psk.hbm_bytes_per_cell_sweep(fused=True, sweeps_per_interval=1)
    )
    # the kernel modules keep their models local — pin the fused branches
    # against silent divergence: both amortize the same int8 in+out over
    # the interval
    for s in (1, 4, 100):
        assert isk.hbm_bytes_per_cell_sweep(
            fused=True, sweeps_per_interval=s
        ) == psk.hbm_bytes_per_cell_sweep(fused=True, sweeps_per_interval=s)


# ---------- Potts kernel vs oracle ----------------------------------------------
def _rand_potts(key, r, h, w, q):
    k1, k2, k3 = jax.random.split(key, 3)
    states = jax.random.randint(k1, (r, h, w), 0, q).astype(jnp.int8)
    u = jax.random.uniform(k2, (r, 2, 2, h, w), jnp.float32)
    betas = jax.random.uniform(k3, (r,), minval=0.1, maxval=1.5)
    return states, u, betas


@pytest.mark.parametrize("r,h,w,r_blk,q", [
    (1, 4, 4, 1, 3), (2, 8, 6, 2, 3), (8, 16, 16, 4, 4), (5, 12, 10, 2, 3),
    (3, 7, 9, 4, 5),   # pad path AND odd lattice dims
    (16, 30, 30, 8, 2),  # q=2 (Ising twin), non-128-aligned like the paper
])
@pytest.mark.parametrize("rule", ["metropolis", "glauber"])
def test_potts_kernel_matches_oracle(r, h, w, r_blk, q, rule):
    states, u, betas = _rand_potts(jax.random.key(r * 100 + h + q), r, h, w, q)
    got = ops.potts_sweep(states, u, betas, q=q, j=0.8, rule=rule,
                          r_blk=r_blk, use_pallas=True)
    want = ref.potts_sweep(states, u, betas, q=q, j=0.8, rule=rule)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-6, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


def test_potts_kernel_block_size_invariance():
    """Same Fig-6 invariant as Ising: the replica tile size must not change
    the sweep's result."""
    states, u, betas = _rand_potts(jax.random.key(0), 16, 8, 8, 3)
    outs = [
        ops.potts_sweep(states, u, betas, q=3, j=1.0, r_blk=rb, use_pallas=True)[0]
        for rb in (1, 2, 4, 8, 16)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))


def test_potts_proposals_never_propose_current_colour():
    """d in {1..q-1} guarantees every proposal differs from the current
    colour; with acceptance u=0 (always accept) every unmasked site of each
    colour class must change."""
    r, h, w, q = 2, 4, 4, 5
    states = jnp.zeros((r, h, w), jnp.int8)
    u = jnp.zeros((r, 2, 2, h, w), jnp.float32)
    u = u.at[:, :, 0].set(jax.random.uniform(jax.random.key(3), (r, 2, h, w)))
    new, _, nacc = ref.potts_sweep(states, u, jnp.ones((r,)), q=q, j=1.0)
    assert np.all(np.asarray(new) != 0)  # every site flipped away from 0
    assert np.all(np.asarray(nacc) == h * w)


def _rand_wkv(key, bh, t, dk, dv, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (bh, t, dk), dtype)
    k = jax.random.normal(ks[1], (bh, t, dk), dtype)
    v = jax.random.normal(ks[2], (bh, t, dv), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (bh, t, dk), dtype))
    u = jax.random.normal(ks[4], (bh, dk), dtype)
    return r, k, v, w, u


@pytest.mark.parametrize("bh,t,dk,dv,chunk", [
    (1, 8, 4, 4, 4), (2, 32, 8, 16, 8), (4, 33, 8, 8, 16),  # pad path
    (3, 64, 64, 64, 32), (2, 16, 16, 8, 16),
])
def test_wkv6_kernel_matches_oracle(bh, t, dk, dv, chunk):
    r, k, v, w, u = _rand_wkv(jax.random.key(bh * 7 + t), bh, t, dk, dv)
    o1, s1 = ops.wkv6(r, k, v, w, u, chunk=chunk, use_pallas=True)
    o2, s2 = ops.wkv6(r, k, v, w, u, use_pallas=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=3e-5, atol=3e-5)


def test_wkv6_initial_state_threading():
    """Chunked decode: running T=32 in two halves == one shot (cache reuse)."""
    bh, t, dk, dv = 2, 32, 8, 8
    r, k, v, w, u = _rand_wkv(jax.random.key(5), bh, t, dk, dv)
    o_full, s_full = ops.wkv6(r, k, v, w, u, chunk=8)
    o1, s1 = ops.wkv6(r[:, :16], k[:, :16], v[:, :16], w[:, :16], u, chunk=8)
    o2, s2 = ops.wkv6(r[:, 16:], k[:, 16:], v[:, 16:], w[:, 16:], u, s1, chunk=8)
    np.testing.assert_allclose(np.asarray(o_full), np.asarray(jnp.concatenate([o1, o2], 1)), rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2), rtol=3e-5, atol=3e-5)


def test_wkv6_decay_semantics():
    """w=1, k=0 must be the identity (state preserved, output = r @ S)."""
    bh, dk, dv = 1, 4, 4
    s0 = jnp.arange(dk * dv, dtype=jnp.float32).reshape(1, dk, dv)
    r = jnp.ones((1, 2, dk))
    k = jnp.zeros((1, 2, dk))
    v = jnp.zeros((1, 2, dv))
    w = jnp.ones((1, 2, dk))
    u = jnp.zeros((1, dk))
    o, s = ops.wkv6(r, k, v, w, u, s0, chunk=2)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s0), rtol=1e-6)
    want = np.asarray(jnp.einsum("bk,bkv->bv", r[:, 0], s0))
    np.testing.assert_allclose(np.asarray(o[0, 0]), want[0], rtol=1e-6)
