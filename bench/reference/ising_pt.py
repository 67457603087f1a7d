"""Plain reference of parallel tempering over the 2-D Ising model.

A straightforward `jax.numpy` statement of the semantics the sampler
promises for one seed, written from its published contract and sharing no
code with it:

* replica ``r`` starts from ``split(split(key(seed))[0], R)[r]`` with each
  site +1 where a uniform draw is below one half;
* a sweep is a checkerboard Metropolis update, colour 0 then colour 1, where
  the site flips when ``u < exp(-beta * dE)`` and
  ``dE = 2 s (J * sum(neighbours) - B)`` under periodic boundaries;
* the per-sweep path draws ``u`` as ``uniform(fold_in(fold_in(k, 2t), r),
  (2, L, L))``; the interval-fused path draws it from Threefry-2x32-20 keyed
  on ``(seed words, t, replica, colour, site)`` under a fixed domain
  constant, taking the top 24 bits;
* every ``swap_interval`` sweeps, rungs pair even/odd by the swap counter and
  the pair swaps temperatures when ``uniform(fold_in(k, 2t + 1), (R,))`` at
  its lower rung is below ``sigmoid(dbeta * dE)``.

``dtype`` is the precision of every floating step.  float32 is what the
configuration states; bfloat16 is the control, which the comparison has to
fail.  Runs in blocks of replicas so that the reference fits beside nothing
else on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Threefry-2x32-20 (Salmon et al., SC'11), as in Random123 and jax.random.
_KS_PARITY = 0x1BD11BDA
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
# Domain constant of the interval-fused sweep stream (ascii "FUSE").
FUSED_DOMAIN = 0x46555345


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32 with 20 rounds; every argument broadcastable uint32."""
    u32 = lambda v: jnp.asarray(v, jnp.uint32)
    k0, k1, x0, x1 = u32(k0), u32(k1), u32(x0), u32(x1)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_KS_PARITY))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for group in range(5):
        for d in _ROTATIONS[group % 2]:
            x0 = x0 + x1
            x1 = ((x1 << jnp.uint32(d)) | (x1 >> jnp.uint32(32 - d))) ^ x0
        x0 = x0 + ks[(group + 1) % 3]
        x1 = x1 + ks[(group + 2) % 3] + jnp.uint32(group + 1)
    return x0, x1


def ladder_temps(ladder: dict, r: int) -> np.ndarray:
    """Temperatures of an ``r``-rung ladder, cold to hot, as float64.

    ``paper``: T_i = t_min + i (t_max - t_min) / R in float32, hot end
    excluded.
    """
    if ladder["kind"] != "paper":
        raise ValueError(f"no reference ladder of kind {ladder['kind']!r}")
    t_min, t_max = ladder["t_min"], ladder["t_max"]
    i = np.arange(r, dtype=np.float32)
    t = np.float32(t_min) + i * np.float32((t_max - t_min) / r)
    return t.astype(np.float64)


def ladder_betas(ladder: dict, r: int) -> np.ndarray:
    """Inverse temperatures in float32, rung order."""
    return (1.0 / ladder_temps(ladder, r)).astype(np.float32)


def init_chain(seed: int, n_replicas: int, length: int, dtype=jnp.float32):
    """(spins (R, L, L) int8, run key) for one chain started from ``seed``."""
    k_init, k_run = jax.random.split(jax.random.key(seed))
    keys = jax.random.split(k_init, n_replicas)
    u = jax.vmap(lambda k: jax.random.uniform(k, (length, length)))(keys)
    return jnp.where(u.astype(dtype) < 0.5, 1, -1).astype(jnp.int8), k_run


def energy(spins, j: float = 1.0, b: float = 0.0, dtype=jnp.float32):
    """E = B sum(s) - J sum over bonds (right and down neighbour), per replica."""
    s = spins.astype(dtype)
    bonds = s * (jnp.roll(s, -1, axis=-1) + jnp.roll(s, -1, axis=-2))
    return (jnp.asarray(b, dtype) * jnp.sum(s, axis=(-2, -1))
            - jnp.asarray(j, dtype) * jnp.sum(bonds, axis=(-2, -1)))


def _sweep(s, u, beta, parity, j, b, dtype):
    """One checkerboard sweep of a replica block; returns (s, dE summed)."""
    de_sum = jnp.zeros(s.shape[0], dtype)
    for colour in (0, 1):
        nbr = (jnp.roll(s, 1, axis=-2) + jnp.roll(s, -1, axis=-2)
               + jnp.roll(s, 1, axis=-1) + jnp.roll(s, -1, axis=-1))
        de = jnp.asarray(2, dtype) * s * (jnp.asarray(j, dtype) * nbr
                                           - jnp.asarray(b, dtype))
        flip = (u[:, colour].astype(dtype) < jnp.exp(-beta * de)) & (parity == colour)
        s = jnp.where(flip, -s, s)
        de_sum = de_sum + jnp.sum(jnp.where(flip, de, 0), axis=(-2, -1))
    return s, de_sum


def _uniforms(path, key, t, slots, length):
    """(n, 2, L, L) f32 uniforms of sweep ``t`` for the replica ``slots``."""
    if path == "per_sweep":
        base = jax.random.fold_in(key, 2 * t)
        keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(slots.astype(jnp.uint32))
        return jax.vmap(lambda k: jax.random.uniform(k, (2, length, length)))(keys)
    words = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    s0, s1 = threefry2x32(words[0], words[1], FUSED_DOMAIN, FUSED_DOMAIN)
    w0, w1 = threefry2x32(s0, s1, t.astype(jnp.uint32),
                          slots.astype(jnp.uint32).reshape(-1, 1, 1))
    i = jnp.arange(length, dtype=jnp.uint32)
    site = i[:, None] * jnp.uint32(length) + i[None, :]
    planes = []
    for colour in (0, 1):
        bits, _ = threefry2x32(w0, w1, jnp.uint32(colour), site)
        top = (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
        planes.append(top * jnp.float32(1.0 / (1 << 24)))
    return jnp.stack(planes, axis=1)


def _swap(key, t, phase, rung, energy_, betas, dtype):
    """One even/odd exchange on temperature labels; returns the new rungs."""
    n = rung.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    holder = jnp.argsort(rung)  # slot holding each rung
    e = energy_[holder].astype(dtype)
    odd = phase % 2
    partner = jnp.where(odd == 0, idx ^ 1, jnp.where(idx == 0, 0, ((idx - 1) ^ 1) + 1))
    partner = jnp.where(partner >= n, idx, partner)
    is_lower = (partner != idx) & (idx < partner)
    beta = betas.astype(dtype)
    p = jax.nn.sigmoid((beta - beta[partner]) * (e - e[partner]))
    u = jax.random.uniform(jax.random.fold_in(key, 2 * t + 1), (n,)).astype(dtype)
    accept_lower = (u < p) & is_lower
    swapped = accept_lower[jnp.minimum(idx, partner)] & (partner != idx)
    new_rung_of_holder = jnp.where(swapped, partner, idx)
    return jnp.zeros((n,), jnp.int32).at[holder].set(new_rung_of_holder)


@functools.partial(jax.jit, static_argnames=(
    "path", "n_intervals", "sweeps_per_interval", "j", "b", "dtype", "block"))
def advance(spins, energy_, rung, t, phase, key, betas, *, path: str,
            n_intervals: int, sweeps_per_interval: int, j: float = 1.0,
            b: float = 0.0, dtype=jnp.float32, block: int = 0):
    """Advance one chain by ``n_intervals`` intervals of sweeps then a swap.

    ``spins`` (R, L, L) int8, ``energy_`` (R,) running energies, ``rung``
    (R,) slot -> rung, ``t``/``phase`` the sweep and swap counters, ``key``
    the chain's run key, ``betas`` (R,) rung order.  ``block`` replicas are
    swept at a time (0: all).  Returns (spins, energy, rung, t, phase) with
    the energy carried as the running sum of accepted dE, as the sampler
    carries it.
    """
    r, length = spins.shape[0], spins.shape[-1]
    block = block or r
    if r % block:
        raise ValueError(f"block {block} does not divide {r} replicas")
    ii = jnp.arange(length)
    parity = (ii[:, None] + ii[None, :]) % 2
    slots = jnp.arange(r, dtype=jnp.int32).reshape(r // block, block)

    def interval(carry, _):
        s, e, rg, t, ph = carry
        beta_slot = betas.astype(dtype)[rg].reshape(r // block, block, 1, 1)

        def sweep_block(args):
            s_blk, slot_blk, beta_blk = args

            def one(k, c):
                s_f, de = c
                u = _uniforms(path, key, t + k, slot_blk, length)
                s_f, d = _sweep(s_f, u, beta_blk, parity, j, b, dtype)
                return s_f, de + d

            s_f, de = jax.lax.fori_loop(
                0, sweeps_per_interval, one,
                (s_blk.astype(dtype), jnp.zeros(block, dtype)))
            return s_f.astype(jnp.int8), de

        s_blocks, de = jax.lax.map(sweep_block, (
            s.reshape(r // block, block, length, length), slots, beta_slot))
        s = s_blocks.reshape(r, length, length)
        e = e + de.reshape(r)
        t = t + sweeps_per_interval
        rg = _swap(key, t, ph, rg, e, betas, dtype)
        return (s, e, rg, t, ph + 1), None

    out, _ = jax.lax.scan(interval, (spins, energy_.astype(dtype), rung, t, phase),
                          None, length=n_intervals)
    return out

