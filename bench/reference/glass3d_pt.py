"""Plain reference of parallel tempering over the ±J Edwards-Anderson spin
glass, many disorder samples at once, on a periodic 2-D or 3-D lattice.

A straightforward `jax.numpy` statement of the semantics the sampler
promises for one seed, on the plain ``(..., *shape)`` lattice, written from
its published contract and sharing no code with it:

* sample ``k``'s bonds are ``J[d] = +1`` where
  ``uniform(fold_in(key(disorder_seed), k), (rank, *shape))[d] < 1/2``,
  else -1: the bond from ``x`` to ``x + e_d``.  Chain ``c`` holds the bonds
  of sample ``c // copies``;
* chain ``c`` of an ensemble starts from ``fold_in(key(seed), c)`` (a lone
  chain from ``key(seed)``), split into ``(k_init, k_run)``; its replica
  ``r`` starts from ``split(k_init, R)[r]`` with each site +1 where a
  uniform draw is below one half;
* ``E = -j sum_x sum_d J[d](x) s(x) s(x + e_d)``;
* a sweep updates colour 0, then colour 1 (the parity of ``sum_d x_d``):
  a site flips where ``u < table[(s h + 2 rank) / 2]``, with
  ``h = sum_d J[d](x) s(x + e_d) + J[d](x - e_d) s(x - e_d)`` and the table
  the acceptance of ``dE = 2 j v`` for ``v = -2 rank, ..., 2 rank`` in steps
  of 2 at the replica's beta (``glauber``: ``sigmoid(-beta dE)``;
  ``metropolis``: ``exp(-beta dE)``).  ``u`` of colour ``k`` in sweep ``t``
  is ``uniform(fold_in(fold_in(fold_in(k_run, 2t), r), k), shape)``;
* every ``swap_interval`` sweeps, rungs pair even/odd by the swap counter
  and the pair swaps temperatures when ``uniform(fold_in(k_run, 2t + 1),
  (R,))`` at its lower rung is below ``min(1, exp(min(dbeta dE, 80)))``
  (``metropolis``) or ``sigmoid(dbeta dE)`` (``logistic``).

``dtype`` is the precision of every floating step.  float32 is what the
configuration states; bfloat16 is the control, which the comparison has to
fail.  Energies are integers below 2**24, so in float32 every step is
exact.  Chains run one after another and replicas in blocks, so that the
reference fits on a chip after the program's buffers are freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def ladder_betas(ladder: dict, r: int) -> np.ndarray:
    """Inverse temperatures in float32, rung order, of a ``linear`` ladder:
    ``linspace(t_min, t_max, r)`` in float32, both ends included."""
    if ladder["kind"] != "linear":
        raise ValueError(f"no reference ladder of kind {ladder['kind']!r}")
    temps = np.asarray(jnp.linspace(ladder["t_min"], ladder["t_max"], r,
                                    dtype=jnp.float32), np.float64)
    return (1.0 / temps).astype(np.float32)


def sample_bonds(disorder_seed: int, sample, shape, dtype=jnp.float32):
    """(rank, *shape) int8 ±1 forward bonds of one disorder sample."""
    key = jax.random.fold_in(jax.random.key(disorder_seed), sample)
    u = jax.random.uniform(key, (len(shape), *shape))
    return jnp.where(u.astype(dtype) < 0.5, 1, -1).astype(jnp.int8)


def chain_bonds(disorder_seed: int, n_chains: int, copies: int, shape,
                dtype=jnp.float32):
    """(C, rank, *shape) int8: chain ``c`` holds sample ``c // copies``."""
    samples = jnp.arange(n_chains, dtype=jnp.uint32) // jnp.uint32(copies)
    return jax.lax.map(lambda k: sample_bonds(disorder_seed, k, shape, dtype), samples)


@functools.partial(jax.jit, static_argnames=("seed", "n_chains", "n_replicas",
                                             "shape", "dtype"))
def init_chains(seed: int, n_chains: int, n_replicas: int, shape,
                dtype=jnp.float32):
    """(spins (C, R, *shape) int8, run keys (C,)) of ``n_chains`` chains."""
    root = jax.random.key(seed)
    if n_chains == 1:
        chain_keys = root[None]
    else:
        chain_keys = jax.vmap(jax.random.fold_in, (None, 0))(
            root, jnp.arange(n_chains, dtype=jnp.uint32))

    def one(key):
        k_init, k_run = jax.random.split(key)
        keys = jax.random.split(k_init, n_replicas)
        u = jax.vmap(lambda k: jax.random.uniform(k, shape))(keys)
        return jnp.where(u.astype(dtype) < 0.5, 1, -1).astype(jnp.int8), k_run

    return jax.lax.map(one, chain_keys)


def _energy(spins, bonds, j, dtype):
    """(n,) energies of replicas (n, *shape) under one chain's bonds."""
    s = spins.astype(dtype)
    axes = tuple(range(1, s.ndim))
    e = jnp.zeros(s.shape[0], dtype)
    for d in range(bonds.shape[0]):
        e = e + jnp.sum(bonds[d].astype(dtype) * s * jnp.roll(s, -1, axis=d + 1),
                        axis=axes)
    return -jnp.asarray(j, dtype) * e


@functools.partial(jax.jit, static_argnames=("j", "dtype"))
def energy(spins, bonds, j: float = 1.0, dtype=jnp.float32):
    """(C, R) energies of chains' replicas ``(C, R, *shape)`` under their
    bonds ``(C, rank, *shape)``."""
    return jax.lax.map(lambda a: _energy(a[0], a[1], j, dtype), (spins, bonds))


def _accept(de, beta, rule):
    if rule == "glauber":
        return jax.nn.sigmoid(-beta * de)
    if rule == "metropolis":
        return jnp.exp(-beta * de)
    raise ValueError(f"unknown acceptance rule {rule!r}")


def _sweep(s, keys, beta, bonds, parity, j, rule, dtype):
    """One checkerboard sweep of a replica block ``(n, *shape)`` held in
    ``dtype``; returns (s, dE summed per replica)."""
    rank = bonds.ndim - 1
    shape = s.shape[1:]
    b = bonds.astype(dtype)
    back = [jnp.roll(b[d], 1, axis=d) for d in range(rank)]  # J[d](x - e_d)
    de_values = jnp.asarray([2.0 * j * v for v in range(-2 * rank, 2 * rank + 1, 2)],
                            dtype)
    table = _accept(de_values[None, :], beta[:, None].astype(dtype), rule)
    de_sum = jnp.zeros(s.shape[0], dtype)
    for colour in (0, 1):
        u = jax.vmap(lambda k: jax.random.uniform(jax.random.fold_in(k, colour),
                                                  shape))(keys)
        h = sum(b[d] * jnp.roll(s, -1, axis=d + 1) + back[d] * jnp.roll(s, 1, axis=d + 1)
                for d in range(rank))
        sh = s * h
        index = ((sh + 2 * rank) / 2).astype(jnp.int32)
        p = jnp.take_along_axis(table, index.reshape(s.shape[0], -1), axis=1)
        flip = (u.astype(dtype) < p.reshape(s.shape)) & (parity == colour)
        s = jnp.where(flip, -s, s)
        de_sum = de_sum + jnp.sum(jnp.where(flip, jnp.asarray(2.0 * j, dtype) * sh, 0),
                                  axis=tuple(range(1, s.ndim)))
    return s, de_sum


def _swap(key, t, phase, rung, energy_, betas, criterion, dtype):
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
    arg = (beta - beta[partner]) * (e - e[partner])
    if criterion == "metropolis":
        p = jnp.minimum(1.0, jnp.exp(jnp.minimum(arg, 80.0)))
    elif criterion == "logistic":
        p = jax.nn.sigmoid(arg)
    else:
        raise ValueError(f"unknown criterion {criterion!r}")
    u = jax.random.uniform(jax.random.fold_in(key, 2 * t + 1), (n,)).astype(dtype)
    accept_lower = (u < p) & is_lower
    swapped = accept_lower[jnp.minimum(idx, partner)] & (partner != idx)
    new_rung_of_holder = jnp.where(swapped, partner, idx)
    return jnp.zeros((n,), jnp.int32).at[holder].set(new_rung_of_holder)


def _advance_chain(spins, energy_, rung, t, phase, key, betas, bonds, *,
                   n_intervals, sweeps_per_interval, j, rule, criterion, dtype,
                   block):
    r, shape = spins.shape[0], spins.shape[1:]
    grid = jnp.meshgrid(*(jnp.arange(n) for n in shape), indexing="ij")
    parity = sum(grid) % 2
    slots = jnp.arange(r, dtype=jnp.uint32).reshape(r // block, block)

    def interval(carry, _):
        s, e, rg, t, ph = carry
        beta_slot = betas.astype(dtype)[rg].reshape(r // block, block)

        def sweep_block(args):
            s_blk, slot_blk, beta_blk = args

            def one(k, c):
                s_f, de = c
                base = jax.random.fold_in(key, 2 * (t + k))
                keys = jax.vmap(jax.random.fold_in, (None, 0))(base, slot_blk)
                s_f, d = _sweep(s_f, keys, beta_blk, bonds, parity, j, rule, dtype)
                return s_f, de + d

            s_f, de = jax.lax.fori_loop(
                0, sweeps_per_interval, one,
                (s_blk.astype(dtype), jnp.zeros(block, dtype)))
            return s_f.astype(jnp.int8), de

        s_blocks, de = jax.lax.map(sweep_block, (
            s.reshape(r // block, block, *shape), slots, beta_slot))
        s = s_blocks.reshape(r, *shape)
        e = e + de.reshape(r)
        t = t + sweeps_per_interval
        rg = _swap(key, t, ph, rg, e, betas, criterion, dtype)
        return (s, e, rg, t, ph + 1), None

    out, _ = jax.lax.scan(interval, (spins, energy_.astype(dtype), rung, t, phase),
                          None, length=n_intervals)
    return out


@functools.partial(jax.jit, static_argnames=(
    "n_intervals", "sweeps_per_interval", "j", "rule", "criterion", "dtype",
    "block"))
def advance(spins, energy_, rung, t, phase, keys, betas, bonds, *,
            n_intervals: int, sweeps_per_interval: int, j: float = 1.0,
            rule: str = "glauber", criterion: str = "metropolis",
            dtype=jnp.float32, block: int = 0):
    """Advance chains by ``n_intervals`` intervals of sweeps then a swap.

    ``spins`` (C, R, *shape) int8, ``energy_`` (C, R) running energies,
    ``rung`` (C, R) slot -> rung, ``t``/``phase`` (C,) the sweep and swap
    counters, ``keys`` (C,) the chains' run keys, ``betas`` (R,) rung order,
    ``bonds`` (C, rank, *shape) int8.  ``block`` replicas are swept at a time
    (0: all of a chain's).  Returns (spins, energy, rung, t, phase) with the
    energy carried as the running sum of accepted dE, as the sampler carries
    it.
    """
    r = spins.shape[1]
    block = block or r
    if r % block:
        raise ValueError(f"block {block} does not divide {r} replicas")
    run = functools.partial(
        _advance_chain, n_intervals=n_intervals,
        sweeps_per_interval=sweeps_per_interval, j=j, rule=rule,
        criterion=criterion, dtype=dtype, block=block)
    return jax.lax.map(lambda a: run(*a[:6], betas, a[6]),
                       (spins, energy_, rung, t, phase, keys, bonds))
