"""2-D Ising model system (the paper's benchmark model).

Energy follows the paper's Eq. (3) exactly::

    E(sigma) = B * sum_i sigma_i  -  J * sum_<i,j> sigma_i sigma_j

with periodic boundary conditions (the paper does not specify boundaries; PBC
is the standard Ising benchmark choice — recorded in DESIGN.md §2).  Spins are
stored as ``int8`` in {-1, +1}; replica-batched state is ``(R, L, L)``.

Two MH update modes (DESIGN.md §2):

* ``single_flip`` — faithful to the paper's per-iteration semantics: one
  random spin-flip proposal per MH iteration, via ``lax.fori_loop``.
* ``checkerboard`` — TPU-native: a *sweep* updates each colour class of the
  checkerboard in parallel (spins of one colour do not interact, so flipping
  them simultaneously with per-site MH acceptance preserves detailed balance
  per half-sweep).  This is the standard massively-parallel Metropolis update
  and is what the Pallas kernel (`repro.kernels.ising_sweep`) implements with
  VMEM-resident tiles; the pure-XLA path here is its oracle and the
  auto-partitionable fallback for lattices too large for VMEM.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

__all__ = ["IsingSystem", "lattice_energy", "neighbor_sum", "magnetization"]

UpdateMode = Literal["single_flip", "checkerboard"]


def neighbor_sum(spins: jnp.ndarray) -> jnp.ndarray:
    """Sum of the 4 nearest neighbours (PBC), same shape as ``spins``.

    Works in any float/int dtype; rolls lower to collective-permute halo
    exchanges under GSPMD when the lattice dims are sharded.
    """
    return (
        jnp.roll(spins, 1, axis=-2)
        + jnp.roll(spins, -1, axis=-2)
        + jnp.roll(spins, 1, axis=-1)
        + jnp.roll(spins, -1, axis=-1)
    )


def lattice_energy(spins: jnp.ndarray, j: float, b: float) -> jnp.ndarray:
    """Paper Eq. (3) with PBC; counts each bond once. Returns float32."""
    s = spins.astype(jnp.float32)
    # Each bond once: right + down neighbours only.
    bonds = s * (jnp.roll(s, -1, axis=-1) + jnp.roll(s, -1, axis=-2))
    return b * jnp.sum(s, axis=(-2, -1)) - j * jnp.sum(bonds, axis=(-2, -1))


def magnetization(spins: jnp.ndarray) -> jnp.ndarray:
    """Mean spin in [-1, 1]; the paper's Fig. 3a reports |m| as a percentage."""
    return jnp.mean(spins.astype(jnp.float32), axis=(-2, -1))


def _delta_e(spins_f: jnp.ndarray, nbr: jnp.ndarray, j: float, b: float) -> jnp.ndarray:
    """Energy change of flipping each spin individually.

    dE = 2*sigma_k*(J * sum_nbr(sigma) - B)   [derived from Eq. (3)]
    """
    return 2.0 * spins_f * (j * nbr - b)


@dataclasses.dataclass(frozen=True)
class IsingSystem:
    """One replica of the 2-D Ising model; vmapped by the PT driver.

    Attributes:
      length: lattice side L (L*L spins; the paper's perf runs use L=300).
      j: spin-interaction constant (paper: J=1, ferromagnet).
      b: external field (paper: B=0).
      update: "single_flip" (faithful) or "checkerboard" (TPU-native sweeps).
      flips_per_step: for single_flip, how many sequential MH iterations are
        fused into one `mcmc_step` call (keeps the scan short).
      use_pallas: checkerboard only — route the sweep through the Pallas
        kernel (interpret=True on CPU) instead of the pure-XLA path.
      use_fused: checkerboard only — run whole swap intervals through the
        interval-fused kernel (`repro.kernels.ops.ising_sweep_fused`) with
        counter-PRNG uniforms generated in-kernel instead of per-sweep
        launches fed an externally generated uniforms stream.  The random
        stream *differs* from the per-sweep path (gated statistically by the
        conformance suite, not bit-equal — DESIGN.md §6); with
        ``use_pallas=False`` the fused pure-JAX reference runs instead,
        bit-exact with the fused kernel.
      use_fused_round: checkerboard + temp-mode DEO/SEO only — fuse whole PT
        rounds (sweeps *plus* the exchange) into one launch via
        `repro.kernels.ops.ising_round_fused`; the swap uniforms come from
        the counter PRNG's swap stream instead of the engine's
        ``fold_in(key, 2t+1)`` draw (gated statistically by conformance,
        like ``use_fused``; bit-equality is pinned against the round
        kernel's own pure-JAX oracle).  Implies the ``use_fused`` sweep
        stream for the sweeps.
      pack_bits: fused paths only — bit-plane multispin spin storage inside
        the kernel (bitwise-identical trajectory; VMEM/ALU density knob).
      accept_rule: "metropolis" (paper Eq. 1) or "glauber" (heat-bath) —
        glauber keeps simultaneous checkerboard updates strictly stochastic
        (see repro.kernels.ref.accept_prob for the ergodicity caveat).
      init_balance: initial fraction of +1 spins (the paper fixes the same
        ratio of -1/+1 across replicas; 0.5 = random balanced).
      r_blk: replicas per Pallas grid step; 8 is the documented
        v5e-VMEM-safe block at the paper's L=300 (`kernels.ising_sweep`).
    """

    length: int
    j: float = 1.0
    b: float = 0.0
    update: UpdateMode = "checkerboard"
    flips_per_step: int = 1
    use_pallas: bool = False
    use_fused: bool = False
    use_fused_round: bool = False
    pack_bits: bool = False
    accept_rule: str = "metropolis"
    init_balance: float = 0.5
    r_blk: int = 8

    def __post_init__(self):
        if self.update == "checkerboard" and self.length % 2 != 0:
            # With periodic boundaries an odd lattice is NOT 2-colourable:
            # wrap-around neighbours share parity, so simultaneous same-colour
            # flips would interact (caught by hypothesis property testing).
            raise ValueError(
                f"checkerboard update needs even L under PBC, got L={self.length}; "
                "use update='single_flip' for odd lattices"
            )
        if self.use_fused and self.update != "checkerboard":
            raise ValueError(
                "use_fused=True needs update='checkerboard' (the fused "
                "kernel is an interval of checkerboard sweeps)"
            )
        if self.use_fused_round and not self.use_fused:
            raise ValueError(
                "use_fused_round=True needs use_fused=True (the round "
                "kernel is the interval-fused kernel plus an in-kernel "
                "exchange)"
            )

    # -- System protocol ---------------------------------------------------
    def init_state(self, key: jax.Array) -> jnp.ndarray:
        u = jax.random.uniform(key, (self.length, self.length))
        return jnp.where(u < self.init_balance, 1, -1).astype(jnp.int8)

    def energy(self, spins: jnp.ndarray) -> jnp.ndarray:
        return lattice_energy(spins, self.j, self.b)

    def mcmc_step(self, key: jax.Array, spins: jnp.ndarray, beta: jnp.ndarray):
        if self.update == "single_flip":
            return self._single_flip_steps(key, spins, beta)
        return self._checkerboard_sweep(key, spins, beta)

    # -- faithful mode ------------------------------------------------------
    def _single_flip_steps(self, key, spins, beta):
        """``flips_per_step`` sequential single-spin MH iterations."""
        L = self.length

        def body(i, carry):
            spins, de_acc, n_acc, key = carry
            key, k_site, k_u = jax.random.split(key, 3)
            site = jax.random.randint(k_site, (2,), 0, L)
            r, c = site[0], site[1]
            s = spins[r, c].astype(jnp.float32)
            nbr = (
                spins[(r + 1) % L, c]
                + spins[(r - 1) % L, c]
                + spins[r, (c + 1) % L]
                + spins[r, (c - 1) % L]
            ).astype(jnp.float32)
            de = 2.0 * s * (self.j * nbr - self.b)
            from repro.kernels.ref import accept_prob

            accept = jax.random.uniform(k_u, ()) < accept_prob(de, beta, self.accept_rule)
            spins = spins.at[r, c].set(jnp.where(accept, -spins[r, c], spins[r, c]))
            de_acc = de_acc + jnp.where(accept, de, 0.0)
            n_acc = n_acc + accept.astype(jnp.int32)
            return spins, de_acc, n_acc, key

        spins, de, n_acc, _ = jax.lax.fori_loop(
            0, self.flips_per_step, body, (spins, jnp.float32(0), jnp.int32(0), key)
        )
        return spins, de, n_acc

    # -- TPU-native mode ----------------------------------------------------
    def _checkerboard_sweep(self, key, spins, beta):
        """One full sweep = colour-0 then colour-1 half-sweeps (one replica)."""
        with jax.named_scope("pt.rng"):
            u = jax.random.uniform(key, (2, self.length, self.length), jnp.float32)
        from repro.kernels import ref as kref

        s, de, na = kref.ising_sweep(
            spins[None], u[None], beta[None], j=self.j, b=self.b, rule=self.accept_rule
        )
        return s[0], de[0], na[0]

    # -- batched fast path (used by the PT driver instead of vmap) ----------
    def batched_mcmc_step(self, keys, spins, betas):
        """Natively replica-batched step: (R,...) in, (R,...) out.

        Dispatches to the Pallas kernel (`use_pallas=True`) or the pure-XLA
        oracle; `single_flip` mode is vmapped (its control flow is scalar).
        """
        if self.update == "single_flip":
            return jax.vmap(self._single_flip_steps)(keys, spins, betas)
        shape = (2, self.length, self.length)
        with jax.named_scope("pt.rng"):
            u = jax.vmap(lambda k: jax.random.uniform(k, shape, jnp.float32))(keys)
        from repro.kernels import ops as kops

        return kops.ising_sweep(
            spins, u, betas, j=self.j, b=self.b, rule=self.accept_rule,
            r_blk=self.r_blk, use_pallas=self.use_pallas,
        )

    # -- fused whole-interval fast path (used when use_fused=True) -----------
    def batched_mcmc_interval(self, key, t, spins, betas, *, n_sweeps,
                              replica_offset=0):
        """``n_sweeps`` replica-batched sweeps in one fused launch.

        ``key`` is the chain's root PRNG key and ``t`` the global sweep
        counter at interval entry; the counter PRNG derives every uniform
        from ``(key, t + sweep, replica, colour)``, so the result is
        independent of chunking and of how intervals were grouped into
        calls.  ``replica_offset`` (traced uint32 scalar) is the global
        index of local replica 0 when the replica axis is sharded across a
        device mesh — the counter streams stay those of the global slots.
        Returns ``(spins', delta_e, n_accepted)`` summed over the interval.
        """
        from repro.kernels import ops as kops

        return kops.ising_sweep_fused(
            spins, key, t, betas, n_sweeps=n_sweeps,
            replica_offset=replica_offset, j=self.j, b=self.b,
            rule=self.accept_rule, r_blk=self.r_blk,
            pack_bits=self.pack_bits, use_pallas=self.use_pallas,
        )

    # -- whole-round fast path (used when use_fused_round=True) --------------
    def batched_mcmc_round(self, key, t, phase, spins, rung, energy, betas,
                           *, n_sweeps, n_rounds=1, criterion="logistic",
                           pairing="deo"):
        """``n_rounds`` whole PT rounds (sweeps + temp-mode exchange) fused.

        ``phase`` is the global swap-iteration counter (keys the in-kernel
        swap draw), ``rung``/``energy`` the per-slot rung map and energies,
        ``betas`` the rung-ordered ladder.  Returns ``(spins', rung',
        energy', n_accepted, accept, prob, attempt)`` — see
        `repro.kernels.ops.ising_round_fused`.
        """
        from repro.kernels import ops as kops

        return kops.ising_round_fused(
            spins, key, t, phase, rung, energy, betas,
            n_sweeps=n_sweeps, n_rounds=n_rounds, j=self.j, b=self.b,
            rule=self.accept_rule, criterion=criterion, pairing=pairing,
            pack_bits=self.pack_bits, use_pallas=self.use_pallas,
        )
