"""q-state Potts model system (the first beyond-paper lattice workload).

The Potts model generalizes Ising to ``q`` colours per site::

    E(s) = -J * sum_<x,y> delta(s_x, s_y)        (each bond counted once)

with periodic boundaries on a rectangular ``(H, W)`` lattice.  At q=2 it is
the Ising model up to an energy rescale (delta = (1 + s s')/2), and for
q >= 3 the 2-D transition turns first-order at q > 4 — a genuinely harder
free-energy landscape for PT to cross, which is why it appears in the
validation zoo (DESIGN.md §Validate).

The update is the same TPU-native checkerboard scheme as the Ising system:
sites of one parity share no bonds (PBC needs even dims — enforced), so a
whole colour class updates simultaneously with per-site MH acceptance.  The
proposal is a uniformly random *different* colour (symmetric, so plain MH
applies).  The sweep reuses the Pallas replica-tile strategy via
`repro.kernels.ops.potts_sweep` (`use_pallas=True`) with
`repro.kernels.ref.potts_sweep` as the bit-exact oracle and XLA fallback.

Order parameter: ``m = (q * max_colour_fraction - 1) / (q - 1)`` in [~0, 1] —
the standard Potts magnetization, reducing to |m| for q=2.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["PottsSystem", "potts_energy", "potts_magnetization"]


def potts_energy(states: jnp.ndarray, q: int, j: float) -> jnp.ndarray:
    """E = -J * sum over right+down bonds of delta(s, s_nbr); PBC, f32.

    Counts each bond once.  (On a 2-wide dim the two wrap bonds between the
    same site pair are both counted — consistent with the 4-neighbour dE the
    sweep uses.)
    """
    s = states.astype(jnp.int32)
    match = (s == jnp.roll(s, -1, axis=-1)).astype(jnp.float32) + (
        s == jnp.roll(s, -1, axis=-2)
    ).astype(jnp.float32)
    return -j * jnp.sum(match, axis=(-2, -1))


def potts_magnetization(states: jnp.ndarray, q: int) -> jnp.ndarray:
    """Potts order parameter ``(q * rho_max - 1)/(q - 1)`` per replica.

    ``rho_max`` is the occupation fraction of the most common colour; the
    parameter is ~0 in the disordered phase and -> 1 at saturation.
    """
    s = states.astype(jnp.int32)
    n = s.shape[-2] * s.shape[-1]
    counts = jnp.stack(
        [jnp.sum((s == c).astype(jnp.float32), axis=(-2, -1)) for c in range(q)],
        axis=-1,
    )
    rho_max = jnp.max(counts, axis=-1) / n
    return (q * rho_max - 1.0) / (q - 1.0)


@dataclasses.dataclass(frozen=True)
class PottsSystem:
    """One replica of the q-state Potts model; vmapped by the PT driver.

    Attributes:
      shape: lattice (H, W); both even (checkerboard 2-colourability, PBC).
      q: number of colours (>= 2).
      j: coupling constant (ferromagnetic for j > 0).
      use_pallas: route the sweep through the Pallas kernel
        (interpret=True on CPU) instead of the pure-XLA oracle.
      use_fused: run whole swap intervals through the interval-fused kernel
        (`repro.kernels.ops.potts_sweep_fused`) with counter-PRNG uniforms
        generated in-kernel.  The random stream differs from the per-sweep
        path (statistically gated, not bit-equal — DESIGN.md §6); with
        ``use_pallas=False`` the bit-exact fused pure-JAX reference runs.
      use_fused_round: temp-mode DEO/SEO only — fuse whole PT rounds
        (sweeps *plus* the exchange) into one launch via
        `repro.kernels.ops.potts_round_fused` (see
        `repro.core.ising.IsingSystem` for the stream contract).
      pack_bits: fused paths only — keep the lattice in dense int8 lanes
        in-kernel instead of widening to int32 (bitwise-identical; needs
        q ≤ 64; refused with ``use_pallas`` on a TPU, which cannot compile
        int8 vector arithmetic).
      accept_rule: "metropolis" or "glauber" (see repro.kernels.ref).
      r_blk: replicas per Pallas grid step; 4 is the documented VMEM-safe
        block at the paper's L=300 (`kernels.potts_sweep`).
    """

    shape: tuple
    q: int = 3
    j: float = 1.0
    use_pallas: bool = False
    use_fused: bool = False
    use_fused_round: bool = False
    pack_bits: bool = False
    accept_rule: str = "metropolis"
    r_blk: int = 4

    def __post_init__(self):
        h, w = self.shape
        if h % 2 != 0 or w % 2 != 0:
            # Same constraint as IsingSystem: with PBC an odd dim breaks
            # 2-colourability (wrap-around neighbours share parity).
            raise ValueError(
                f"checkerboard Potts needs even dims under PBC, got {self.shape}"
            )
        if self.q < 2:
            raise ValueError(f"Potts needs q >= 2, got q={self.q}")
        if self.use_fused_round and not self.use_fused:
            raise ValueError(
                "use_fused_round=True needs use_fused=True (the round "
                "kernel is the interval-fused kernel plus an in-kernel "
                "exchange)"
            )
        if self.pack_bits:
            from repro.kernels import ops as kops
            from repro.kernels.potts_sweep import check_pack_bits

            # refused at build time: a kernel that cannot compile would
            # otherwise degrade the run to the per-sweep path
            check_pack_bits(
                self.q,
                interpret=kops._interpret() if self.use_pallas else True,
            )

    # -- System protocol ---------------------------------------------------
    def init_state(self, key: jax.Array) -> jnp.ndarray:
        return jax.random.randint(key, self.shape, 0, self.q).astype(jnp.int8)

    def energy(self, states: jnp.ndarray) -> jnp.ndarray:
        return potts_energy(states, self.q, self.j)

    def magnetization(self, states: jnp.ndarray) -> jnp.ndarray:
        return potts_magnetization(states, self.q)

    def mcmc_step(self, key: jax.Array, states: jnp.ndarray, beta: jnp.ndarray):
        s, de, na = self._sweep(states[None], key[None], beta[None])
        return s[0], de[0], na[0]

    # -- batched fast path (used by the PT driver instead of vmap) ----------
    def batched_mcmc_step(self, keys, states, betas):
        """Natively replica-batched sweep: (R, H, W) in, (R, H, W) out."""
        return self._sweep(states, keys, betas)

    def _sweep(self, states, keys, betas):
        h, w = self.shape
        u = jax.vmap(
            lambda k: jax.random.uniform(k, (2, 2, h, w), jnp.float32)
        )(keys)
        from repro.kernels import ops as kops

        return kops.potts_sweep(
            states, u, betas, q=self.q, j=self.j, rule=self.accept_rule,
            r_blk=self.r_blk, use_pallas=self.use_pallas,
        )

    # -- fused whole-interval fast path (used when use_fused=True) -----------
    def batched_mcmc_interval(self, key, t, states, betas, *, n_sweeps,
                              replica_offset=0):
        """``n_sweeps`` replica-batched sweeps in one fused launch (see
        `repro.core.ising.IsingSystem.batched_mcmc_interval`)."""
        from repro.kernels import ops as kops

        return kops.potts_sweep_fused(
            states, key, t, betas, n_sweeps=n_sweeps, q=self.q,
            replica_offset=replica_offset, j=self.j,
            rule=self.accept_rule, r_blk=self.r_blk,
            pack_bits=self.pack_bits, use_pallas=self.use_pallas,
        )

    # -- whole-round fast path (used when use_fused_round=True) --------------
    def batched_mcmc_round(self, key, t, phase, states, rung, energy, betas,
                           *, n_sweeps, n_rounds=1, criterion="logistic",
                           pairing="deo"):
        """``n_rounds`` whole PT rounds fused (see
        `repro.core.ising.IsingSystem.batched_mcmc_round`)."""
        from repro.kernels import ops as kops

        return kops.potts_round_fused(
            states, key, t, phase, rung, energy, betas,
            n_sweeps=n_sweeps, q=self.q, n_rounds=n_rounds, j=self.j,
            rule=self.accept_rule, criterion=criterion, pairing=pairing,
            pack_bits=self.pack_bits, use_pallas=self.use_pallas,
        )
