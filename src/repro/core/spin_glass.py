"""Edwards-Anderson ±J spin glasses.

    E(s) = - sum_<x,y> J_xy s_x s_y,     J_xy = ±J quenched (fixed per run)

The EA model is the canonical rugged-landscape PT workload (Earl & Deem;
Katzgraber's feedback-optimized PT was developed on it): frustration (loops
whose coupling product is negative) produces the many-valley landscape that
motivates replica exchange in the first place.  Two systems live here.

`EASpinGlass` (``ea_spin_glass``) is the 2-D model with the quenched
disorder carried in the state: each replica's state bundles its spins with
the coupling planes ``{"spins", "jr", "jd"}``.  Every replica of a run
holds the *same* disorder realization (drawn deterministically from
``disorder_seed``), as PT requires — replicas must sample one common target
at different temperatures — but the couplings ride inside the state pytree,
so `temp`-mode swaps, `state`-mode swaps (tree_map gather), checkpointing and
the ensemble axis all exercise the generic pytree path through
`engine.driver`.

`EAGlass` (``ea_glass``) is the rank-generic model (2-D or 3-D) over many
disorder samples, as spin-glass studies run it: the couplings are
*per-chain quenched data* (`init_quenched`), int8 ±1 forward-bond planes
held once per chain, and chain ``c`` holds the bonds of sample
``c // copies``, so the ``copies`` chains of a sample share them.  The
engine builds them at ``init`` and hands them to `EAGlass.mcmc_step`
un-batched over replicas; a replica's state is its int8 spins alone.

Both update by the simultaneous checkerboard (the lattice is bipartite;
PBC needs even dims), in pure XLA — bond disorder breaks the single-J
premise of the Ising Pallas kernel.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels.ref import accept_prob

__all__ = ["EASpinGlass", "ea_energy", "EAGlass"]


def ea_energy(state: dict, j_scale: float = 1.0) -> jnp.ndarray:
    """E = -sum(jr * s * s_right) - sum(jd * s * s_down); PBC, f32.

    ``jr[x, y]`` couples site (x, y) to its right neighbour (y+1 mod W);
    ``jd`` to its down neighbour (x+1 mod H).  Each bond counted once.
    """
    s = state["spins"].astype(jnp.float32)
    right = jnp.roll(s, -1, axis=-1)
    down = jnp.roll(s, -1, axis=-2)
    return -j_scale * (
        jnp.sum(state["jr"] * s * right, axis=(-2, -1))
        + jnp.sum(state["jd"] * s * down, axis=(-2, -1))
    )


@dataclasses.dataclass(frozen=True)
class EASpinGlass:
    """One replica of the 2-D ±J Edwards-Anderson model (System protocol).

    Attributes:
      shape: lattice (H, W), both even (checkerboard under PBC).
      j: coupling magnitude (bonds are ±j with equal probability).
      disorder_seed: seed of the quenched coupling draw — *every* replica
        gets the same realization (the PT extended ensemble shares one
        target), carried inside each replica's state pytree.
      accept_rule: "metropolis" or "glauber" (see repro.kernels.ref).
    """

    shape: tuple
    j: float = 1.0
    disorder_seed: int = 0
    accept_rule: str = "metropolis"

    def __post_init__(self):
        h, w = self.shape
        if h % 2 != 0 or w % 2 != 0:
            raise ValueError(
                f"checkerboard EA needs even dims under PBC, got {self.shape}"
            )

    def disorder(self) -> tuple:
        """The quenched ±j coupling planes (jr, jd) — deterministic."""
        kr, kd = jax.random.split(jax.random.key(self.disorder_seed))
        draw = lambda k: jnp.where(
            jax.random.uniform(k, self.shape) < 0.5, self.j, -self.j
        ).astype(jnp.float32)
        return draw(kr), draw(kd)

    # -- System protocol ---------------------------------------------------
    def init_state(self, key: jax.Array) -> dict:
        jr, jd = self.disorder()
        u = jax.random.uniform(key, self.shape)
        return {
            "spins": jnp.where(u < 0.5, 1, -1).astype(jnp.int8),
            "jr": jr,
            "jd": jd,
        }

    def energy(self, state: dict) -> jnp.ndarray:
        return ea_energy(state)

    def mcmc_step(self, key: jax.Array, state: dict, beta: jnp.ndarray):
        """One full checkerboard sweep (colour 0 then colour 1)."""
        h, w = self.shape
        s = state["spins"].astype(jnp.float32)
        jr, jd = state["jr"], state["jd"]
        ii = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
        parity = (ii + jj) % 2
        u = jax.random.uniform(key, (2, h, w), jnp.float32)

        de_total = jnp.float32(0.0)
        n_acc = jnp.int32(0)
        for color in (0, 1):
            # Local field of each site through its 4 (disordered) bonds.
            field = (
                jr * jnp.roll(s, -1, axis=-1)
                + jnp.roll(jr, 1, axis=-1) * jnp.roll(s, 1, axis=-1)
                + jd * jnp.roll(s, -1, axis=-2)
                + jnp.roll(jd, 1, axis=-2) * jnp.roll(s, 1, axis=-2)
            )
            de = 2.0 * s * field  # flip s -> -s changes E by +2 s h
            accept = (u[color] < accept_prob(de, beta, self.accept_rule)) & (
                parity == color
            )
            s = jnp.where(accept, -s, s)
            de_total = de_total + jnp.sum(jnp.where(accept, de, 0.0))
            n_acc = n_acc + jnp.sum(accept.astype(jnp.int32))
        new = dict(state)
        new["spins"] = s.astype(jnp.int8)
        return new, de_total, n_acc


@dataclasses.dataclass(frozen=True)
class EAGlass:
    """One replica of the ±J Edwards-Anderson model over disorder samples.

    Attributes:
      shape: lattice, 2-D or 3-D, every dimension even (checkerboard under
        PBC).
      j: coupling magnitude; bonds are ±j with equal probability.
      disorder_seed: seed of the samples' bond draws.
      copies: chains per disorder sample (a sample's real replicas): chain
        ``c`` holds the bonds of sample ``c // copies``.
      accept_rule: "glauber" (heat-bath) or "metropolis".

    Layout.  A lattice is held lane-dense as ``(shape[0],
    prod(shape[1:]))``, the row-major view of the plain lattice (`plain`
    gives that back): at L=32 in 3-D the plain minor dimension would fill
    32 of a TPU vreg's 128 lanes and pad every array 4x in HBM.  Dimension
    0 rolls the rows, dimension 1 the whole lane axis by its stride, and
    the last dimension of a 3-D lattice rolls within lane segments of
    ``shape[-1]`` (`_shift`).

    Random stream.  A replica's uniforms of colour ``k`` are
    ``uniform(fold_in(key, k), shape)``, drawn one colour at a time (in the
    lane-dense view, which holds the same numbers in the same order).

    Acceptance.  A flip changes E by ``2 j s h`` with ``h = sum J s_nbr``
    an even integer in [-2·rank, 2·rank], so each replica's flip
    probabilities are a table of those 2·rank + 1 values at its β, and the
    rest of the update is integer work.
    """

    shape: tuple
    j: float = 1.0
    disorder_seed: int = 0
    copies: int = 1
    accept_rule: str = "glauber"

    def __post_init__(self):
        shape = tuple(int(n) for n in self.shape)
        if len(shape) not in (2, 3) or any(n < 2 or n % 2 for n in shape):
            raise ValueError(
                "ea_glass needs a 2-D or 3-D lattice with every dimension "
                f"even (checkerboard under PBC), got {self.shape}"
            )
        if self.copies < 1:
            raise ValueError(f"copies must be >= 1, got {self.copies}")
        if self.accept_rule not in ("metropolis", "glauber"):
            raise ValueError(f"unknown acceptance rule {self.accept_rule!r}")
        object.__setattr__(self, "shape", shape)

    @property
    def rank(self) -> int:
        return len(self.shape)

    @property
    def layout(self) -> tuple:
        """A lattice's lane-dense shape ``(shape[0], prod(shape[1:]))``."""
        return (self.shape[0], math.prod(self.shape[1:]))

    def plain(self, x: jax.Array) -> jax.Array:
        """Lane-dense ``(..., rows, lanes)`` -> the plain ``(..., *shape)``."""
        return x.reshape(*x.shape[:-2], *self.shape)

    def _shift(self, a: jax.Array, d: int, step: int) -> jax.Array:
        """``a`` at ``x + step * e_d`` (periodic), in the lane-dense view."""
        if d == 0:
            return jnp.roll(a, -step, axis=-2)
        n, stride = self.shape[d], math.prod(self.shape[d + 1:])
        near = jnp.roll(a, -step * stride, axis=-1)
        if n * stride == a.shape[-1]:
            return near  # the roll wraps the lane axis where the lattice does
        lane = jax.lax.broadcasted_iota(jnp.int32, a.shape[-2:], 1)
        edge = (lane // stride) % n == (n - 1 if step > 0 else 0)
        return jnp.where(edge, jnp.roll(a, step * (n - 1) * stride, axis=-1), near)

    def _parity(self) -> jax.Array:
        rows = jax.lax.broadcasted_iota(jnp.int32, self.layout, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, self.layout, 1)
        total = rows
        for d in range(1, self.rank):
            total = total + (lane // math.prod(self.shape[d + 1:])) % self.shape[d]
        return total % 2

    # -- per-chain quenched data -------------------------------------------
    def init_quenched(self, chain) -> jax.Array:
        """Chain ``chain``'s bonds: ``(rank, *layout)`` int8 ±1, the forward
        bond of every site along each dimension, those of sample
        ``chain // copies`` drawn from ``fold_in(key(disorder_seed),
        sample)``."""
        sample = jnp.asarray(chain, jnp.uint32) // jnp.uint32(self.copies)
        key = jax.random.fold_in(jax.random.key(self.disorder_seed), sample)
        u = jax.random.uniform(key, (self.rank, *self.layout))
        return jnp.where(u < 0.5, 1, -1).astype(jnp.int8)

    # -- System protocol ---------------------------------------------------
    def init_state(self, key: jax.Array) -> jax.Array:
        u = jax.random.uniform(key, self.layout)
        return jnp.where(u < 0.5, 1, -1).astype(jnp.int8)

    def energy(self, spins: jax.Array, bonds: jax.Array) -> jax.Array:
        """E = -j sum_x sum_d J_{x,d} s_x s_{x+e_d}, summed in int32."""
        total = jnp.int32(0)
        for d in range(self.rank):
            total = total + jnp.sum(
                bonds[d] * spins * self._shift(spins, d, 1), dtype=jnp.int32)
        return jnp.float32(-self.j) * total.astype(jnp.float32)

    def mcmc_step(self, key: jax.Array, spins: jax.Array, beta: jax.Array,
                  bonds: jax.Array):
        """One checkerboard sweep (colour 0 then colour 1) under ``bonds``."""
        back = [self._shift(bonds[d], d, -1) for d in range(self.rank)]
        parity = self._parity()
        n = 2 * self.rank
        de_values = jnp.asarray(
            [2.0 * self.j * v for v in range(-n, n + 1, 2)], jnp.float32)
        table = accept_prob(de_values, beta, self.accept_rule)
        s = spins
        de_sum = n_acc = jnp.int32(0)
        for colour in (0, 1):
            with jax.named_scope("pt.rng"):
                u = jax.random.uniform(jax.random.fold_in(key, colour), self.layout)
            with jax.named_scope("pt.field"):
                h = bonds[0] * self._shift(s, 0, 1) + back[0] * self._shift(s, 0, -1)
                for d in range(1, self.rank):
                    h = h + bonds[d] * self._shift(s, d, 1) + back[d] * self._shift(s, d, -1)
            sh = s * h  # int8 in [-n, n], even
            k = (sh + n) // 2
            p = table[0]
            for i in range(1, n + 1):
                p = jnp.where(k == i, table[i], p)
            accept = (u < p) & (parity == colour)
            s = jnp.where(accept, -s, s)
            de_sum = de_sum + jnp.sum(jnp.where(accept, sh, 0), dtype=jnp.int32)
            n_acc = n_acc + jnp.sum(accept, dtype=jnp.int32)
        de = jnp.float32(2.0 * self.j) * de_sum.astype(jnp.float32)
        return s, de, n_acc
