"""Pure-jnp oracles for the Pallas kernels.

These are the *numerical contracts*: each kernel in this package must match
its oracle bit-for-bit in lattices and acceptance counts, and in the f32 ΔE
totals to rounding — a lattice sum's reduction order is the backend's own
(tests/test_kernels.py sweeps shapes/dtypes; `chip_smoke.py` checks the
same on the chip).
They are also used as the production XLA fallback paths (e.g. lattices too
large for VMEM-resident tiles, or CPU execution).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def accept_prob(de: jnp.ndarray, beta, rule: str) -> jnp.ndarray:
    """Per-site acceptance probability.

    * ``metropolis`` — ``min(1, e^{-beta*dE})`` (paper Eq. 1).  NOTE: at
      dE <= 0 this accepts deterministically; simultaneous (checkerboard)
      deterministic flips can create absorbing 2-cycles on tiny/stripe-
      symmetric lattices (observed on 2x2 — see tests/test_ising.py).
    * ``glauber`` — heat-bath ``1/(1 + e^{beta*dE})``: strictly in (0,1), so
      the simultaneous update stays aperiodic; same stationary law.
    """
    if rule == "metropolis":
        return jnp.exp(-beta * de)  # u in [0,1) < e^0 handles dE<=0
    if rule == "glauber":
        return jax.nn.sigmoid(-beta * de)
    raise ValueError(f"unknown acceptance rule {rule!r}")


def ising_sweep(
    spins: jnp.ndarray,
    u: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    j: float,
    b: float,
    rule: str = "metropolis",
):
    """One full checkerboard Metropolis sweep, batched over replicas.

    Args:
      spins: (R, L, L) int8 in {-1, +1}.
      u: (R, 2, L, L) float32 uniforms in [0, 1) — one lattice of randoms per
        colour half-sweep.  Randoms are *inputs* (not generated in-kernel) so
        the Pallas kernel and this oracle agree exactly (DESIGN.md §6).
      betas: (R,) float32 inverse temperatures.
      rule: per-site acceptance rule (see `accept_prob`).

    Returns:
      (new_spins (R,L,L) int8, delta_e (R,) f32, n_accepted (R,) i32).
    """
    L = spins.shape[-1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    parity = (ii + jj) % 2
    beta = betas.astype(jnp.float32)[:, None, None]

    s = spins.astype(jnp.float32)
    de_total = jnp.zeros(spins.shape[0], jnp.float32)
    n_acc = jnp.zeros(spins.shape[0], jnp.int32)
    for color in (0, 1):  # static unroll, exactly as the kernel does
        nbr = (
            jnp.roll(s, 1, axis=-2)
            + jnp.roll(s, -1, axis=-2)
            + jnp.roll(s, 1, axis=-1)
            + jnp.roll(s, -1, axis=-1)
        )
        de = 2.0 * s * (j * nbr - b)
        accept = (u[:, color] < accept_prob(de, beta, rule)) & (parity == color)
        s = jnp.where(accept, -s, s)
        de_total = de_total + jnp.sum(jnp.where(accept, de, 0.0), axis=(-2, -1))
        n_acc = n_acc + jnp.sum(accept.astype(jnp.int32), axis=(-2, -1))
    return s.astype(jnp.int8), de_total, n_acc


def potts_sweep(
    states: jnp.ndarray,
    u: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    q: int,
    j: float,
    rule: str = "metropolis",
):
    """One full checkerboard sweep of the q-state Potts model, replica-batched.

    The proposal at each site is a uniformly random *different* colour,
    ``s' = (s + d) mod q`` with ``d = 1 + floor(u_prop * (q-1))`` — symmetric,
    so plain MH acceptance applies.  Same two-colour scheme as the Ising
    sweep: sites of one parity share no bonds (PBC needs even dims), so the
    whole colour class updates simultaneously.

    Args:
      states: (R, H, W) int8 colours in {0..q-1}.
      u: (R, 2, 2, H, W) float32 uniforms in [0, 1) — axis 1 is the colour
        half-sweep, axis 2 is (proposal draw, acceptance draw).  Randoms are
        inputs so the Pallas kernel and this oracle agree exactly
        (DESIGN.md §6).
      betas: (R,) float32 inverse temperatures.
      q: number of colours (static).
      j: coupling; E = -j * sum_<xy> delta(s_x, s_y), each bond once.
      rule: per-site acceptance rule (see `accept_prob`).

    Returns:
      (new_states (R,H,W) int8, delta_e (R,) f32, n_accepted (R,) i32).
    """
    h, w = states.shape[-2], states.shape[-1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    parity = (ii + jj) % 2
    beta = betas.astype(jnp.float32)[:, None, None]

    s = states.astype(jnp.int32)
    de_total = jnp.zeros(states.shape[0], jnp.float32)
    n_acc = jnp.zeros(states.shape[0], jnp.int32)
    for color in (0, 1):  # static unroll, exactly as the kernel does
        d = 1 + jnp.floor(u[:, color, 0] * (q - 1)).astype(jnp.int32)
        trial = jax.lax.rem(s + d, q)
        de = jnp.zeros(s.shape, jnp.float32)
        for axis, shift in ((-2, 1), (-2, -1), (-1, 1), (-1, -1)):
            nbr = jnp.roll(s, shift, axis=axis)
            de = de + j * (
                (s == nbr).astype(jnp.float32) - (trial == nbr).astype(jnp.float32)
            )
        accept = (u[:, color, 1] < accept_prob(de, beta, rule)) & (parity == color)
        s = jnp.where(accept, trial, s)
        de_total = de_total + jnp.sum(jnp.where(accept, de, 0.0), axis=(-2, -1))
        n_acc = n_acc + jnp.sum(accept.astype(jnp.int32), axis=(-2, -1))
    return s.astype(jnp.int8), de_total, n_acc


def wkv6(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    initial_state: jnp.ndarray | None = None,
):
    """RWKV-6 ("Finch") recurrence, one batch*head slab at a time.

    Per head, with state ``S`` of shape (dk, dv)::

        o_t = r_t @ S_{t-1}  +  (r_t · (u ⊙ k_t)) v_t
        S_t = diag(w_t) S_{t-1} + k_t ⊗ v_t

    ``w_t`` is the *data-dependent* decay in (0, 1) — the defining RWKV-6
    feature [arXiv:2404.05892].

    Args:
      r, k, w: (BH, T, dk) float32 (w already exp(-exp(...))-activated).
      v: (BH, T, dv) float32.
      u: (BH, dk) float32 "bonus" for the current token.
      initial_state: optional (BH, dk, dv) f32 (decode); zeros otherwise.

    Returns (o (BH, T, dv) f32, final_state (BH, dk, dv) f32).
    """
    bh, t, dk = r.shape
    dv = v.shape[-1]
    s0 = (
        jnp.zeros((bh, dk, dv), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(s, inputs):
        rt, kt, vt, wt, ut = inputs  # (bh,dk),(bh,dk),(bh,dv),(bh,dk),(bh,dk)
        bonus = jnp.sum(rt * ut * kt, axis=-1, keepdims=True)  # (bh, 1)
        out = jnp.einsum("bk,bkv->bv", rt, s) + bonus * vt
        s = wt[:, :, None] * s + kt[:, :, None] * vt[:, None, :]
        return s, out

    xs = (
        r.transpose(1, 0, 2),
        k.transpose(1, 0, 2),
        v.transpose(1, 0, 2),
        w.transpose(1, 0, 2),
        jnp.broadcast_to(u[None], (t, bh, dk)),
    )
    s_final, o = jax.lax.scan(step, s0, xs)
    return o.transpose(1, 0, 2), s_final
