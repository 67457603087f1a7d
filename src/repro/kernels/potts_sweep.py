"""Pallas TPU kernel: checkerboard sweep for the q-state Potts model.

Same tile strategy as `repro.kernels.ising_sweep` (DESIGN.md §2/§6), on the
shared launch plumbing of `repro.kernels.lattice`: one grid step moves a
block of ``r_blk`` replicas with their full (H, W) lattices into VMEM and
sweeps them one at a time, both colour half-sweeps run back-to-back
in-kernel (one HBM round-trip of the colour block per sweep), colours are
int8 in HBM and widened to int32 only inside VMEM.  The proposal randoms
ride alongside the acceptance randoms as kernel inputs, so the CPU
`interpret=True` path matches `ref.potts_sweep` exactly in colours and
acceptance counts (ΔE to f32 rounding).

Like the Ising kernel, the variants share the tile strategy (DESIGN.md §6):
``potts_sweep_pallas`` (one sweep per launch, uniforms as an input stream —
exact vs `ref.potts_sweep`), ``potts_sweep_fused_pallas`` (one swap
*interval* per launch: all ``n_sweeps`` sweeps with the colour block
VMEM-resident, the four uniform planes per sweep generated in-kernel by the
counter PRNG `repro.kernels.prng` at ``(key, sweep, replica, 2*colour +
(proposal|accept))``, ΔE/acceptance accumulated in-kernel), and
``potts_round_fused_pallas`` (one launch = whole PT round(s): sweeps plus
the temp-mode DEO/SEO exchange via `repro.kernels.exchange`, swap uniforms
from the counter PRNG's swap stream).  Modeled HBM traffic drops from
34 B/cell/sweep (int8 in/out + 16 B of uniforms written externally + 16 B
read back) to 2 B/cell/*launch* plus O(R) scalars
(`hbm_bytes_per_cell_sweep`).

The fused variants take ``pack_bits``: a Potts colour does not compress to
one bit, so "packing" here keeps the lattice in its dense **int8 lanes**
through the whole update (proposal, trial, equality comparisons) instead of
widening to int32 — 4× denser working state, valid for ``q ≤ 64`` (the
int8 intermediate ``s + d`` peaks at ``2q − 2``).  Comparisons and selects
on int8 produce the same booleans, so the trajectory is bitwise identical
(pinned by tests).  Mosaic on v5e has no int8 vector arithmetic, so this
mode runs in interpret mode only; a compiled request is refused
(`check_pack_bits`).

VMEM working set per grid step ≈ r_blk · H · W · (2 int8 in/out + 4·4 u-f32 +
2·4 i32 working copies + 4 de-f32) = 30·r_blk·H·W bytes — roughly 2.3× the
Ising kernel's (the extra uniform plane pays for the colour proposal), still
inside a v5e core's 16 MB for the paper's L=300 at r_blk=4 (~10.8 MB;
`vmem_working_set_bytes`).  The fused variant swaps the 16 B/cell uniforms
block for one in-flight plane of PRNG draws (8 B bits+f32), totalling
22 B/cell (`vmem_working_set_bytes_fused`) — r_blk=4 at L=300 stays well
inside budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lattice import (
    Kind,
    accept_prob,
    fused_call,
    roll1,
    round_call,
    site_sum,
    sweep_call,
)


def _potts_sweep_body(s, beta, parity, draw, *, q, j, rule, packed):
    """One checkerboard Potts sweep on a colour group, shared by all kernels.

    ``draw(plane)`` gives the uniforms of plane ``2*colour + (0 proposal |
    1 accept)``; ``beta`` is a (g, 1, 1) column.  ``packed`` keeps the
    update in int8 lanes (multispin-style dense storage, q ≤ 64) instead of
    the int32 working copy; equality comparisons and the accept select
    produce identical booleans either way, so the two modes are
    bitwise-identical — only the VMEM working set differs.
    Returns ``(s', delta_e (g, 1, 1), n_accepted (g, 1, 1))``.
    """
    ds = jnp.zeros(beta.shape, jnp.float32)
    na = jnp.zeros(beta.shape, jnp.int32)
    for color in (0, 1):  # static unroll: two half-sweeps, one HBM round-trip
        u_prop = draw(2 * color + 0)
        u_acc = draw(2 * color + 1)
        if packed:
            # int8 lanes throughout: s + d peaks at 2q-2 <= 126 for q <= 64
            d = 1 + jnp.floor(u_prop * (q - 1)).astype(jnp.int8)
            trial = jax.lax.rem((s + d).astype(jnp.int8), jnp.int8(q))
        else:
            d = 1 + jnp.floor(u_prop * (q - 1)).astype(jnp.int32)
            trial = jax.lax.rem(s + d, q)
        de = jnp.zeros(s.shape, jnp.float32)
        for axis, shift in ((1, 1), (1, -1), (2, 1), (2, -1)):
            nbr = roll1(s, shift, axis)
            de = de + j * (
                (s == nbr).astype(jnp.float32)
                - (trial == nbr).astype(jnp.float32)
            )
        accept = (u_acc < accept_prob(de, beta, rule)) & (parity == color)
        s = jnp.where(accept, trial, s)
        ds = ds + site_sum(jnp.where(accept, de, 0.0))
        na = na + site_sum(accept.astype(jnp.int32))
    return s, ds, na


def check_pack_bits(q: int, *, interpret: bool) -> None:
    """Refuse a ``pack_bits`` request the kernels cannot honour.

    The int8 lanes hold ``s + d`` only for q ≤ 64, and Mosaic has no int8
    vector arithmetic on a v5e, so the mode runs interpreted (CPU) only.
    """
    if q > 64:
        raise ValueError(f"pack_bits needs q <= 64 (int8 lanes), got q={q}")
    if not interpret:
        raise ValueError(
            "Potts pack_bits computes in int8 vector lanes, which Mosaic "
            "cannot compile for a TPU; it runs interpreted on the CPU only"
        )


def _kind(q, j, rule, pack_bits=False, interpret=True) -> Kind:
    if pack_bits:
        check_pack_bits(q, interpret=interpret)
    dtype = jnp.int8 if pack_bits else jnp.int32
    return Kind(
        load=lambda s8: s8.astype(dtype),
        step=functools.partial(
            _potts_sweep_body, q=q, j=j, rule=rule, packed=pack_bits
        ),
        store=lambda s, _n: s.astype(jnp.int8),
    )


def potts_sweep_pallas(
    states: jnp.ndarray,
    u: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    q: int,
    j: float = 1.0,
    rule: str = "metropolis",
    r_blk: int = 8,
    interpret: bool = True,
):
    """pallas_call wrapper. See `repro.kernels.ref.potts_sweep` for semantics.

    Args:
      states: (R, H, W) int8 in {0..q-1}; R must be a multiple of ``r_blk``
        (ops.py pads).
      u: (R, 2, 2, H, W) f32 uniforms (colour x (proposal, accept)).
      betas: (R,) f32.
      q: number of colours (static).
      r_blk: replicas per grid step (the Fig.-6 "block size" analogue).
      interpret: True on CPU; False on real TPU.
    """
    r, h, w = states.shape
    planes = u.reshape(r, 4, h, w)  # plane 2*colour + (proposal | accept)
    return sweep_call(
        _kind(q, j, rule), states, planes, betas, r_blk=r_blk,
        interpret=interpret,
    )


def potts_sweep_fused_pallas(
    states: jnp.ndarray,
    key_words: jnp.ndarray,
    t0: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    n_sweeps: int,
    q: int,
    replica_offset: jnp.ndarray | None = None,
    j: float = 1.0,
    rule: str = "metropolis",
    r_blk: int = 4,
    pack_bits: bool = False,
    interpret: bool = True,
):
    """Interval-fused pallas_call wrapper (see module docstring).

    Args:
      states: (R, H, W) int8 in {0..q-1}; R a multiple of ``r_blk``
        (ops.py pads).
      key_words: (2,) uint32 run-key words (`prng.key_words`).
      t0: (1,) uint32 global sweep counter at interval entry.
      betas: (R,) f32;  n_sweeps / q: static.
      replica_offset: (1,) uint32 global index of local slot 0 (sharded
        replica axis); default 0 keeps single-device streams unchanged.
      pack_bits: dense int8-lane storage in VMEM (bitwise-identical; q ≤ 64).

    Returns ``(states', delta_e, n_accepted)`` summed over the interval.
    """
    kind = _kind(q, j, rule, pack_bits, interpret)
    if replica_offset is None:
        replica_offset = jnp.zeros((1,), jnp.uint32)
    return fused_call(
        kind, states, key_words, t0, betas, n_sweeps=n_sweeps,
        replica_offset=replica_offset, r_blk=r_blk, interpret=interpret,
    )


def potts_round_fused_pallas(
    states: jnp.ndarray,
    key_words: jnp.ndarray,
    t0: jnp.ndarray,
    phase0: jnp.ndarray,
    rung: jnp.ndarray,
    energy: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    n_sweeps: int,
    q: int,
    n_rounds: int = 1,
    j: float = 1.0,
    rule: str = "metropolis",
    criterion: str = "logistic",
    pairing: str = "deo",
    pack_bits: bool = False,
    interpret: bool = True,
):
    """Whole-PT-round pallas_call wrapper (Potts).

    Same contract as `ising_sweep.ising_round_fused_pallas`: whole ladder,
    single grid step, returns ``(states', rung', energy', n_accepted,
    accept, prob, attempt)`` with diagnostics shaped (n_rounds, R)
    (accept/attempt as int32 0/1).
    """
    kind = _kind(q, j, rule, pack_bits, interpret)
    return round_call(
        kind, states, key_words, t0, phase0, rung, energy, betas,
        n_sweeps=n_sweeps, n_rounds=n_rounds, criterion=criterion,
        pairing=pairing, interpret=interpret,
    )


def vmem_working_set_bytes(r_blk: int, height: int, width: int) -> int:
    """Static VMEM budget model (bytes per grid step; see module docstring)."""
    cells = r_blk * height * width
    states_in = cells  # int8
    uniforms = cells * 4 * 4  # (2 colours) x (prop, acc) f32
    widened = cells * 4  # i32 working copy
    trial = cells * 4  # i32 proposal lattice
    de = cells * 4  # f32 per-site energy delta
    out = cells
    return states_in + uniforms + widened + trial + de + out


def vmem_working_set_bytes_fused(r_blk: int, height: int, width: int) -> int:
    """VMEM budget of the interval-fused Potts kernel (bytes per grid step).

    The 16 B/cell uniforms input block is replaced by one in-flight plane of
    counter-PRNG draws (4 B uint32 bits + 4 B f32) plus O(r_blk) key state —
    22 B/cell total vs the per-sweep kernel's 30.
    """
    cells = r_blk * height * width
    states_in = cells  # int8
    bits = cells * 4  # uint32 PRNG draw, active plane
    uniforms = cells * 4  # f32 uniforms, active plane
    widened = cells * 4  # i32 working copy
    trial = cells * 4  # i32 proposal lattice
    de = cells * 4  # f32 per-site energy delta
    out = cells
    rng_state = 4 * 4 * r_blk  # stream/sweep key words + replica counters
    return states_in + bits + uniforms + widened + trial + de + out + rng_state


def vmem_working_set_bytes_packed(r_blk: int, height: int, width: int) -> int:
    """VMEM budget of the fused Potts kernel with int8-lane packing.

    The i32 working copy and i32 trial lattice (4 B/cell each) stay int8
    (1 B each): 22 → 16 B/cell.
    """
    cells = r_blk * height * width
    states_in = cells  # int8
    bits = cells * 4  # uint32 PRNG draw, active plane
    uniforms = cells * 4  # f32 uniforms, active plane
    working = cells  # int8 lanes (replaces i32 working copy)
    trial = cells  # int8 proposal lattice (replaces i32)
    de = cells * 4  # f32 per-site energy delta
    out = cells
    rng_state = 4 * 4 * r_blk
    return states_in + bits + uniforms + working + trial + de + out + rng_state


def hbm_bytes_per_cell_sweep(
    *, fused: bool, sweeps_per_interval: int = 1, rounds_per_launch: int = 1
) -> float:
    """Modeled HBM bytes per cell per sweep (O(R) scalars excluded).

    Per-sweep path: int8 in+out (2 B) + 16 B/cell of uniforms written by the
    external generator + 16 B read back = 34 B/cell/sweep.  Fused: the
    colour block crosses HBM once each way per launch (2 B/cell amortized
    over ``sweeps_per_interval × rounds_per_launch``); randoms never exist
    in HBM.

    Delegates to `repro.hlo.traffic.hbm_bytes_per_cell_sweep` — the shared
    model the roofline report and traffic assertions also consume.
    """
    from repro.hlo.traffic import hbm_bytes_per_cell_sweep as model

    return model(
        fused=fused, sweeps_per_interval=sweeps_per_interval,
        rounds_per_launch=rounds_per_launch,
        state_bytes=2.0, uniform_plane_bytes=16.0,
    )
