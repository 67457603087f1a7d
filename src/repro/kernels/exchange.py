"""Kernel-shared replica-exchange core for the whole-round fused kernels.

The whole-PT-round kernels (`ising_sweep.ising_round_fused_pallas`,
`potts_sweep.potts_round_fused_pallas`) fold the DEO/SEO swap decision and
the slot↔rung permutation into the interval launch: the ladder's O(R) energy
row is already accumulated in VMEM, so the exchange costs O(R²) elementwise
ops instead of a kernel exit + host-side `jax.random` draw per swap.

One function — `exchange_step` — is the single source of truth for that
decision.  The *same jnp ops* run in three places:

* inside the Pallas round-kernel bodies (Mosaic or ``interpret=True``);
* in the pure-JAX ``use_pallas=False`` reference path (`ops.*_round_fused`);
* in the sharded driver (`engine.driver.make_sharded_interval_step`), where
  each device recomputes the full-ladder decision redundantly from the
  all-gathered O(R) energy/rung rows (PR 6 contract) — the replica axis
  cannot be sharded *through* an exchange, so the multi-device analogue of
  the round kernel is per-shard fused sweeps + this function on gathered
  rows, bit-equal to the single-device launch.

That sharing is what makes interpret-mode bit-equality against the
`repro.exchange` DEO/SEO strategy + `core.swap.accept_pairs` oracle (fed the
same counter-stream uniforms) hold by construction, and it is why everything
here is written Mosaic-friendly: (R, 1, 1) columns and (1, 1, R) rows
from `broadcasted_iota` instead of 1-D ``arange``, one-hot
broadcast-compare-sum instead of gather/argsort (an arbitrary slot→rung
permutation has no static gather pattern Mosaic can lower; at O(R²) on R
scalars the one-hot form is noise next to the O(R·L²) sweeps).

Scope: temp-mode DEO/SEO only.  State-mode swaps move O(R·L²) lattice bytes
(exactly what fusion exists to avoid), `windowed` builds its random matching
with a host-side sequential loop, and VMPT needs pre-swap virtual-outcome
records the kernel does not emit — all three keep the PR 4 strategy path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import swap as swap_lib
from repro.kernels import prng

__all__ = [
    "to_row",
    "pair_partners",
    "onehot_gather",
    "rung_energies",
    "decide",
    "exchange_columns",
    "exchange_step",
]

PAIRINGS = ("deo", "seo")


# Kernel bodies keep every per-replica row as an (n, 1, 1) *column*: it
# broadcasts against the (n, L, L) lattice block as it stands, where a 1-D
# (n,) row would need a shape cast Mosaic cannot lower.  A one-hot gather
# compares a column against a (1, 1, n) *row* of the same values; both live
# in the three minor-most dims Mosaic tiles, so nothing is ever reshaped.


def _col(n: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1, 1), 0)


def _row(n: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, (1, 1, n), 2)


def to_row(col: jnp.ndarray) -> jnp.ndarray:
    """(n, 1, 1) column -> (1, 1, n) row of the same values (one-hot sum)."""
    n = col.shape[0]
    eq = _col(n) == _row(n)
    return jnp.sum(jnp.where(eq, col, jnp.zeros((), col.dtype)), axis=0,
                   keepdims=True)


def pair_partners(n: int, phase) -> jnp.ndarray:
    """(n, 1, 1) mirror of `core.swap.pair_partners` (same values)."""
    idx = _col(n)
    ph = jnp.asarray(phase, jnp.int32) % 2
    even = idx ^ 1
    odd = jnp.where(idx == 0, 0, ((idx - 1) ^ 1) + 1)
    partner = jnp.where(ph == 0, even, odd)
    return jnp.where(partner >= n, idx, partner)


def onehot_gather(values: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``values[idx]`` on (n, 1, 1) columns as a one-hot compare-sum.

    Exactly one term of each sum is nonzero, so the result is bitwise the
    gathered value (a ``-0.0`` entry surfaces as ``+0.0`` — value-equal,
    and impossible for the betas/energies this module gathers).
    """
    return gather_row(to_row(values), idx)


def gather_row(row: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """`onehot_gather` from a (1, 1, n) `to_row` row: O(n) per index, so a
    caller gathering from the same values many times builds the row once."""
    n = row.shape[2]
    zero = jnp.zeros((), row.dtype)
    return jnp.sum(jnp.where(idx == _row(n), row, zero), axis=2,
                   keepdims=True)


def rung_energies(rung: jnp.ndarray, energy: jnp.ndarray) -> jnp.ndarray:
    """(n, 1, 1) energy column in *rung* order from per-slot energies.

    The inversion-free form of ``energy[argsort(rung)]``: ``e_rung[r] =
    Σ_i energy[i]·[rung[i] == r]`` — argsort does not lower in a kernel
    body, the one-hot sum does.
    """
    n = rung.shape[0]
    eq = _col(n) == to_row(rung)
    return jnp.sum(jnp.where(eq, to_row(energy), 0.0), axis=2, keepdims=True)


def decide(partner, betas, e_rung, u, criterion):
    """`core.swap.accept_pairs` with externally supplied uniforms.

    Same decision structure (one uniform per rung, decided at the lower
    member, broadcast to both) and the shared `swap_probability`, so the
    outputs are bit-equal to ``accept_pairs(..., uniforms=u)`` — the oracle
    the round kernels are pinned against.  All arguments and results are
    (n, 1, 1) columns.  Returns ``(perm, accept_at_lower, prob_at_lower,
    attempt_at_lower)`` in `accept_pairs`' conventions.
    """
    idx = _col(partner.shape[0])
    lower = jnp.minimum(idx, partner)
    is_lower = (partner != idx) & (idx == lower)
    p = swap_lib.swap_probability(
        betas, onehot_gather(betas, partner),
        e_rung, onehot_gather(e_rung, partner), criterion=criterion,
    )
    accept_at_lower = (u < p) & is_lower
    pair_accept = (
        onehot_gather(accept_at_lower.astype(jnp.int32), lower) > 0
    ) & (partner != idx)
    perm = jnp.where(pair_accept, partner, idx)
    prob_at_lower = jnp.where(is_lower, p, 0.0)
    return perm, accept_at_lower, prob_at_lower, is_lower


def exchange_columns(rung, energy, betas, phase, key_words, *, pairing,
                     criterion):
    """`exchange_step` on (n, 1, 1) columns — the form a kernel body calls.

    ``key_words`` may be a (2,) array or a pair of scalars read from SMEM.
    """
    if pairing not in PAIRINGS:
        raise ValueError(
            f"in-kernel exchange supports pairings {PAIRINGS}, got {pairing!r}"
        )
    n = rung.shape[0]
    e_rung = rung_energies(rung, energy)
    u = prng.swap_uniforms(key_words, phase, n, shape=(n, 1, 1))
    if pairing == "deo":
        partner = pair_partners(n, phase)
    else:
        partner = pair_partners(n, prng.seo_coin(key_words, phase))
    perm, accept, prob, attempt = decide(partner, betas, e_rung, u, criterion)
    # temp mode: slot i holding rung r now holds perm[r]; states stay put.
    new_rung = onehot_gather(perm, rung)
    return new_rung, accept, prob, attempt, e_rung


def exchange_step(
    rung: jnp.ndarray,
    energy: jnp.ndarray,
    betas: jnp.ndarray,
    phase,
    key_words: jnp.ndarray,
    *,
    pairing: str,
    criterion: str,
):
    """One temp-mode exchange from the counter stream (kernel/driver shared).

    Args:
      rung: (R,) int32 slot→rung map.
      energy: (R,) f32 per-*slot* energies.
      betas: (R,) f32 inverse temperatures in *rung* order (cold→hot).
      phase: traced int — the global swap-iteration counter (keys the draw;
        `prng.swap_uniforms`).
      key_words: (2,) uint32 run-key words (`prng.key_words`).
      pairing: "deo" (alternating even/odd by phase parity) or "seo"
        (even/odd drawn from the counter stream's phase coin).
      criterion: "logistic" | "metropolis".

    Returns ``(new_rung, accept, prob, attempt, e_rung)``: the post-swap
    slot→rung map plus `accept_pairs`-convention lower-rung diagnostics and
    the pre-swap rung-ordered energy row, all (R,).
    """
    cols = [jnp.reshape(x, (-1, 1, 1)) for x in (rung, energy, betas)]
    out = exchange_columns(
        *cols, phase, key_words, pairing=pairing, criterion=criterion
    )
    return tuple(x.reshape(-1) for x in out)
