"""Public jit'd wrappers around the Pallas kernels.

Handles padding to kernel-friendly shapes, dispatch between the Pallas path
and the pure-jnp oracle (`ref.py`), and the platform: kernels are compiled
by Mosaic on a TPU and run in interpret mode on the CPU; any other backend
is refused rather than silently interpreted.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import exchange as _kx
from repro.kernels import ising_sweep as _ising
from repro.kernels import potts_sweep as _potts
from repro.kernels import prng as _prng
from repro.kernels import ref as _ref
from repro.kernels import wkv6 as _wkv6


def _interpret() -> bool:
    """Interpret on the CPU, compile on a TPU, refuse anything else."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default backend is {backend!r}"
    )


def _pad_replicas(arrays, betas, r_blk: int):
    """Pad the replica axis of every array to a multiple of ``r_blk``.

    Pad rows *tile* the real replicas (``row i -> row i % R``) so any pad
    count — including ``pad > R``, e.g. R=3 at r_blk=8 — yields consistent
    shapes (``spins[:pad]`` silently under-padded there, leaving betas one
    length and spins another).  Padded rows are *copies of real lattices*
    running at beta=0 (infinite temperature) and are dropped by the caller;
    the grid shape stays static and real rows are untouched.
    """
    r = betas.shape[0]
    pad = (-r) % r_blk
    if not pad:
        return arrays, betas, r
    idx = jnp.arange(pad) % r
    arrays = [jnp.concatenate([a, a[idx]], axis=0) for a in arrays]
    betas = jnp.concatenate([betas, jnp.zeros((pad,), betas.dtype)], axis=0)
    return arrays, betas, r


@partial(jax.jit, static_argnames=("j", "b", "rule", "r_blk", "use_pallas"))
def ising_sweep(
    spins: jnp.ndarray,
    u: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    j: float = 1.0,
    b: float = 0.0,
    rule: str = "metropolis",
    r_blk: int = 8,
    use_pallas: bool = True,
):
    """Checkerboard sweep; see `ref.ising_sweep` for the contract.

    Pads the replica axis to a multiple of ``r_blk`` (pad rows tile the real
    lattices at beta=0 and are dropped — grid shape stays static).
    """
    if not use_pallas:
        return _ref.ising_sweep(spins, u, betas, j=j, b=b, rule=rule)
    (spins, u), betas, r = _pad_replicas([spins, u], betas, r_blk)
    out, de, nacc = _ising.ising_sweep_pallas(
        spins, u, betas, j=j, b=b, rule=rule, r_blk=r_blk,
        interpret=_interpret(),
    )
    return out[:r], de[:r], nacc[:r]


@partial(jax.jit, static_argnames=("q", "j", "rule", "r_blk", "use_pallas"))
def potts_sweep(
    states: jnp.ndarray,
    u: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    q: int,
    j: float = 1.0,
    rule: str = "metropolis",
    r_blk: int = 4,
    use_pallas: bool = True,
):
    """Checkerboard Potts sweep; see `ref.potts_sweep` for the contract.

    Pads the replica axis to a multiple of ``r_blk`` exactly like
    `ising_sweep` (pad rows tile the real lattices at beta=0 and are
    dropped — grid shape stays static).  The default ``r_blk=4`` is the
    documented v5e-VMEM-safe block for the paper's L=300 lattice (the Potts
    working set is ~2.3x Ising's per cell; `potts_sweep.vmem_working_set_bytes`).
    """
    if not use_pallas:
        return _ref.potts_sweep(states, u, betas, q=q, j=j, rule=rule)
    (states, u), betas, r = _pad_replicas([states, u], betas, r_blk)
    out, de, nacc = _potts.potts_sweep_pallas(
        states, u, betas, q=q, j=j, rule=rule,
        r_blk=r_blk, interpret=_interpret(),
    )
    return out[:r], de[:r], nacc[:r]


def _fused_prelude(key, t):
    """Normalize the fused-kernel PRNG inputs: key words + (1,) u32 counter."""
    words = _prng.key_words(key)
    t0 = jnp.asarray(t).astype(jnp.uint32).reshape(1)
    return words, t0


@partial(
    jax.jit,
    static_argnames=(
        "n_sweeps", "j", "b", "rule", "r_blk", "pack_bits", "use_pallas"
    ),
)
def ising_sweep_fused(
    spins: jnp.ndarray,
    key: jnp.ndarray,
    t: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    n_sweeps: int,
    replica_offset=0,
    j: float = 1.0,
    b: float = 0.0,
    rule: str = "metropolis",
    r_blk: int = 8,
    pack_bits: bool = False,
    use_pallas: bool = True,
):
    """Interval-fused checkerboard sweeps: ``n_sweeps`` sweeps, one launch.

    ``key`` is a typed JAX PRNG key (or raw uint32 key data) and ``t`` the
    global sweep counter at interval entry; uniforms come from the counter
    PRNG (`repro.kernels.prng`) so the ``use_pallas=False`` pure-JAX path —
    ``n_sweeps`` applications of `ref.ising_sweep` fed
    `prng.ising_sweep_uniforms` — matches the kernel exactly in spins and
    acceptance counts, and in ΔE to f32 rounding.  Replica padding follows
    `ising_sweep` (pad rows tile the real lattices at beta=0, dropped on
    return); real replicas keep counter indices ``offset..offset+R-1``
    so the stream is padding-invariant.  ``replica_offset`` (traced uint32
    scalar, default 0) is the global index of local replica 0 when the
    replica axis is sharded across devices: a device holding slots
    ``[off, off+R_local)`` reproduces exactly the single-device streams.
    ``pack_bits`` selects bit-plane multispin storage inside the kernel
    (`ising_sweep.vmem_working_set_bytes_packed`); the trajectory is
    bitwise-identical, so the reference path is packing-oblivious.
    """
    words, t0 = _fused_prelude(key, t)
    off = jnp.asarray(replica_offset).astype(jnp.uint32).reshape(-1)[:1]
    r, length = spins.shape[0], spins.shape[-1]
    if not use_pallas:
        rep = off[0] + jnp.arange(r, dtype=jnp.uint32)

        def sweep(i, carry):
            s, de, na = carry
            u = _prng.ising_sweep_uniforms(
                words, t0[0] + jnp.uint32(i), rep, length
            )
            s, d, n = _ref.ising_sweep(s, u, betas, j=j, b=b, rule=rule)
            return s, de + d, na + n

        return jax.lax.fori_loop(
            0, n_sweeps, sweep,
            (spins, jnp.zeros((r,), jnp.float32), jnp.zeros((r,), jnp.int32)),
        )
    (spins,), padded_betas, r = _pad_replicas([spins], betas, r_blk)
    out, de, nacc = _ising.ising_sweep_fused_pallas(
        spins, words, t0, padded_betas, n_sweeps=n_sweeps,
        replica_offset=off, j=j, b=b,
        rule=rule, r_blk=r_blk, pack_bits=pack_bits, interpret=_interpret(),
    )
    return out[:r], de[:r], nacc[:r]


@partial(
    jax.jit,
    static_argnames=(
        "n_sweeps", "q", "j", "rule", "r_blk", "pack_bits", "use_pallas"
    ),
)
def potts_sweep_fused(
    states: jnp.ndarray,
    key: jnp.ndarray,
    t: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    n_sweeps: int,
    q: int,
    replica_offset=0,
    j: float = 1.0,
    rule: str = "metropolis",
    r_blk: int = 4,
    pack_bits: bool = False,
    use_pallas: bool = True,
):
    """Interval-fused Potts sweeps; see `ising_sweep_fused` for the contract
    (including the sharded-replica ``replica_offset`` counter convention).

    The ``use_pallas=False`` path applies `ref.potts_sweep` ``n_sweeps``
    times on `prng.potts_sweep_uniforms` — exact in colours and counts, ΔE
    to f32 rounding, against the fused kernel.
    """
    words, t0 = _fused_prelude(key, t)
    off = jnp.asarray(replica_offset).astype(jnp.uint32).reshape(-1)[:1]
    r = states.shape[0]
    h, w = states.shape[-2], states.shape[-1]
    if not use_pallas:
        rep = off[0] + jnp.arange(r, dtype=jnp.uint32)

        def sweep(i, carry):
            s, de, na = carry
            u = _prng.potts_sweep_uniforms(
                words, t0[0] + jnp.uint32(i), rep, h, w
            )
            s, d, n = _ref.potts_sweep(s, u, betas, q=q, j=j, rule=rule)
            return s, de + d, na + n

        return jax.lax.fori_loop(
            0, n_sweeps, sweep,
            (states, jnp.zeros((r,), jnp.float32), jnp.zeros((r,), jnp.int32)),
        )
    (states,), padded_betas, r = _pad_replicas([states], betas, r_blk)
    out, de, nacc = _potts.potts_sweep_fused_pallas(
        states, words, t0, padded_betas, n_sweeps=n_sweeps, q=q,
        replica_offset=off, j=j,
        rule=rule, r_blk=r_blk, pack_bits=pack_bits, interpret=_interpret(),
    )
    return out[:r], de[:r], nacc[:r]


def _round_prelude(key, t, phase, rung, energy):
    """Normalize the round-kernel inputs (words, t0, ph0, rung, energy)."""
    words, t0 = _fused_prelude(key, t)
    ph0 = jnp.asarray(phase).astype(jnp.int32).reshape(1)
    rung = jnp.asarray(rung, jnp.int32)
    energy = jnp.asarray(energy, jnp.float32)
    return words, t0, ph0, rung, energy


@partial(
    jax.jit,
    static_argnames=(
        "n_sweeps", "n_rounds", "j", "b", "rule", "criterion", "pairing",
        "pack_bits", "use_pallas",
    ),
)
def ising_round_fused(
    spins: jnp.ndarray,
    key: jnp.ndarray,
    t: jnp.ndarray,
    phase: jnp.ndarray,
    rung: jnp.ndarray,
    energy: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    n_sweeps: int,
    n_rounds: int = 1,
    j: float = 1.0,
    b: float = 0.0,
    rule: str = "metropolis",
    criterion: str = "logistic",
    pairing: str = "deo",
    pack_bits: bool = False,
    use_pallas: bool = True,
):
    """Whole-PT-round launch: ``n_rounds`` × (``n_sweeps`` sweeps + exchange).

    The in-kernel exchange is temp-mode DEO/SEO with uniforms from the
    counter PRNG's swap stream (`prng.swap_uniforms` at the global swap
    ``phase``); ``rung``/``energy`` are the per-slot rung map and energies,
    ``betas`` the rung-ordered ladder.  The ``use_pallas=False`` pure-JAX
    reference composes `ising_sweep_fused` (reference mode) with the shared
    `exchange.exchange_step` per round — bit-exact with the kernel in
    interpret mode where ΔE is integer-valued (tests/test_fused_round.py
    pins it), and to f32 rounding in the energies otherwise.  Keying the swap
    stream on ``phase`` makes the trajectory invariant to ``n_rounds``
    launch grouping: K rounds in one launch ≡ K single-round launches.

    Returns ``(spins', rung', energy', n_accepted, accept, prob, attempt)``;
    diagnostics are (n_rounds, R) in `core.swap.accept_pairs` conventions
    (accept/attempt bool).
    """
    words, t0, ph0, rung, energy = _round_prelude(key, t, phase, rung, energy)
    r = spins.shape[0]
    if not use_pallas:
        na_total = jnp.zeros((r,), jnp.int32)
        acc_rows, prob_rows, att_rows = [], [], []
        for k in range(n_rounds):
            spins, de, na = ising_sweep_fused(
                spins, key, t0[0] + jnp.uint32(k * n_sweeps), betas[rung],
                n_sweeps=n_sweeps, j=j, b=b, rule=rule, use_pallas=False,
            )
            energy = energy + de
            na_total = na_total + na
            rung, acc, prob, att, _ = _kx.exchange_step(
                rung, energy, betas, ph0[0] + jnp.int32(k), words,
                pairing=pairing, criterion=criterion,
            )
            acc_rows.append(acc)
            prob_rows.append(prob)
            att_rows.append(att)
        return (
            spins, rung, energy, na_total,
            jnp.stack(acc_rows), jnp.stack(prob_rows), jnp.stack(att_rows),
        )
    out, rung, energy, nacc, acc, prob, att = _ising.ising_round_fused_pallas(
        spins, words, t0, ph0, rung, energy, betas,
        n_sweeps=n_sweeps, n_rounds=n_rounds, j=j, b=b, rule=rule,
        criterion=criterion, pairing=pairing, pack_bits=pack_bits,
        interpret=_interpret(),
    )
    return out, rung, energy, nacc, acc.astype(bool), prob, att.astype(bool)


@partial(
    jax.jit,
    static_argnames=(
        "n_sweeps", "n_rounds", "q", "j", "rule", "criterion", "pairing",
        "pack_bits", "use_pallas",
    ),
)
def potts_round_fused(
    states: jnp.ndarray,
    key: jnp.ndarray,
    t: jnp.ndarray,
    phase: jnp.ndarray,
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
    use_pallas: bool = True,
):
    """Whole-PT-round Potts launch; see `ising_round_fused` for the contract."""
    words, t0, ph0, rung, energy = _round_prelude(key, t, phase, rung, energy)
    r = states.shape[0]
    if not use_pallas:
        na_total = jnp.zeros((r,), jnp.int32)
        acc_rows, prob_rows, att_rows = [], [], []
        for k in range(n_rounds):
            states, de, na = potts_sweep_fused(
                states, key, t0[0] + jnp.uint32(k * n_sweeps), betas[rung],
                n_sweeps=n_sweeps, q=q, j=j, rule=rule, use_pallas=False,
            )
            energy = energy + de
            na_total = na_total + na
            rung, acc, prob, att, _ = _kx.exchange_step(
                rung, energy, betas, ph0[0] + jnp.int32(k), words,
                pairing=pairing, criterion=criterion,
            )
            acc_rows.append(acc)
            prob_rows.append(prob)
            att_rows.append(att)
        return (
            states, rung, energy, na_total,
            jnp.stack(acc_rows), jnp.stack(prob_rows), jnp.stack(att_rows),
        )
    out, rung, energy, nacc, acc, prob, att = _potts.potts_round_fused_pallas(
        states, words, t0, ph0, rung, energy, betas,
        n_sweeps=n_sweeps, q=q, n_rounds=n_rounds, j=j, rule=rule,
        criterion=criterion, pairing=pairing, pack_bits=pack_bits,
        interpret=_interpret(),
    )
    return out, rung, energy, nacc, acc.astype(bool), prob, att.astype(bool)


@partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def wkv6(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    initial_state: jnp.ndarray | None = None,
    *,
    chunk: int = 64,
    use_pallas: bool = True,
):
    """RWKV-6 recurrence; see `ref.wkv6` for the contract.

    Pads T to a multiple of ``chunk`` with w=1, k=0 steps (state-neutral).
    """
    if not use_pallas:
        return _ref.wkv6(r, k, v, w, u, initial_state)
    bh, t, dk = r.shape
    pad = (-t) % chunk
    if pad:
        zk = jnp.zeros((bh, pad, dk), r.dtype)
        zv = jnp.zeros((bh, pad, v.shape[-1]), v.dtype)
        r = jnp.concatenate([r, zk], axis=1)
        k = jnp.concatenate([k, zk], axis=1)
        v = jnp.concatenate([v, zv], axis=1)
        w = jnp.concatenate([w, jnp.ones((bh, pad, dk), w.dtype)], axis=1)
    o, s = _wkv6.wkv6_pallas(
        r, k, v, w, u, initial_state, chunk=chunk, interpret=_interpret()
    )
    return o[:, :t], s
