"""Counter-based in-kernel PRNG for the interval-fused sweep kernels.

The per-sweep kernels take their uniforms as an externally generated
``(R, colours, ..., H, W)`` f32 *input stream* — 8 bytes of pure
random-number HBM traffic per cell per colour against 1-byte int8 spins.
Fusing a whole swap interval into one kernel (DESIGN.md §6) only pays off if
the randoms are generated *inside* VMEM, so this module provides the
established GPU-Ising recipe (Weigel, arXiv:1004.0023): a **counter-based**
generator — Threefry-2x32-20 (Salmon et al., SC'11), the same cipher behind
``jax.random`` — evaluated at a deterministic counter derived from

    (run key, sweep counter t, replica index, plane)

where *plane* enumerates the per-sweep random lattices a system consumes
(Ising: one per colour half-sweep; Potts: (proposal, accept) per colour).

Why counter-based and not ``pltpu.prng_random_bits``: the hardware PRNG is
stateful and backend-specific, so a CPU oracle could never reproduce its
stream.  Threefry is pure uint32 arithmetic — the *same jnp ops* run inside
the Pallas kernel body (Mosaic or ``interpret=True``) and in the pure-JAX
reference below, which is what keeps the fused kernels bit-exact against
``ref.ising_sweep`` / ``ref.potts_sweep`` fed this module's stream
(tests/test_kernels.py pins it).

Stream derivation (all uint32)::

    stream key  = threefry(key_words, (DOMAIN, DOMAIN))     # once per run
    sweep key   = threefry(stream key, (t, replica))        # per sweep x replica
    lattice bits= threefry(sweep key, (plane, i*W + j))     # per site

The DOMAIN constant separates this stream from every ``jax.random`` fold-in
derivation of the same run key (the engine's swap phase draws
``fold_in(key, 2t+1)`` uniforms from the *same* root key; without domain
separation the (t=0, replica=odd) sweep keys would collide with swap keys).

Uniforms are the top 24 bits scaled by 2^-24 — exact in f32, in [0, 1), and
never 1.0, matching the half-open contract of the acceptance comparisons.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "DOMAIN",
    "SWAP_DOMAIN",
    "threefry2x32",
    "key_words",
    "stream_key",
    "sweep_key",
    "iota_u32",
    "to_uniform",
    "site_index",
    "site_uniforms",
    "plane_uniforms",
    "ising_sweep_uniforms",
    "potts_sweep_uniforms",
    "swap_stream_key",
    "swap_key",
    "swap_uniforms",
    "seo_coin",
]

# Domain-separation constant for the fused-sweep stream (arbitrary, fixed
# forever: changing it changes every fused trajectory).
DOMAIN = 0x46555345  # ascii "FUSE"
# Domain-separation constant for the in-kernel *exchange* stream of the
# whole-round fused kernels.  The round kernel draws its per-rung swap
# uniforms from (run key, swap-phase counter, rung) inside the launch; this
# constant keeps those draws disjoint from both the sweep stream above and
# every `jax.random` fold-in of the same root key.  Like DOMAIN: arbitrary,
# fixed forever.
SWAP_DOMAIN = 0x53574150  # ascii "SWAP"

_KS_PARITY = 0x1BD11BDA  # Threefry key-schedule constant
# Threefry-2x32 rotation schedule: groups of four rounds alternate between
# these two rotation quadruples; 20 rounds = 5 groups.
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x: jnp.ndarray, d: int) -> jnp.ndarray:
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32-20 block cipher: key (k0,k1), counter (x0,x1) -> 2 words.

    All inputs are (broadcastable) uint32 arrays; uint32 addition wraps
    mod 2^32 by definition, which is exactly the cipher's arithmetic.  This
    is the reference implementation for both the pure-JAX stream functions
    below and the Pallas kernel bodies — one function, one stream.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    x0 = jnp.asarray(x0, jnp.uint32)
    x1 = jnp.asarray(x1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_KS_PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for group in range(5):
        for d in _ROTATIONS[group % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, d) ^ x0
        inject = group + 1
        x0 = x0 + ks[inject % 3]
        x1 = x1 + ks[(inject + 1) % 3] + jnp.uint32(inject)
    return x0, x1


def key_words(key: jax.Array) -> jnp.ndarray:
    """(2,) uint32 key words from a typed JAX PRNG key (or raw uint32 data).

    Threefry keys are two words; wider key data (e.g. the rbg impl) is
    folded down by XOR so every bit of the original key still matters.
    """
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = key
    data = jnp.asarray(data, jnp.uint32).reshape(-1)
    k0 = data[0]
    k1 = data[1] if data.shape[0] > 1 else jnp.uint32(0)
    for i in range(2, data.shape[0]):
        k0, k1 = (k0 ^ data[i], k1) if i % 2 == 0 else (k0, k1 ^ data[i])
    return jnp.stack([k0, k1])


def stream_key(words: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Domain-separated root of the fused-sweep stream (two uint32 scalars)."""
    return threefry2x32(words[0], words[1], DOMAIN, DOMAIN)


def sweep_key(s0, s1, t, replica):
    """Per-(sweep, replica) subkey; ``t``/``replica`` broadcast elementwise."""
    return threefry2x32(s0, s1, t, replica)


def iota_u32(shape, dimension: int) -> jnp.ndarray:
    """uint32 `broadcasted_iota`, built as int32 and bitcast (Mosaic-safe)."""
    return jax.lax.bitcast_convert_type(
        jax.lax.broadcasted_iota(jnp.int32, shape, dimension), jnp.uint32
    )


def to_uniform(bits: jnp.ndarray) -> jnp.ndarray:
    """Top 24 bits of uint32 draws as f32 in [0, 1).

    ``bits >> 8`` is below 2^24, so it converts exactly through int32 —
    Mosaic has no uint32 -> f32 cast, and the value is the same either way.
    """
    top = jax.lax.bitcast_convert_type(bits >> jnp.uint32(8), jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def site_index(h: int, w: int) -> jnp.ndarray:
    """(h, w) uint32 linear site counter ``i*w + j`` of a whole lattice."""
    return iota_u32((h, w), 0) * jnp.uint32(w) + iota_u32((h, w), 1)


def site_uniforms(w0, w1, plane: int, site: jnp.ndarray) -> jnp.ndarray:
    """f32 uniforms in [0,1) of one random lattice ("plane") at ``site``.

    ``site`` holds the uint32 linear index ``i*W + j`` of each element's
    lattice site, in whatever layout the caller keeps the lattice: a site
    draws the same bits wherever it is stored.  ``w0``/``w1`` are
    per-replica sweep-key words shaped (R, 1, 1), which broadcast against
    the counters without a reshape inside a kernel body.
    """
    b0, _ = threefry2x32(w0, w1, jnp.uint32(plane), site)
    return to_uniform(b0)


def plane_uniforms(w0, w1, plane: int, h: int, w: int) -> jnp.ndarray:
    """(R, h, w) f32 uniforms in [0,1) for one whole random lattice.

    The counter is the linear index ``i*w + j`` (`site_index`), so the
    stream is layout-independent: padding W for TPU lanes, or storing the
    lattice split by colour, does not change the value at a real site.
    """
    return site_uniforms(w0, w1, plane, site_index(h, w))


# -- pure-JAX per-sweep stream (the oracle's view of the kernel stream) --------


def ising_sweep_uniforms(words, t, replica_ids, length: int) -> jnp.ndarray:
    """(R, 2, L, L) f32 — the Ising sweep-``t`` uniforms of the fused stream.

    Feeding this to `ref.ising_sweep` for t = t0..t0+S-1 reproduces
    ``ising_sweep_fused`` over S sweeps bit-for-bit (spins and counters).
    """
    w0, w1 = _replica_keys(words, t, replica_ids)
    return jnp.stack(
        [plane_uniforms(w0, w1, c, length, length) for c in (0, 1)], axis=1
    )


def _replica_keys(words, t, replica_ids):
    """(R, 1, 1) sweep-key words for sweep ``t`` of each listed replica."""
    s0, s1 = stream_key(words)
    rep = jnp.asarray(replica_ids, jnp.uint32).reshape(-1, 1, 1)
    return sweep_key(s0, s1, jnp.uint32(t), rep)


# -- counter-based exchange stream (the in-kernel swap draw) -------------------
#
# Derivation mirrors the sweep stream, keyed on the swap-*phase* counter
# (one increment per exchange attempt) instead of the sweep counter:
#
#     swap stream key = threefry(key_words, (SWAP_DOMAIN, SWAP_DOMAIN))
#     swap step key   = threefry(swap stream key, (phase, 0))
#     rung uniforms   = threefry(swap step key, (0, rung))     # plane 0
#     SEO phase coin  = threefry(swap step key, (1, 0)) & 1    # plane 1
#
# Keying on `phase` (not t) makes the stream invariant to how sweeps are
# grouped into launches: round k of a multi-round launch draws exactly what
# k successive single-round launches would.  The stream deliberately differs
# from the engine's `fold_in(key, 2t+1)` swap draw — like the fused sweep
# stream, whole-round fusion is gated *statistically* (conformance), with
# bit-equality pinned against this stream's own pure-JAX oracle.


def swap_stream_key(words: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Domain-separated root of the in-kernel exchange stream."""
    return threefry2x32(words[0], words[1], SWAP_DOMAIN, SWAP_DOMAIN)


def swap_key(s0, s1, phase):
    """Per-swap-iteration subkey; ``phase`` is the global swap counter."""
    return threefry2x32(s0, s1, jnp.asarray(phase, jnp.uint32), jnp.uint32(0))


def swap_uniforms(words: jnp.ndarray, phase, n: int, shape=None) -> jnp.ndarray:
    """f32 in [0,1): one acceptance uniform per rung for swap ``phase``.

    Same top-24-bit scaling as `plane_uniforms`; the counter is the rung
    index, so the draw at rung r is independent of R (ladder growth never
    perturbs existing rungs' streams).  The result is shaped (n,), or
    ``shape`` with the rungs on its leading axis — the (n, 1, 1) column the
    kernel-side exchange works in.
    """
    s0, s1 = swap_stream_key(words)
    w0, w1 = swap_key(s0, s1, phase)
    rung = iota_u32(shape or (n,), 0)
    b0, _ = threefry2x32(w0, w1, jnp.uint32(0), rung)
    return to_uniform(b0)


def seo_coin(words: jnp.ndarray, phase) -> jnp.ndarray:
    """Scalar int32 in {0, 1}: the SEO even/odd pairing coin for ``phase``."""
    s0, s1 = swap_stream_key(words)
    w0, w1 = swap_key(s0, s1, phase)
    b0, _ = threefry2x32(w0, w1, jnp.uint32(1), jnp.uint32(0))
    return jax.lax.bitcast_convert_type(b0 & jnp.uint32(1), jnp.int32)


def potts_sweep_uniforms(words, t, replica_ids, h: int, w: int) -> jnp.ndarray:
    """(R, 2, 2, H, W) f32 — the Potts sweep-``t`` uniforms (colour x
    (proposal, accept)); plane index is ``2*colour + which``."""
    w0, w1 = _replica_keys(words, t, replica_ids)
    return jnp.stack(
        [
            jnp.stack(
                [plane_uniforms(w0, w1, 2 * c + p, h, w) for p in (0, 1)], axis=1
            )
            for c in (0, 1)
        ],
        axis=1,
    )
