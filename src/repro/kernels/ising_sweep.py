"""Pallas TPU kernel: checkerboard Metropolis sweep for the 2-D Ising model.

This is the paper's compute hot-spot (the per-iteration MH update, §3)
re-thought for the TPU memory hierarchy (DESIGN.md §2/§6):

* one grid step moves a **block of replicas** with their full (L, L)
  lattices into VMEM — the analogue of the paper's "replicas per CUDA
  block" question (Fig. 6); the block size `r_blk` is the tuning knob swept by
  ``benchmarks/tile_sweep.py``.  Inside the block the kernel sweeps one
  replica at a time (`repro.kernels.lattice`, which holds the launch
  plumbing all lattice kernels share);
* both colour half-sweeps run back-to-back in-kernel, so each sweep costs one
  HBM round-trip of the spin block instead of two;
* spins are int8 in HBM (8× denser than the f32 math dtype) and are widened
  to f32 only inside VMEM.

Three kernels share that tile strategy (DESIGN.md §6):

* ``ising_sweep_pallas`` — **one sweep per launch**; the random uniforms are
  a kernel *input* stream ``(R, 2, L, L)`` f32, so the CPU
  ``interpret=True`` path matches `ref.ising_sweep` exactly in spins and
  acceptance counts.  Modeled HBM
  traffic: int8 spins in+out (2 B/cell) plus the externally generated
  uniforms stream (8 B/cell written by the generator + 8 B/cell read back) =
  **18 B/cell/sweep** (`hbm_bytes_per_cell_sweep`).
* ``ising_sweep_fused_pallas`` — **one swap interval per launch**: all
  ``n_sweeps`` sweeps run with the spin block VMEM-resident and the uniforms
  generated *in-kernel* by the counter PRNG (`repro.kernels.prng`, threefry
  from ``(key, sweep, replica, colour)``), accumulating per-replica
  ΔE/acceptance in-kernel.  The spin block crosses HBM once each way per
  *interval*, cutting modeled traffic to **2 B/cell/interval** plus O(R)
  scalars — the paper's single-launch device residency (its 986× CUDA
  recipe) applied to the TPU memory hierarchy.  The stream is deterministic
  pure-uint32 arithmetic, so spins and acceptance counts are bit-exact
  with repeated `ref.ising_sweep` application fed
  `prng.ising_sweep_uniforms`.
* ``ising_round_fused_pallas`` — **one launch = whole PT round(s)**: sweeps
  *plus* the temp-mode DEO/SEO exchange, with the swap uniforms drawn from
  the counter PRNG's swap stream (`prng.swap_uniforms`) and the slot↔rung
  permutation applied in-kernel (`repro.kernels.exchange`).  Eliminates the
  per-swap kernel exit + host round-trip entirely; with ``n_rounds > 1``
  the spin block stays VMEM-resident across multiple exchanges.

The fused and round kernels sweep the lattice **split by colour**
(`repro.kernels.lattice`): two (L/2, L) half-planes ``X_c[k, j] = s[2k +
((j + c) & 1), j]``, split once per launch and merged at its end, so each
half-sweep draws, computes and compares only the L²/2 sites of its colour
instead of the whole plane masked to half.  The site counter feeding the
threefry draw is each element's real linear index ``(2k + ((j + c) &
1))·L + j``, built once per launch, and the acceptance arithmetic is the
same per site, so the stream is unchanged: the split launches are bit-equal
to the whole-plane sweep fed `prng.ising_sweep_uniforms`.  Their
operations are named ``ising_sweep_fused_split`` and
``ising_round_fused_split`` in a profile.  The per-sweep kernel, whose
uniforms arrive as whole planes, keeps the whole-plane body, as does
bit-plane packing.

ΔE totals come from integer-valued sums over the flipped sites (Σ s·nbr
and Σ s; `_flip_energy`), exact in any reduction order up to 2^23 sites,
so every backend and every storage layout gives the same bits.  The
oracle sums f32 per-site values in its backend's own order, so kernel and
oracle ΔE agree to rounding; spins and acceptance counts are exact.

All fused variants take ``pack_bits``: bit-plane **multispin packing** of
the replica axis (Weigel, arXiv:1004.0023) — spins live as 1 bit per
replica in uint32 words, neighbour counts come from a bitwise full-adder
tree, and ΔE is table-selected per replica; bitwise-identical trajectories
to the unpacked path (pinned by tests).

VMEM working set per grid step (bytes; pinned by tests/test_kernels.py and
checked by the tile sweep).  The models count the whole block's working
copies at the whole plane's size, an upper bound now that the kernels
sweep one replica, and on the fused paths one colour half-plane, at a
time:

* per-sweep: r_blk · L² · (2 int8 in/out + 2·4 u-f32 + 4 f32 widened +
  4 f32 neighbour-sum) = 18·r_blk·L²; L=300, r_blk=8 ≈ 12.4 MiB — just
  inside a v5e core's 16 MB (`vmem_working_set_bytes`);
* fused: the uniforms input stream is replaced by one in-flight colour plane
  of PRNG draws (4 B bits + 4 B f32) plus O(r_blk) key/counter state —
  same 18 B/cell total (`vmem_working_set_bytes_fused`), the win is HBM
  traffic, not VMEM footprint.

On hardware, the trailing lattice dim should be padded to a multiple of 128
lanes for full VPU utilization; L=300 runs on padded (8, 128) tiles.
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
    split_neighbours,
    swap_odd_columns,
    sweep_call,
)


def _widen(s: jnp.ndarray) -> jnp.ndarray:
    """int8 spins -> f32, through int32 (Mosaic casts int8 only to int32)."""
    return s.astype(jnp.int32).astype(jnp.float32)


def _narrow(s: jnp.ndarray) -> jnp.ndarray:
    return s.astype(jnp.int32).astype(jnp.int8)


def _flip_energy(s_nbr, s_sum, j, b):
    """ΔE of one colour's flips, Σ 2s(j·nbr − b), from its integer sums.

    ``s_nbr`` is Σ s·nbr and ``s_sum`` is Σ s over the flipped sites (f32
    sums of integer values; ``s_sum`` unused when b = 0).  Every partial
    sum is an integer of magnitude ≤ 4·H·W/2, so for lattices of up to
    2^23 sites (L ≤ 2896) they are exact in any order, and every backend
    and storage layout gives the same total.
    """
    de = j * s_nbr
    if b:
        de = de - b * s_sum
    return 2.0 * de


def _flip(s, nbr, u, beta, active, *, j, b, rule):
    """Metropolis/Glauber flips of the spins ``s`` given their neighbour
    sums and uniforms, restricted to ``active`` unless it is None.

    Returns ``(s', delta_e (g, 1, 1), n_accepted (g, 1, 1))``.
    """
    de = 2.0 * s * (j * nbr - b)
    accept = u < accept_prob(de, beta, rule)
    if active is not None:
        accept = accept & active
    flipped = jnp.where(accept, s, 0.0)
    s_sum = site_sum(flipped) if b else None
    ds = _flip_energy(site_sum(flipped * nbr), s_sum, j, b)
    na = site_sum(accept.astype(jnp.int32))
    return jnp.where(accept, -s, s), ds, na


def _ising_sweep_body(s, beta, parity, draw, *, j, b, rule):
    """One checkerboard sweep (two half-sweeps) on a widened f32 spin plane.

    The per-sweep kernel's body: ``draw(colour)`` reads the whole plane's
    uniforms from the input stream, and each half-sweep masks its colour
    with the (H, W) ``parity`` map.  ``beta`` is a (g, 1, 1) column.
    Returns ``(s', delta_e (g, 1, 1), n_accepted (g, 1, 1))``.
    """
    ds = jnp.zeros(beta.shape, jnp.float32)
    na = jnp.zeros(beta.shape, jnp.int32)
    for color in (0, 1):  # static unroll: two half-sweeps, one HBM round-trip
        nbr = roll1(s, 1, 1) + roll1(s, -1, 1) + roll1(s, 1, 2) + roll1(s, -1, 2)
        s, d, n = _flip(
            s, nbr, draw(color), beta, parity == color, j=j, b=b, rule=rule
        )
        ds, na = ds + d, na + n
    return s, ds, na


def _ising_sweep_body_split(x, beta, colours, draw, *, j, b, rule):
    """`_ising_sweep_body` on the colour-split layout (`repro.kernels.lattice`).

    ``x`` is the pair of (g, H/2, W) colour half-planes, ``colours`` their
    column parity and ``draw(c)`` colour c's uniforms at its sites' own
    counters.  Every element of a half-plane is a site of its colour, so
    nothing is masked and each half-sweep computes half the plane.
    """
    x = list(x)
    ds = jnp.zeros(beta.shape, jnp.float32)
    na = jnp.zeros(beta.shape, jnp.int32)
    for color in (0, 1):
        nbr = split_neighbours(x[1 - color], colours, color)
        x[color], d, n = _flip(
            x[color], nbr, draw(color), beta, None, j=j, b=b, rule=rule
        )
        ds, na = ds + d, na + n
    return tuple(x), ds, na


def _plane_kind(j, b, rule) -> Kind:
    """The per-sweep kernel's whole-plane Ising sweep."""
    return Kind(
        load=_widen,
        step=functools.partial(_ising_sweep_body, j=j, b=b, rule=rule),
        store=lambda s, _n: _narrow(s),
    )


def _fused_kind(j, b, rule, pack_bits) -> Kind:
    """The fused and round kernels' Ising sweep: bit-plane packed, or on
    the colour-split layout."""
    if pack_bits:
        return Kind(
            load=lambda s8: _pack_spins(_widen(s8)),
            step=functools.partial(_ising_sweep_body_packed, j=j, b=b, rule=rule),
            store=lambda words, n: _narrow(_unpack_spins(words, n)),
            whole_block=True,
        )
    return Kind(
        load=lambda s8: swap_odd_columns(_widen(s8[:, 0]), _widen(s8[:, 1])),
        step=functools.partial(_ising_sweep_body_split, j=j, b=b, rule=rule),
        store=lambda x, _n: jnp.stack(
            [_narrow(p) for p in swap_odd_columns(*x)], axis=1
        ),
        colour_split=True,
    )


def _launch_name(stem: str, pack_bits: bool) -> str | None:
    """The kernel operation's name in a profile: the colour-split launches
    say so; a packed launch keeps its wrapper's name."""
    return None if pack_bits else f"{stem}_split"


def ising_sweep_pallas(
    spins: jnp.ndarray,
    u: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    j: float = 1.0,
    b: float = 0.0,
    rule: str = "metropolis",
    r_blk: int = 8,
    interpret: bool = True,
):
    """pallas_call wrapper. See `repro.kernels.ref.ising_sweep` for semantics.

    Args:
      spins: (R, L, L) int8; R must be a multiple of ``r_blk`` (ops.py pads).
      u: (R, 2, L, L) f32 uniforms; betas: (R,) f32.
      r_blk: replicas per grid step (the Fig.-6 "block size" analogue).
      interpret: True on CPU; False on real TPU.
    """
    return sweep_call(
        _plane_kind(j, b, rule), spins, u, betas, r_blk=r_blk,
        interpret=interpret,
    )


# -- bit-plane multispin packing (Weigel, arXiv:1004.0023 §multi-spin) ---------
#
# An Ising spin is one bit; storing a replica block as f32 planes spends 32×
# the state bytes and runs the neighbour reduction on r_blk separate f32
# planes.  Packing the *replica axis* into uint32 bit-plane words (spin k of
# word w = replica 32w+k; up=1) lets one logical op update 32 replicas'
# worth of lattice at once: the 4-neighbour up-count (0..4) comes from a
# bitwise full-adder tree over the 4 rolled word planes, and ΔE is selected
# per replica from the 10 possible values (s ∈ {−1,+1} × count ∈ 0..4) by
# nested `where`s on the count's 3 bit-planes.  The table entries are built
# with the *same f32 op sequence* as the unpacked ``2.0 * s * (j*nbr - b)``,
# so acceptances match; ΔE comes from the same exact integer sums
# (`_flip_energy`), so the packed path is bit-equal to the unpacked one —
# pinned by tests/test_fused_round.py.


def _pack_spins(s: jnp.ndarray):
    """(r, l, l) ±1 f32 → tuple of ⌈r/32⌉ (l, l) uint32 bit-plane words."""
    r = s.shape[0]
    words = []
    for w in range((r + 31) // 32):
        acc = jnp.zeros(s.shape[1:], jnp.uint32)
        for k in range(min(32, r - 32 * w)):
            bit = (s[32 * w + k] > 0).astype(jnp.uint32)
            acc = acc | (bit << jnp.uint32(k))
        words.append(acc)
    return tuple(words)


def _unpack_spins(words, r: int) -> jnp.ndarray:
    """Inverse of `_pack_spins`: bit-plane words → (r, l, l) ±1 f32."""
    planes = []
    for i in range(r):
        bit = (words[i // 32] >> jnp.uint32(i % 32)) & jnp.uint32(1)
        bit = jax.lax.bitcast_convert_type(bit, jnp.int32)
        planes.append(2.0 * bit.astype(jnp.float32) - 1.0)
    return jnp.stack(planes)


def _majority(a, b, c):
    return (a & b) | (a & c) | (b & c)


def _sel_cnt(n0, n1, n2, vals):
    """Select ``vals[cnt]`` from the count's bit-planes (cnt = n0+2·n1+4·n2).

    cnt ∈ 0..4, so n2 set implies n0 = n1 = 0; two nested `where` levels
    cover all five values without a gather.
    """
    lo = jnp.where(n0 > 0, vals[1], vals[0])
    mid = jnp.where(n0 > 0, vals[3], vals[2])
    x = jnp.where(n1 > 0, mid, lo)
    return jnp.where(n2 > 0, vals[4], x)


_NBR = [2 * cnt - 4 for cnt in range(5)]  # neighbour sum of an up-count


def _ising_de_tables(j, b):
    """ΔE(s, count) lookup rows, one per spin sign, f32-op-identical.

    Entry ``cnt`` is ``2.0 * s * (j * nbr - b)`` with ``nbr = _NBR[cnt]``,
    evaluated with the same jnp f32 op order as the unpacked body so the
    selected values match it bitwise.
    """
    rows = {}
    for sv in (-1.0, 1.0):
        s = jnp.float32(sv)
        rows[sv] = [
            2.0 * s * (j * jnp.float32(nbr) - b) for nbr in _NBR
        ]
    return rows[-1.0], rows[1.0]


def _ising_sweep_body_packed(words, beta, parity, draw, *, j, b, rule):
    """`_ising_sweep_body` on bit-plane-packed spins (same protocol).

    ``words`` is the `_pack_spins` tuple; r is recovered from the beta
    column.  The uniforms draw and acceptance comparison reuse the exact
    unpacked expressions on each replica's plane, and ΔE comes from the
    same integer sums — only the spin storage and neighbour count differ.
    """
    r = beta.shape[0]
    neg_tab, pos_tab = _ising_de_tables(j, b)
    one = jnp.uint32(1)
    ds = jnp.zeros(beta.shape, jnp.float32)
    na = jnp.zeros(beta.shape, jnp.int32)
    for color in (0, 1):
        u = draw(color)
        new_words = []
        nbr_sums, s_sums, acc_sums = [], [], []
        for wi, word in enumerate(words):
            # 4-neighbour up-count via a bitwise full adder on rolled planes:
            # count bit-planes (n0, n1, n2) hold cnt = n0 + 2·n1 + 4·n2.
            up = roll1(word, 1, 0)
            dn = roll1(word, -1, 0)
            lf = roll1(word, 1, 1)
            rt = roll1(word, -1, 1)
            s0, c0 = up ^ dn, up & dn
            s1, c1 = lf ^ rt, lf & rt
            n0 = s0 ^ s1
            c2 = s0 & s1
            n1 = c0 ^ c1 ^ c2
            n2 = _majority(c0, c1, c2)
            flips = jnp.zeros_like(word)
            for k in range(min(32, r - 32 * wi)):
                i = 32 * wi + k
                kk = jnp.uint32(k)
                sbit = (word >> kk) & one
                b0 = (n0 >> kk) & one
                b1 = (n1 >> kk) & one
                b2 = (n2 >> kk) & one
                de = jnp.where(
                    sbit > 0,
                    _sel_cnt(b0, b1, b2, pos_tab),
                    _sel_cnt(b0, b1, b2, neg_tab),
                )
                accept = (u[i] < accept_prob(de, beta[i], rule)) & (
                    parity == color
                )
                flips = flips | (accept.astype(jnp.uint32) << kk)
                s_nbr = jnp.where(
                    sbit > 0,
                    _sel_cnt(b0, b1, b2, [float(n) for n in _NBR]),
                    _sel_cnt(b0, b1, b2, [float(-n) for n in _NBR]),
                )
                nbr_sums.append(site_sum(jnp.where(accept, s_nbr, 0.0)[None]))
                if b:
                    s_i = jnp.where(sbit > 0, 1.0, -1.0)
                    s_sums.append(site_sum(jnp.where(accept, s_i, 0.0)[None]))
                acc_sums.append(site_sum(accept.astype(jnp.int32)[None]))
            new_words.append(word ^ flips)
        words = tuple(new_words)
        s_sum = jnp.concatenate(s_sums) if b else None
        ds = ds + _flip_energy(jnp.concatenate(nbr_sums), s_sum, j, b)
        na = na + jnp.concatenate(acc_sums)
    return words, ds, na


def ising_sweep_fused_pallas(
    spins: jnp.ndarray,
    key_words: jnp.ndarray,
    t0: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    n_sweeps: int,
    replica_offset: jnp.ndarray | None = None,
    j: float = 1.0,
    b: float = 0.0,
    rule: str = "metropolis",
    r_blk: int = 8,
    pack_bits: bool = False,
    interpret: bool = True,
):
    """Interval-fused pallas_call wrapper (see module docstring).

    Args:
      spins: (R, L, L) int8; R must be a multiple of ``r_blk`` (ops.py pads).
      key_words: (2,) uint32 run-key words (`prng.key_words`).
      t0: (1,) uint32 global sweep counter at interval entry.
      betas: (R,) f32.
      n_sweeps: sweeps fused into this launch (static).
      replica_offset: (1,) uint32 global index of local slot 0 (sharded
        replica axis); default 0 keeps single-device streams unchanged.
      r_blk: replicas per grid step (the Fig.-6 "block size" analogue).
      pack_bits: bit-plane-pack the replica axis inside the kernel
        (multispin coding); bitwise-identical trajectory, denser VMEM.
      interpret: True on CPU; False on real TPU.

    Returns ``(spins', delta_e, n_accepted)`` with ΔE/acceptance summed over
    the whole interval.
    """
    if replica_offset is None:
        replica_offset = jnp.zeros((1,), jnp.uint32)
    return fused_call(
        _fused_kind(j, b, rule, pack_bits), spins, key_words, t0, betas,
        n_sweeps=n_sweeps, replica_offset=replica_offset, r_blk=r_blk,
        interpret=interpret,
        name=_launch_name("ising_sweep_fused", pack_bits),
    )


def ising_round_fused_pallas(
    spins: jnp.ndarray,
    key_words: jnp.ndarray,
    t0: jnp.ndarray,
    phase0: jnp.ndarray,
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
    interpret: bool = True,
):
    """Whole-PT-round pallas_call wrapper: one launch = ``n_rounds`` rounds.

    Args:
      spins: (R, L, L) int8 (whole ladder; no r_blk padding — the exchange
        couples all replicas, so the launch is a single grid step).
      key_words: (2,) uint32 run-key words (`prng.key_words`).
      t0: (1,) uint32 global sweep counter at entry.
      phase0: (1,) int32 global swap-phase counter at entry.
      rung: (R,) int32 slot→rung map; energy: (R,) f32 per-slot energies.
      betas: (R,) f32 inverse temperatures in rung order (cold→hot).
      n_sweeps: sweeps per round (the swap interval, static).
      n_rounds: PT rounds fused into this launch (static).
      pairing: "deo" | "seo"; criterion: "logistic" | "metropolis".
      pack_bits: bit-plane multispin storage in VMEM (bitwise-identical).
      interpret: True on CPU; False on real TPU.

    Returns ``(spins', rung', energy', n_accepted, accept, prob, attempt)``
    with the three diagnostic rows shaped (n_rounds, R) (accept/attempt as
    int32 0/1).
    """
    return round_call(
        _fused_kind(j, b, rule, pack_bits), spins, key_words, t0, phase0,
        rung, energy, betas, n_sweeps=n_sweeps, n_rounds=n_rounds,
        criterion=criterion, pairing=pairing, interpret=interpret,
        name=_launch_name("ising_round_fused", pack_bits),
    )


def vmem_working_set_bytes(r_blk: int, length: int) -> int:
    """Static VMEM budget model used by the tile sweep (bytes per grid step)."""
    spins_in = r_blk * length * length  # int8
    uniforms = r_blk * 2 * length * length * 4
    widened = r_blk * length * length * 4  # f32 working copy
    nbr = r_blk * length * length * 4  # neighbour-sum temporary
    out = r_blk * length * length
    return spins_in + uniforms + widened + nbr + out


def vmem_working_set_bytes_fused(r_blk: int, length: int) -> int:
    """VMEM budget of the interval-fused kernel (bytes per grid step).

    The per-sweep kernel's 8 B/cell uniforms *input block* is replaced by one
    in-flight colour plane of counter-PRNG draws (4 B uint32 bits + 4 B f32
    uniforms) plus O(r_blk) key/counter scalars — the total stays 18 B/cell;
    fusing wins HBM traffic (`hbm_bytes_per_cell_sweep`), not VMEM footprint.
    """
    cells = r_blk * length * length
    spins_in = cells  # int8
    bits = cells * 4  # uint32 PRNG draw, active colour
    uniforms = cells * 4  # f32 uniforms, active colour
    widened = cells * 4  # f32 working copy
    nbr = cells * 4  # neighbour-sum temporary
    out = cells
    rng_state = 4 * 4 * r_blk  # stream/sweep key words + replica counters
    return spins_in + bits + uniforms + widened + nbr + out + rng_state


def vmem_working_set_bytes_packed(r_blk: int, length: int) -> int:
    """VMEM budget of the fused kernel with bit-plane multispin packing.

    The f32 widened carry (4 B/cell) and the f32 neighbour-sum plane
    (4 B/cell) are replaced by ⌈r_blk/32⌉ uint32 bit-plane words plus the
    full-adder count planes (rolled plane + 3 count bit-planes, all uint32)
    and per-replica selected-ΔE / accept planes (4 + 1 B/cell).  Net:
    18 → 15 + 20·⌈r_blk/32⌉·L²/cells B/cell (17.5 at r_blk=8, 15.6 at 32) —
    a modest VMEM saving; the real packing win is the neighbour reduction
    running on uint32 words (32 replica lanes per logical op) instead of
    r_blk separate f32 planes.
    """
    cells = r_blk * length * length
    plane = length * length
    n_words = -(-r_blk // 32)
    spins_in = cells  # int8 in
    packed = 4 * n_words * plane  # bit-plane spin carry (replaces f32 widened)
    adder = 4 * 4 * n_words * plane  # rolled plane + 3 count bit-planes
    bits = cells * 4  # uint32 PRNG draw, active colour
    uniforms = cells * 4  # f32 uniforms, active colour
    de_sel = cells * 4  # selected-ΔE planes (replaces f32 neighbour sum)
    accept = cells  # accept planes (bool)
    out = cells  # int8 out
    rng_state = 4 * 4 * r_blk
    return (
        spins_in + packed + adder + bits + uniforms + de_sel + accept + out
        + rng_state
    )


def hbm_bytes_per_cell_sweep(
    *, fused: bool, sweeps_per_interval: int = 1, rounds_per_launch: int = 1
) -> float:
    """Modeled HBM bytes per lattice cell per sweep (O(R) scalars excluded).

    Per-sweep path: int8 spins in+out (2 B) **plus the uniforms stream** —
    8 B/cell written by the external generator and 8 B/cell read back by the
    kernel — 18 B/cell/sweep.  Fused path: the spin block crosses HBM once
    each way per *launch*, so 2 B/cell amortized over ``sweeps_per_interval
    × rounds_per_launch`` sweeps (the whole-round kernels fold the exchange
    in too, so multi-round launches never touch HBM between rounds); the
    randoms never exist in HBM.

    Delegates to `repro.hlo.traffic.hbm_bytes_per_cell_sweep` — the shared
    model the roofline report and traffic assertions also consume.
    """
    from repro.hlo.traffic import hbm_bytes_per_cell_sweep as model

    return model(
        fused=fused, sweeps_per_interval=sweeps_per_interval,
        rounds_per_launch=rounds_per_launch,
        state_bytes=2.0, uniform_plane_bytes=8.0,
    )
