"""Shared Pallas scaffolding of the checkerboard lattice kernels.

The Ising (`repro.kernels.ising_sweep`) and Potts
(`repro.kernels.potts_sweep`) kernels differ only in the arithmetic of one
sweep and in how an int8 lattice is widened into the carry that sweep works
on.  A system describes that with a `Kind`; the three launch shapes live
here, once:

* `sweep_call` — one sweep per launch, uniforms read from an input stream;
* `fused_call` — one swap interval per launch, uniforms drawn in-kernel
  from the counter PRNG (`repro.kernels.prng`);
* `round_call` — whole PT rounds per launch: the interval's sweeps plus the
  temp-mode exchange (`repro.kernels.exchange`), whole ladder in one grid
  step.

Each grid step DMAs a block of ``r_blk`` replicas into VMEM and loops over
it one replica at a time (a `Kind` whose storage spans replicas, like
bit-plane packing, takes the whole block as one group).  Replicas never
interact inside an interval, so the loop changes no result; it keeps the
kernel's vector code one lattice wide, which is what keeps Mosaic's compile
time and code size flat in ``r_blk``.

Mosaic's rules shape the interfaces:

* per-replica rows (betas, ΔE, acceptance counts, rungs, energies) travel
  as (R, 1, 1) columns in (r_blk, 1, 1) blocks.  A rank-1 block must be the
  whole array or a multiple of 128 lanes, and a column broadcasts against
  a (g, H, W) lattice group with no in-kernel reshape;
* scalars (key words, counters, the swap phase) sit in SMEM as (1, n)
  rows: a batch axis that `vmap` puts in front of them (the engine's chain
  ensemble) then leaves the two minor dims whole, as Mosaic requires;
* the scoped-VMEM limit is raised from its 16 MiB default
  (`VMEM_LIMIT_BYTES`), which the whole-block DMA buffers at the paper's
  L=300 approach once Mosaic pads the lattice to (8, 128) tiles.

The colour-split layout.  A checkerboard half-sweep updates only the sites
of one colour, so a sweep over the whole (L, L) plane masked to one colour
computes twice the work it keeps.  A `Kind` with ``colour_split`` is swept
on two compacted half-planes instead, split along rows so that each keeps
all L lanes::

    X_c[k, j] = s[2k + ((j + c) & 1), j]        k < L/2, j < L

A site of colour c at X_c[k, j] has its four neighbours in Y = X_{1-c}:
Y[k, j-1] and Y[k, j+1] along the lanes, and along the sublanes Y[k, j]
and, as p = (j + c) & 1 is 0 or 1, Y[k-1, j] or Y[k+1, j]
(`split_neighbours`).  Periodic wrap holds because L is even, which a
checkerboard needs anyway.  `fused_call` and `round_call` hand the kernel
the lattice's even and odd rows as two planes (`split_rows`,
`merge_rows`: a transpose that XLA folds into the layout copy it makes
around the kernel anyway); the kernel swaps their odd columns
(`swap_odd_columns`) into the colour half-planes as it loads a replica,
and back as it stores it, and the carry holds both half-planes through
all the launch's sweeps.  Each element draws its
uniform at its site's linear index ``(2k + ((j + c) & 1))·L + j``
(`colour_split_geometry`, built once per launch), which is the counter the
whole-plane draw gives that site; the acceptance arithmetic is the same
per site, so spins, acceptance counts and ΔE are those of the whole-plane
sweep, bit for bit.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import exchange as _kx
from repro.kernels import prng

VMEM_LIMIT_BYTES = 100 * 2**20  # of a v5e TensorCore's 128 MiB


class Kind(NamedTuple):
    """What a lattice system contributes to the shared kernels.

    ``load`` widens an int8 group of the kernel's lattice block into the
    sweep's carry and ``store`` narrows it back (``store(carry, g)``);
    ``step(carry, beta, colours, draw)`` is one checkerboard sweep, where
    ``draw(plane)`` gives that plane's uniforms and ``beta`` is a (g, 1, 1)
    column; it returns ``(carry', delta_e, n_accepted)`` with (g, 1, 1)
    totals.  ``whole_block`` makes the whole block one group, for storage
    that spans replicas.

    ``colour_split`` picks the fused and round launches' layout.  False:
    the block is (g, H, W), ``colours`` is the (H, W) checkerboard map and
    each ``draw`` is a whole (g, H, W) plane.  True: the block is the
    (g, 2, H/2, W) pair of row planes (`split_rows`), which ``load`` turns
    into the colour half-planes and ``store`` back (module docstring),
    ``colours`` is the (H/2, W) column parity ``j & 1`` and ``draw(c)``
    gives colour c's (g, H/2, W) uniforms.  The per-sweep launch always
    takes the whole plane.
    """

    load: Callable
    step: Callable
    store: Callable
    whole_block: bool = False
    colour_split: bool = False


# -- kernel-body helpers (Mosaic-safe) -----------------------------------------


def roll1(x: jnp.ndarray, shift: int, axis: int) -> jnp.ndarray:
    """±1 circular shift via slice+concat (lowers on both Mosaic and CPU)."""
    n = x.shape[axis]
    if shift == 1:
        a = jax.lax.slice_in_dim(x, n - 1, n, axis=axis)
        b = jax.lax.slice_in_dim(x, 0, n - 1, axis=axis)
    else:  # shift == -1
        a = jax.lax.slice_in_dim(x, 1, n, axis=axis)
        b = jax.lax.slice_in_dim(x, 0, 1, axis=axis)
    return jnp.concatenate([a, b], axis=axis)


def accept_prob(de, beta, rule):
    """Mirror of `ref.accept_prob` (kept local: kernel code is self-contained)."""
    if rule == "metropolis":
        return jnp.exp(-beta * de)
    if rule == "glauber":
        return jax.nn.sigmoid(-beta * de)
    raise ValueError(rule)


def parity(h: int, w: int) -> jnp.ndarray:
    """(h, w) checkerboard colour map from 2-D iotas (Mosaic-safe)."""
    ii = jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    return (ii + jj) % 2


def require_even(shape) -> None:
    """Refuse a lattice that periodic wrap leaves without a checkerboard.

    On an odd side the wrap-around neighbours share a colour, so a
    half-sweep would flip interacting sites at once.
    """
    h, w = shape[-2:]
    if h % 2 or w % 2:
        raise ValueError(
            f"a checkerboard sweep needs even lattice sides under periodic "
            f"wrap, got {h}x{w}"
        )


def split_rows(lattice: jnp.ndarray) -> jnp.ndarray:
    """(..., H, W) lattice -> (..., 2, H/2, W) row planes: plane p holds
    rows 2k + p.  A transpose, outside any kernel, that XLA folds into the
    layout copy it makes around the kernel anyway."""
    require_even(lattice.shape)
    *lead, h, w = lattice.shape
    return jnp.swapaxes(lattice.reshape(*lead, h // 2, 2, w), -3, -2)


def merge_rows(planes: jnp.ndarray) -> jnp.ndarray:
    """Inverse of `split_rows`: (..., 2, H/2, W) -> (..., H, W)."""
    *lead, _, h2, w = planes.shape
    return jnp.swapaxes(planes, -3, -2).reshape(*lead, 2 * h2, w)


def swap_odd_columns(a: jnp.ndarray, b: jnp.ndarray):
    """``(where(j even, a, b), where(j even, b, a))`` over the last axis j.

    Takes the row planes (rows 2k, 2k+1) to the colour half-planes
    ``X_c[k, j] = s[2k + ((j + c) & 1), j]``, and, being its own inverse,
    the colour half-planes back to the row planes.
    """
    even = jax.lax.broadcasted_iota(jnp.int32, a.shape[-2:], 1) % 2 == 0
    return jnp.where(even, a, b), jnp.where(even, b, a)


def colour_split_geometry(h2: int, w: int):
    """``(colours, sites)`` of the colour-split layout of an (2·h2, w) lattice.

    ``colours`` is the (h2, w) int32 column parity ``j & 1``; ``sites(c)``
    is colour c's uint32 site counter ``(2k + ((j + c) & 1))·w + j``, the
    linear index of the site that X_c[k, j] holds.  Built once per launch.
    """
    k = prng.iota_u32((h2, w), 0)
    j = prng.iota_u32((h2, w), 1)
    one = jnp.uint32(1)
    counters = tuple(
        (k + k + ((j + jnp.uint32(c)) & one)) * jnp.uint32(w) + j
        for c in (0, 1)
    )
    colours = jax.lax.broadcasted_iota(jnp.int32, (h2, w), 1) % 2
    return colours, lambda plane: counters[plane]


def split_neighbours(y: jnp.ndarray, colours: jnp.ndarray, colour: int):
    """4-neighbour sum of colour ``colour``'s sites from the other colour's
    half-plane ``y`` (g, H/2, W); ``colours`` is the column parity map.

    Lanes: Y[k, j∓1].  Sublanes: Y[k, j] and Y[k-1, j] where
    ``(j + colour) & 1`` is 0, else Y[k+1, j].
    """
    up_first = colours == colour  # (j + colour) & 1 == 0
    vertical = y + jnp.where(up_first, roll1(y, 1, 1), roll1(y, -1, 1))
    return vertical + roll1(y, 1, 2) + roll1(y, -1, 2)


def site_sum(x: jnp.ndarray) -> jnp.ndarray:
    """(g, H, W) -> (g, 1, 1) per-replica sum over the lattice.

    Lanes first, then sublanes: Mosaic cannot reduce both in one op.
    """
    return jnp.sum(jnp.sum(x, axis=2, keepdims=True), axis=1, keepdims=True)


def _u32(x) -> jnp.ndarray:
    """int32 scalar (grid or loop index) -> uint32 counter."""
    return x.astype(jnp.uint32)


def _for_each_group(n: int, g: int, fn) -> None:
    """``fn(k, rows)`` for the k-th group of ``g`` replicas of an n-block."""

    def body(k, carry):
        fn(k, pl.ds(k * g, g))
        return carry

    jax.lax.fori_loop(0, n // g, body, 0)


def _geometry(kind: Kind, block_shape):
    """``(colours, sites)`` of a launch's (…, H, W) lattice block, built once
    outside the sweep loop: the map `Kind.step` takes and the site counters
    ``sites(plane)`` each plane's draw is made at."""
    h, w = block_shape[-2:]
    if kind.colour_split:
        return colour_split_geometry(h, w)
    site = prng.site_index(h, w)
    return parity(h, w), lambda plane: site


def _fused_sweeps(kind: Kind, lat, beta, rep, sk, t0, n_sweeps, geometry):
    """``n_sweeps`` counter-PRNG sweeps of one group: the shared inner loop.

    ``rep`` is the (g, 1, 1) column of global replica counters, ``t0``
    the global sweep counter at entry and ``geometry`` the launch's
    `_geometry`.  ΔE and acceptances accumulate per colour within a sweep,
    then per sweep, as repeated `ref` application does.  Returns ``(int8
    lattice, delta_e, n_accepted)``.
    """
    colours, sites = geometry

    def sweep(i, carry):
        x, de, na = carry
        w0, w1 = prng.sweep_key(sk[0], sk[1], t0 + _u32(i), rep)
        draw = lambda plane: prng.site_uniforms(w0, w1, plane, sites(plane))
        x, ds, dn = kind.step(x, beta, colours, draw)
        return x, de + ds, na + dn

    x, de, na = jax.lax.fori_loop(
        0, n_sweeps, sweep,
        (kind.load(lat), jnp.zeros(beta.shape, jnp.float32),
         jnp.zeros(beta.shape, jnp.int32)),
    )
    return kind.store(x, beta.shape[0]), de, na


# -- launch plumbing -------------------------------------------------------------


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _row(x: jnp.ndarray) -> jnp.ndarray:
    """A scalar operand as the (1, n) SMEM row the kernels read."""
    return x.reshape(1, -1)


def _col(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(-1, 1, 1)


def _blocked(r_blk: int, shape) -> pl.BlockSpec:
    """Block ``r_blk`` replicas of an (R, *shape) array along axis 0."""
    return pl.BlockSpec((r_blk, *shape), lambda i: (i,) + (0,) * len(shape))


def _row_outs(r: int, lat_shape):
    return [
        jax.ShapeDtypeStruct((r, *lat_shape), jnp.int8),
        jax.ShapeDtypeStruct((r, 1, 1), jnp.float32),
        jax.ShapeDtypeStruct((r, 1, 1), jnp.int32),
    ]


# -- one sweep per launch ----------------------------------------------------------


def _sweep_kernel(lat_ref, u_ref, beta_ref, out_ref, de_ref, nacc_ref, *,
                  kind):
    par = parity(*lat_ref.shape[-2:])
    g = lat_ref.shape[0] if kind.whole_block else 1

    def group(_, rows):
        draw = lambda plane: u_ref[rows, plane]
        x, de, na = kind.step(
            kind.load(lat_ref[rows]), beta_ref[rows], par, draw
        )
        out_ref[rows] = kind.store(x, g)
        de_ref[rows] = de
        nacc_ref[rows] = na

    _for_each_group(lat_ref.shape[0], g, group)


def sweep_call(kind: Kind, lattice, u, betas, *, r_blk: int, interpret: bool):
    """One sweep of an (R, H, W) int8 lattice fed (R, planes, H, W) uniforms.

    R must be a multiple of ``r_blk`` (`ops` pads).  Returns ``(lattice',
    delta_e (R,), n_accepted (R,))``.
    """
    r, lat_shape = lattice.shape[0], lattice.shape[1:]
    assert r % r_blk == 0, (r, r_blk)
    out, de, nacc = pl.pallas_call(
        functools.partial(_sweep_kernel, kind=kind),
        grid=(r // r_blk,),
        in_specs=[
            _blocked(r_blk, lat_shape),
            _blocked(r_blk, u.shape[1:]),
            _blocked(r_blk, (1, 1)),
        ],
        out_specs=[_blocked(r_blk, lat_shape)] + [_blocked(r_blk, (1, 1))] * 2,
        out_shape=_row_outs(r, lat_shape),
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(lattice, u, _col(betas))
    return out, de.reshape(r), nacc.reshape(r)


# -- one swap interval per launch ------------------------------------------------


def _fused_kernel(lat_ref, beta_ref, kw_ref, t0_ref, off_ref, out_ref, de_ref,
                  nacc_ref, *, kind, n_sweeps):
    """``n_sweeps`` sweeps of an (r_blk, H, W) block, uniforms in-kernel.

    The replica counter is *global*: block offset plus ``off_ref`` (the
    device's first global slot when the replica axis is sharded), so a
    device computing slots [off, off+r_local) draws exactly the streams the
    single-device launch would.
    """
    r_blk = lat_ref.shape[0]
    g = r_blk if kind.whole_block else 1
    geometry = _geometry(kind, lat_ref.shape)
    sk = prng.stream_key((kw_ref[0, 0], kw_ref[0, 1]))
    first = _u32(pl.program_id(0) * r_blk) + off_ref[0, 0]
    t0 = t0_ref[0, 0]

    def group(k, rows):
        rep = prng.iota_u32((g, 1, 1), 0) + (first + _u32(k * g))
        out_ref[rows], de_ref[rows], nacc_ref[rows] = _fused_sweeps(
            kind, lat_ref[rows], beta_ref[rows], rep, sk, t0, n_sweeps,
            geometry,
        )

    _for_each_group(r_blk, g, group)


def fused_call(kind: Kind, lattice, key_words, t0, betas, *, n_sweeps: int,
               replica_offset, r_blk: int, interpret: bool,
               name: str | None = None):
    """``n_sweeps`` counter-PRNG sweeps of an (R, H, W) int8 lattice.

    ``key_words`` is the (2,) uint32 run key (`prng.key_words`), ``t0`` the
    (1,) uint32 sweep counter at entry and ``replica_offset`` the (1,)
    uint32 global index of local slot 0.  R must be a multiple of ``r_blk``.
    A ``colour_split`` kind gets the lattice split by colour and merges it
    on return.  ``name`` names the kernel's operation in a profile.
    Returns ``(lattice', delta_e (R,), n_accepted (R,))`` summed over the
    interval.
    """
    if kind.colour_split:
        lattice = split_rows(lattice)
    r, lat_shape = lattice.shape[0], lattice.shape[1:]
    assert r % r_blk == 0, (r, r_blk)
    out, de, nacc = pl.pallas_call(
        functools.partial(_fused_kernel, kind=kind, n_sweeps=n_sweeps),
        grid=(r // r_blk,),
        in_specs=[
            _blocked(r_blk, lat_shape), _blocked(r_blk, (1, 1)),
            _smem(), _smem(), _smem(),
        ],
        out_specs=[_blocked(r_blk, lat_shape)] + [_blocked(r_blk, (1, 1))] * 2,
        out_shape=_row_outs(r, lat_shape),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=name,
    )(lattice, _col(betas), _row(key_words), _row(t0), _row(replica_offset))
    if kind.colour_split:
        out = merge_rows(out)
    return out, de.reshape(r), nacc.reshape(r)


# -- whole PT rounds per launch --------------------------------------------------


def _round_kernel(lat_ref, beta_ref, kw_ref, t0_ref, ph0_ref, rung_ref,
                  energy_ref, out_ref, rung_out_ref, energy_out_ref, nacc_ref,
                  acc_ref, prob_ref, att_ref, *, kind, n_sweeps, n_rounds,
                  criterion, pairing):
    """``n_rounds`` full PT rounds — sweeps *and* exchange — in one launch.

    Each round is ``n_sweeps`` sweeps of every slot at its current rung's
    temperature (``beta_ref`` is the rung-ordered ladder, gathered per slot
    by one-hot) followed by one temp-mode DEO/SEO exchange
    (`exchange.exchange_columns`) on the energy column, drawn from the
    counter PRNG's swap stream at the global swap phase.  The lattice, rung
    map and energies are carried between rounds in the output buffers.
    Diagnostics are written per round; int32 stands in for bool on the
    accept/attempt planes.
    """
    r = lat_ref.shape[0]
    g = r if kind.whole_block else 1
    geometry = _geometry(kind, lat_ref.shape)
    kw = (kw_ref[0, 0], kw_ref[0, 1])
    sk = prng.stream_key(kw)
    t0, ph0 = t0_ref[0, 0], ph0_ref[0, 0]
    betas = beta_ref[...]
    betas_row = _kx.to_row(betas)  # once: each slot's lookup is then O(R)
    rung_out_ref[...] = rung_ref[...]
    energy_out_ref[...] = energy_ref[...]
    nacc_ref[...] = jnp.zeros((r, 1, 1), jnp.int32)

    for k in range(n_rounds):  # static unroll: one exchange per round
        src = lat_ref if k == 0 else out_ref
        t_round = t0 + jnp.uint32(k * n_sweeps)

        def group(gi, rows, src=src, t_round=t_round):
            beta = _kx.gather_row(betas_row, rung_out_ref[rows])
            rep = prng.iota_u32((g, 1, 1), 0) + _u32(gi * g)
            out_ref[rows], de, na = _fused_sweeps(
                kind, src[rows], beta, rep, sk, t_round, n_sweeps, geometry
            )
            # as engine.driver does: the interval's ΔE is summed over its
            # sweeps first, then added onto the running per-slot energy
            energy_out_ref[rows] = energy_out_ref[rows] + de
            nacc_ref[rows] = nacc_ref[rows] + na

        _for_each_group(r, g, group)
        rung, acc, prob, att, _ = _kx.exchange_columns(
            rung_out_ref[...], energy_out_ref[...], betas,
            ph0 + jnp.int32(k), kw, pairing=pairing, criterion=criterion,
        )
        rung_out_ref[...] = rung
        acc_ref[k] = acc.astype(jnp.int32)
        prob_ref[k] = prob
        att_ref[k] = att.astype(jnp.int32)


def round_call(kind: Kind, lattice, key_words, t0, phase0, rung, energy,
               betas, *, n_sweeps: int, n_rounds: int, criterion: str,
               pairing: str, interpret: bool, name: str | None = None):
    """``n_rounds`` × (``n_sweeps`` sweeps + exchange) in one launch.

    The exchange couples every replica, so the whole ladder is one grid
    step.  ``phase0`` is the (1,) int32 swap phase at entry, ``rung`` the
    (R,) slot→rung map, ``energy`` the (R,) per-slot energies and ``betas``
    the (R,) rung-ordered ladder.  The lattice is split and merged for a
    ``colour_split`` kind, as in `fused_call`, and ``name`` names the
    kernel's operation.  Returns ``(lattice', rung', energy', n_accepted,
    accept, prob, attempt)`` with (R,) rows and (n_rounds, R) diagnostics
    (accept/attempt as int32 0/1).
    """
    if kind.colour_split:
        lattice = split_rows(lattice)
    r = lattice.shape[0]
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    col, diag = full((r, 1, 1)), full((n_rounds, r, 1, 1))
    outs = pl.pallas_call(
        functools.partial(
            _round_kernel, kind=kind, n_sweeps=n_sweeps, n_rounds=n_rounds,
            criterion=criterion, pairing=pairing,
        ),
        grid=(1,),
        in_specs=[
            full(lattice.shape), col, _smem(), _smem(), _smem(), col, col,
        ],
        out_specs=[full(lattice.shape), col, col, col, diag, diag, diag],
        out_shape=[
            jax.ShapeDtypeStruct(lattice.shape, jnp.int8),
            jax.ShapeDtypeStruct((r, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_rounds, r, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_rounds, r, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_rounds, r, 1, 1), jnp.int32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=name,
    )(lattice, _col(betas), _row(key_words), _row(t0), _row(phase0),
      _col(rung), _col(energy))
    return (
        merge_rows(outs[0]) if kind.colour_split else outs[0],
        *(x.reshape(r) for x in outs[1:4]),
        *(x.reshape(n_rounds, r) for x in outs[4:]),
    )
