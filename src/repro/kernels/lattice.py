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

    ``load`` widens an int8 (g, H, W) group into the sweep's carry and
    ``store`` narrows it back (``store(carry, g)``); ``step(carry, beta,
    parity, draw)`` is one checkerboard sweep, where ``draw(plane)`` gives
    that plane's (g, H, W) uniforms and ``beta`` is a (g, 1, 1) column; it
    returns ``(carry', delta_e, n_accepted)`` with (g, 1, 1) totals.
    ``whole_block`` makes the whole block one group, for storage that spans
    replicas.
    """

    load: Callable
    step: Callable
    store: Callable
    whole_block: bool = False


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


def _fused_sweeps(kind: Kind, lat, beta, rep, sk, t0, n_sweeps, par):
    """``n_sweeps`` counter-PRNG sweeps of one group: the shared inner loop.

    ``rep`` is the (g, 1, 1) column of global replica counters and ``t0``
    the global sweep counter at entry.  ΔE and acceptances accumulate per
    colour within a sweep, then per sweep, as repeated `ref` application
    does.  Returns ``(int8 lattice, delta_e, n_accepted)``.
    """
    h, w = par.shape

    def sweep(i, carry):
        x, de, na = carry
        w0, w1 = prng.sweep_key(sk[0], sk[1], t0 + _u32(i), rep)
        draw = lambda plane: prng.plane_uniforms(w0, w1, plane, h, w)
        x, ds, dn = kind.step(x, beta, par, draw)
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
    par = parity(*lat_ref.shape[-2:])
    sk = prng.stream_key((kw_ref[0, 0], kw_ref[0, 1]))
    first = _u32(pl.program_id(0) * r_blk) + off_ref[0, 0]
    t0 = t0_ref[0, 0]

    def group(k, rows):
        rep = prng.iota_u32((g, 1, 1), 0) + (first + _u32(k * g))
        out_ref[rows], de_ref[rows], nacc_ref[rows] = _fused_sweeps(
            kind, lat_ref[rows], beta_ref[rows], rep, sk, t0, n_sweeps, par
        )

    _for_each_group(r_blk, g, group)


def fused_call(kind: Kind, lattice, key_words, t0, betas, *, n_sweeps: int,
               replica_offset, r_blk: int, interpret: bool):
    """``n_sweeps`` counter-PRNG sweeps of an (R, H, W) int8 lattice.

    ``key_words`` is the (2,) uint32 run key (`prng.key_words`), ``t0`` the
    (1,) uint32 sweep counter at entry and ``replica_offset`` the (1,)
    uint32 global index of local slot 0.  R must be a multiple of ``r_blk``.
    Returns ``(lattice', delta_e (R,), n_accepted (R,))`` summed over the
    interval.
    """
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
    )(lattice, _col(betas), _row(key_words), _row(t0), _row(replica_offset))
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
    par = parity(*lat_ref.shape[-2:])
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
                kind, src[rows], beta, rep, sk, t_round, n_sweeps, par
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
               pairing: str, interpret: bool):
    """``n_rounds`` × (``n_sweeps`` sweeps + exchange) in one launch.

    The exchange couples every replica, so the whole ladder is one grid
    step.  ``phase0`` is the (1,) int32 swap phase at entry, ``rung`` the
    (R,) slot→rung map, ``energy`` the (R,) per-slot energies and ``betas``
    the (R,) rung-ordered ladder.  Returns ``(lattice', rung', energy',
    n_accepted, accept, prob, attempt)`` with (R,) rows and (n_rounds, R)
    diagnostics (accept/attempt as int32 0/1).
    """
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
    )(lattice, _col(betas), _row(key_words), _row(t0), _row(phase0),
      _col(rung), _col(energy))
    return (
        outs[0],
        *(x.reshape(r) for x in outs[1:4]),
        *(x.reshape(n_rounds, r) for x in outs[4:]),
    )
