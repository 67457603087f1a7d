"""Chunked streaming PT driver: AOT mega-steps, online stats, ensembles.

The seed driver (`repro.core.pt._run_jit`) compiled one XLA program per
``n_sweeps`` value and materialized the whole O(intervals x R) trace on
device.  This engine keeps the paper's device-residency insight but
restructures the execution for unbounded runs (DESIGN.md §1):

* **chunked driver** — one "mega-step" (``chunk_intervals`` intervals of
  sweeps + swap phase + stats update) is AOT-lowered once with donated state
  buffers and called from a host loop.  Compile cost is O(1) in run length
  (at most two executables: the steady chunk and a remainder chunk) and the
  state is updated in place on device;
* **streaming statistics** — `repro.engine.stats` accumulators ride inside
  the scan, so a 10k-sweep run carries O(R) diagnostic state instead of an
  O(intervals x R) trace.  The full trace remains available as an opt-in
  (``record_trace=True``) and is streamed to host per chunk, bounding device
  memory by O(chunk_intervals x R);
* **in-loop adaptation** — betas are a *traced* engine input (a leaf of
  `EngineState`, not a static config field), so `repro.engine.adapt` can
  retune the ladder between chunks with zero recompiles;
* **ensemble axis** — the mega-step `vmap`s over ``n_chains`` independent
  chains ``(C, R, ...)``; chain ``c`` draws its PRNG stream from
  ``fold_in(key, c)`` so its results are invariant to the ensemble size;
* **explicit multi-device placement** — `EngineConfig.mesh`
  (`repro.core.distributed.MeshSpec`) runs the mega-step through an explicit
  `shard_map` over a named (``chains`` x ``replicas``) device mesh instead
  of GSPMD constraint hints.  Each device advances its local replica block
  with zero communication (fused kernels run per-shard with global-slot
  counter streams via ``replica_offset``); the exchange step is
  device-resident — only the O(R) energy/rung rows are all-gathered, the
  full-ladder swap decision is recomputed redundantly on every device from
  identical inputs, and temp-mode swaps move *no lattice state*.  That
  redundancy is what keeps the sharded mega-step bit-equal to the
  single-device path at identical seeds.

PRNG streams are identical to the seed driver (keys derive from the state's
global sweep counter), so a fixed-ladder chunked run is bit-equal to the
monolithic `repro.core.pt.run` — chunk boundaries are invisible to the chain.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import config as jax_config
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed as dist_lib
from repro.core.distributed import CHAIN_AXIS, MeshSpec, REPLICA_AXIS
from repro.core.pt import PTState, init_replicas as pt_init_replicas
from repro.core.systems import System, has_quenched
from repro.engine import stats as stats_lib
from repro.engine.adapt import AdaptConfig, AdaptState, maybe_adapt
from repro.exchange import DEO, ExchangeStrategy, make_strategy
from repro.kernels import exchange as kernel_exchange
from repro.kernels import prng as kernel_prng
from repro.obs.timeline import NULL, gc_spans

__all__ = [
    "StepSpec",
    "EngineConfig",
    "EngineState",
    "RunResult",
    "ChunkInfo",
    "AdaptInfo",
    "Engine",
    "make_interval_step",
    "make_sharded_interval_step",
]


# -- interval step: the shared physics core -----------------------------------
#
# This is the single source of truth for "one PT interval" — the monolithic
# compatibility path (`repro.core.pt.run`) and the chunked engine both build
# on it, which is what makes them bit-equal.


@dataclasses.dataclass(frozen=True)
class StepSpec:
    """Hashable static shape of one PT interval (jit-static).

    ``sweeps_per_interval`` sweeps, then one swap phase (if ``do_swap``)
    executed through ``exchange`` — the pluggable replica-exchange strategy
    (`repro.exchange`; the default `DEO` is the paper's even/odd scheme and
    is bit-equal to the pre-strategy swap path).
    """

    n_replicas: int
    sweeps_per_interval: int
    do_swap: bool = True
    criterion: str = "logistic"
    swap_mode: str = "temp"
    exchange: ExchangeStrategy = DEO()

    def __post_init__(self):
        if self.sweeps_per_interval < 1:
            raise ValueError("sweeps_per_interval must be >= 1")
        if self.swap_mode not in ("temp", "state"):
            raise ValueError(f"bad swap_mode {self.swap_mode!r}")
        if self.criterion not in ("logistic", "metropolis"):
            raise ValueError(
                f"unknown criterion {self.criterion!r}; "
                "allowed: ['logistic', 'metropolis']"
            )


def _batched_step(system: System):
    """System step batched over replicas (kernel fast-path if provided)."""
    fn = getattr(system, "batched_mcmc_step", None)
    if fn is not None:
        return fn
    return jax.vmap(system.mcmc_step)


def _replica_step(system: System, keys, states, betas, quenched):
    """One step of every replica; the chain's quenched data, where the
    system holds some, goes to each replica's step un-batched."""
    if quenched is None:
        return _batched_step(system)(keys, states, betas)

    def step(key, state, beta, q):
        # vmap renames the first scope opened inside it ("vmap(pt.replica)"):
        # this one takes that, so the step's own scopes (pt.rng, pt.field)
        # keep their names in the ops' metadata and the profile
        with jax.named_scope("pt.replica"):
            return system.mcmc_step(key, state, beta, q)

    return jax.vmap(step, in_axes=(0, 0, 0, None))(keys, states, betas, quenched)


def _batched_interval(system: System):
    """The fused whole-interval fast path, when selected by the system.

    Systems expose ``batched_mcmc_interval(key, t, states, betas, *,
    n_sweeps)`` — all ``sweeps_per_interval`` sweeps in one kernel launch
    with in-kernel counter-PRNG uniforms (`repro.kernels.prng`).  It is an
    *opt-in* (``use_fused=True``): the fused random stream cannot be
    bit-equal to the per-sweep `jax.random` stream, so the default path must
    stay bit-equal to pre-fused behaviour.  Systems without the method (or
    with fusion off) fall back to the per-sweep scan.
    """
    if not getattr(system, "use_fused", False):
        return None
    return getattr(system, "batched_mcmc_interval", None)


def _round_interval(system: System, spec: StepSpec):
    """The whole-round fused fast path, when selected by the system.

    Systems expose ``batched_mcmc_round(key, t, phase, states, rung, energy,
    betas, *, n_sweeps, criterion, pairing)`` — the interval's sweeps *plus*
    the temp-mode exchange in one kernel launch, with the swap uniforms drawn
    from the counter PRNG's swap stream (`repro.kernels.prng.swap_uniforms`)
    instead of the engine's ``fold_in(key, 2t+1)`` draw.  Opt-in via
    ``use_fused_round=True``; only the kernel-resident subset of the exchange
    zoo is supported — temp-mode DEO/SEO with swaps on (see
    `repro.kernels.exchange` for why the rest stays on the strategy path) —
    and an incompatible spec is a loud error, not a silent fallback.
    """
    if not getattr(system, "use_fused_round", False):
        return None
    fn = getattr(system, "batched_mcmc_round", None)
    if fn is None:
        return None
    pairing = getattr(spec.exchange, "name", None)
    supported = (
        spec.do_swap
        and spec.swap_mode == "temp"
        and pairing in kernel_exchange.PAIRINGS
        and spec.exchange.n_virtual == 1
    )
    if not supported:
        raise ValueError(
            "use_fused_round=True folds the exchange into the kernel and "
            "supports only temp-mode DEO/SEO with swaps on; got "
            f"do_swap={spec.do_swap}, swap_mode={spec.swap_mode!r}, "
            f"exchange={pairing!r} (n_virtual={spec.exchange.n_virtual})"
        )
    return fn


def _sweep_once(system, spec: StepSpec, betas, st: PTState, shard=None) -> PTState:
    """One parallel sweep of every replica at its current temperature."""
    r = spec.n_replicas
    with jax.named_scope("pt.sweep"):
        with jax.named_scope("pt.rng"):
            # 2t/2t+1 split keeps sweep and swap key streams disjoint for any R.
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                jax.random.fold_in(st.key, 2 * st.t),
                jnp.arange(r, dtype=jnp.uint32),
            )
        if shard is not None:
            # pin the per-replica key axis: the per-replica random lattices
            # then generate shard-local (otherwise the partitioner replicates
            # the whole PRNG stream — measured 16x redundant HBM traffic)
            keys = jax.lax.with_sharding_constraint(keys, shard)
        betas_slot = betas[st.rung]
        states, de, _ = _replica_step(system, keys, st.states, betas_slot,
                                      st.quenched)
        return dataclasses.replace(
            st,
            states=states,
            energy=st.energy + de.astype(jnp.float32),
            t=st.t + 1,
        )


def _swap_decision(spec: StepSpec, betas, st: PTState):
    """Propose + accept this iteration's exchanges (no state mutation).

    Returns ``(partner, perm, diagnostics)`` — ``partner`` is the proposed
    pairing involution in rung space, ``perm`` the accepted rung permutation.
    """
    r = spec.n_replicas
    with jax.named_scope("pt.exchange"):
        k_swap = jax.random.fold_in(st.key, 2 * st.t + 1)
        inv = jnp.argsort(st.rung)  # slot holding rung r
        e_rung = st.energy[inv]
        strat = spec.exchange
        partner = strat.propose_pairs(k_swap, st.phase, r)
        # Attempts are the structural pairing mask, NOT `prob > 0`: a badly
        # spaced pair can underflow sigmoid to exactly 0 in f32 and would
        # otherwise never register an attempt — starving the adaptive-ladder
        # feedback in precisely the case it exists to fix.
        perm, accept, prob, attempt = strat.accept(
            k_swap, partner, betas, e_rung, criterion=spec.criterion
        )
    diag = {"swap_accept": accept, "swap_prob": prob, "swap_attempt": attempt}
    return partner, perm, diag


def _apply_swap(spec: StepSpec, st: PTState, perm) -> PTState:
    """Apply an accepted rung permutation and advance the phase counter."""
    r = spec.n_replicas
    with jax.named_scope("pt.exchange"):
        if spec.swap_mode == "temp":
            # Slot inv[r] now holds rung perm[r]; states stay in place.
            inv = jnp.argsort(st.rung)
            new_rung = jnp.zeros((r,), jnp.int32).at[inv].set(perm)
            st = dataclasses.replace(st, rung=new_rung)
        else:
            # Faithful mode: rung == slot identity; move the states themselves.
            states = jax.tree_util.tree_map(
                lambda x: jnp.take(x, perm, axis=0), st.states)
            st = dataclasses.replace(st, states=states, energy=st.energy[perm])
        return dataclasses.replace(st, phase=st.phase + 1)


def _swap_phase(spec: StepSpec, betas, st: PTState):
    """One parallel swap iteration; returns (state, diagnostics)."""
    _, perm, diag = _swap_decision(spec, betas, st)
    return _apply_swap(spec, st, perm), diag


def _observe(system, observables, st: PTState) -> Mapping[str, jax.Array]:
    """Per-rung diagnostics (rung order, cold->hot)."""
    with jax.named_scope("pt.observe"):
        inv = jnp.argsort(st.rung)
        out = {"energy": st.energy[inv]}
        for name, fn in (observables or {}).items():
            vals = jax.vmap(fn)(st.states)
            out[name] = vals[inv]
    return out


def make_interval_step(
    system: System,
    spec: StepSpec,
    observables: Mapping[str, Callable] | None = None,
    shard=None,
):
    """Build ``(PTState, betas) -> (PTState, record)`` for one interval.

    ``record`` holds per-rung arrays: ``energy``, each observable, and
    ``swap_accept``/``swap_prob`` at the lower rung of each attempted pair.
    With a waste-recycling exchange strategy (``spec.exchange.n_virtual >
    1``, e.g. `repro.exchange.VMPT`) the series are recorded *pre-swap* as
    stacked virtual outcomes ``(n_virtual, R)`` alongside an ``est_weight``
    channel — `repro.engine.stats.update_stats` folds them in with West's
    weighted Welford update.
    """
    observables = dict(observables or {})
    recycle = spec.do_swap and spec.exchange.n_virtual > 1
    fused = _batched_interval(system)
    fused_round = _round_interval(system, spec)

    def constrain(st):
        # keep the replica axis sharded through the loop — without this the
        # partitioner may replicate the whole simulation (measured: 256x
        # redundant compute on the production mesh; DESIGN.md §Perf)
        if shard is None:
            return st
        from repro.core.distributed import shard_state

        return shard_state(st, shard)

    def interval_step(st: PTState, betas):
        if fused_round is not None:
            # One launch for the whole PT round: the kernel owns the sweep
            # loop AND the temp-mode exchange (swap uniforms from the counter
            # PRNG's swap stream, keyed on st.phase), so nothing but the
            # post-round state crosses the launch boundary.
            with jax.named_scope("pt.sweep"):
                states, rung, energy, _, acc, prob, att = fused_round(
                    st.key, st.t, st.phase, st.states, st.rung, st.energy,
                    betas, n_sweeps=spec.sweeps_per_interval,
                    criterion=spec.criterion, pairing=spec.exchange.name,
                )
            st = constrain(dataclasses.replace(
                st,
                states=states,
                rung=rung,
                energy=energy,
                t=st.t + spec.sweeps_per_interval,
                phase=st.phase + 1,
            ))
            rec = dict(_observe(system, observables, st))
            # diag rows come back (n_rounds, R); the engine runs one round
            # per interval, so row 0 is the interval's swap diagnostics.
            rec.update({
                "swap_accept": acc[0],
                "swap_prob": prob[0],
                "swap_attempt": att[0],
            })
            return constrain(st), rec
        if fused is not None:
            # One launch for the whole interval: the kernel owns the sweep
            # loop (VMEM-resident states, in-kernel counter PRNG keyed on the
            # same (st.key, st.t) the per-sweep path derives from); the
            # driver just advances the incremental energy and the counter.
            with jax.named_scope("pt.sweep"):
                states, de, _ = fused(
                    st.key, st.t, st.states, betas[st.rung],
                    n_sweeps=spec.sweeps_per_interval,
                )
                st = constrain(dataclasses.replace(
                    st,
                    states=states,
                    energy=st.energy + de.astype(jnp.float32),
                    t=st.t + spec.sweeps_per_interval,
                ))
        else:
            def sweep_body(s, _):
                return constrain(_sweep_once(system, spec, betas, s, shard)), None

            st, _ = jax.lax.scan(
                sweep_body, st, None, length=spec.sweeps_per_interval
            )
        if recycle:
            # Waste recycling: record BOTH virtual outcomes of every
            # attempted exchange (pre-swap values, rung order), weighted by
            # the acceptance probability, then apply the realized swap.
            # The chain law is untouched — only the estimator changes.
            partner, perm, swap_diag = _swap_decision(spec, betas, st)
            weights = spec.exchange.estimator_weights(
                partner, swap_diag["swap_prob"]
            )
            pre = _observe(system, observables, st)
            rec = {k: jnp.stack([v, v[partner]]) for k, v in pre.items()}
            rec["est_weight"] = weights
            st = _apply_swap(spec, st, perm)
        else:
            if spec.do_swap:
                st, swap_diag = _swap_phase(spec, betas, st)
            else:
                z = jnp.zeros((spec.n_replicas,))
                swap_diag = {
                    "swap_accept": z.astype(bool),
                    "swap_prob": z,
                    "swap_attempt": z.astype(bool),
                }
            rec = dict(_observe(system, observables, st))
        rec.update(swap_diag)
        return constrain(st), rec

    return interval_step


# -- sharded interval step: the shard_map per-device body ----------------------


def _observe_full(observables, st_local: PTState, full: PTState):
    """`_observe` on a device's full-row view of a sharded state.

    ``full`` carries the all-gathered (R,) energy/rung rows; per-replica
    observables are evaluated on the *local* lattice block and all-gathered
    as O(R) scalar rows — lattices never cross devices.
    """
    with jax.named_scope("pt.observe"):
        inv = jnp.argsort(full.rung)
        out = {"energy": full.energy[inv]}
        for name, fn in (observables or {}).items():
            vals = jax.lax.all_gather(
                jax.vmap(fn)(st_local.states), REPLICA_AXIS, tiled=True
            )
            out[name] = vals[inv]
    return out


def make_sharded_interval_step(
    system: System,
    spec: StepSpec,
    observables: Mapping[str, Callable] | None = None,
):
    """Per-device interval body for the `shard_map` mega-step.

    Semantics match `make_interval_step` exactly — same record contract,
    same PRNG streams — but expressed per replica shard:

    * **sweeps**: each device advances its contiguous slot block
      ``[off, off + R_local)`` with the *global* slot indices folded into the
      per-replica keys (and ``replica_offset`` into the fused kernels'
      counter PRNG), so local streams are bit-identical to the single-device
      launch;
    * **exchange (device-resident)**: one `all_gather` each of the (R,)
      energy and rung rows — O(R) scalars, the module docstring's
      O(R·L²) → O(R) reduction — then the full-ladder `_swap_decision` is
      recomputed *redundantly* on every device from identical inputs (same
      ``fold_in(key, 2t+1)`` swap key), and each device slices its block of
      the new rung assignment back out.  Temp-mode swaps therefore move no
      lattice state between devices.  DEO/SEO/windowed/VMPT all ride the
      same gathered row, differing only in how they consume it.

    Returns ``step(st_local, betas) -> (st_local, record, rung_full)`` where
    ``record`` holds full (R,) rung-ordered rows (replicated along the
    replica axis) and ``rung_full`` is the post-swap slot->rung map the
    redundant stats update keys on.
    """
    observables = dict(observables or {})
    recycle = spec.do_swap and spec.exchange.n_virtual > 1
    fused = _batched_interval(system)
    fused_round = _round_interval(system, spec)
    r = spec.n_replicas

    def gather(x):
        with jax.named_scope("pt.exchange"):
            return jax.lax.all_gather(x, REPLICA_AXIS, tiled=True)

    def step(st: PTState, betas):
        r_local = st.energy.shape[0]
        start = jax.lax.axis_index(REPLICA_AXIS) * r_local
        offset = start.astype(jnp.uint32)
        if fused is not None:
            with jax.named_scope("pt.sweep"):
                states, de, _ = fused(
                    st.key, st.t, st.states, betas[st.rung],
                    n_sweeps=spec.sweeps_per_interval, replica_offset=offset,
                )
                st = dataclasses.replace(
                    st,
                    states=states,
                    energy=st.energy + de.astype(jnp.float32),
                    t=st.t + spec.sweeps_per_interval,
                )
        else:
            def sweep_body(s, _):
                with jax.named_scope("pt.sweep"):
                    with jax.named_scope("pt.rng"):
                        # global slot ids into fold_in: slot k's stream is
                        # invariant to how the replica axis is carved up
                        keys = jax.vmap(jax.random.fold_in, (None, 0))(
                            jax.random.fold_in(s.key, 2 * s.t),
                            offset + jnp.arange(r_local, dtype=jnp.uint32),
                        )
                    states, de, _ = _replica_step(
                        system, keys, s.states, betas[s.rung], s.quenched)
                    return dataclasses.replace(
                        s,
                        states=states,
                        energy=s.energy + de.astype(jnp.float32),
                        t=s.t + 1,
                    ), None

            st, _ = jax.lax.scan(
                sweep_body, st, None, length=spec.sweeps_per_interval
            )

        # device-resident exchange: gather the O(R) scalar rows, nothing else
        full = dataclasses.replace(
            st, energy=gather(st.energy), rung=gather(st.rung)
        )

        def pull_back(local: PTState, full_after: PTState) -> PTState:
            with jax.named_scope("pt.exchange"):
                new_rung = jax.lax.dynamic_slice_in_dim(
                    full_after.rung, start, r_local
                )
            local = dataclasses.replace(
                local, rung=new_rung, phase=full_after.phase
            )
            if spec.swap_mode == "state":
                # only reachable with a 1-way replica axis (Engine guards):
                # the full rows ARE the local rows, lattices moved locally
                local = dataclasses.replace(
                    local, states=full_after.states, energy=full_after.energy
                )
            return local

        if fused_round is not None:
            # The replica axis cannot be sharded *through* an exchange, so
            # the multi-device analogue of the whole-round kernel is the
            # per-shard fused sweeps above plus this device-resident exchange
            # on the gathered rows — drawn from the SAME counter-PRNG swap
            # stream the round kernel uses (`repro.kernels.exchange`), which
            # keeps a sharded ``use_fused_round`` run bit-equal to the
            # single-device whole-round launch at identical seeds.
            with jax.named_scope("pt.exchange"):
                new_rung, acc, prob, att, _ = kernel_exchange.exchange_step(
                    full.rung, full.energy, betas, st.phase,
                    kernel_prng.key_words(st.key),
                    pairing=spec.exchange.name, criterion=spec.criterion,
                )
            full = dataclasses.replace(
                full, rung=new_rung, phase=full.phase + 1
            )
            st = pull_back(st, full)
            rec = dict(_observe_full(observables, st, full))
            rec.update({
                "swap_accept": acc,
                "swap_prob": prob,
                "swap_attempt": att,
            })
            return st, rec, full.rung
        if recycle:
            partner, perm, swap_diag = _swap_decision(spec, betas, full)
            weights = spec.exchange.estimator_weights(
                partner, swap_diag["swap_prob"]
            )
            pre = _observe_full(observables, st, full)
            rec = {k: jnp.stack([v, v[partner]]) for k, v in pre.items()}
            rec["est_weight"] = weights
            full = _apply_swap(spec, full, perm)
            st = pull_back(st, full)
        else:
            if spec.do_swap:
                _, perm, swap_diag = _swap_decision(spec, betas, full)
                full = _apply_swap(spec, full, perm)
                st = pull_back(st, full)
            else:
                z = jnp.zeros((r,))
                swap_diag = {
                    "swap_accept": z.astype(bool),
                    "swap_prob": z,
                    "swap_attempt": z.astype(bool),
                }
            rec = dict(_observe_full(observables, st, full))
        rec.update(swap_diag)
        return st, rec, full.rung

    return step


# -- engine configuration and state -------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine configuration (ladder *values* live in `EngineState`).

    Attributes:
      n_replicas: |R| rungs per chain.
      swap_interval: sweeps between swap phases (0 disables swaps).
      criterion: "logistic" (paper) | "metropolis".
      swap_mode: "temp" (optimized) | "state" (faithful).
      chunk_intervals: intervals fused into one compiled mega-step — the
        device-memory bound for opt-in trace recording and the host-loop
        cadence for adaptation/checkpointing.
      n_chains: ensemble axis C — independent chains run per launch.
      record_trace: opt-in full per-interval trace, streamed to host each
        chunk (the seed's always-on behaviour).
      track_stats: update the O(R) online statistics inside the mega-step.
      measure_interval: record/stats cadence (sweeps) when swaps are off.
      donate: donate the state buffers to the mega-step (in-place device
        update).  Disable to re-run the same `EngineState` several times,
        e.g. benchmark timing loops.
      exchange: replica-exchange strategy — an `repro.exchange` strategy
        instance, a registered strategy name ("deo"/"seo"/"windowed"/
        "vmpt"), or None for the default `DEO` (the paper's scheme,
        bit-equal to the pre-strategy swap path).
      mesh: `repro.core.distributed.MeshSpec` (or its dict form) selecting
        the explicit shard_map mega-step over an (ensemble x replica) device
        mesh; None (default) keeps the single-device path.  Requires
        ``n_chains % mesh.ensemble == 0``, ``n_replicas % mesh.replica == 0``
        and — with ``mesh.replica > 1`` — ``swap_mode='temp'`` (state-mode
        swaps would move O(R·L²) lattice bytes per exchange).
    """

    n_replicas: int
    swap_interval: int = 100
    criterion: str = "logistic"
    swap_mode: str = "temp"
    chunk_intervals: int = 8
    n_chains: int = 1
    record_trace: bool = False
    track_stats: bool = True
    measure_interval: int = 100
    donate: bool = True
    exchange: Any = None
    mesh: Any = None

    def __post_init__(self):
        if self.chunk_intervals < 1:
            raise ValueError("chunk_intervals must be >= 1")
        if self.n_chains < 1:
            raise ValueError("n_chains must be >= 1")
        # resolve names eagerly so a bad strategy fails at config time, not
        # deep inside the first compiled chunk
        object.__setattr__(self, "exchange", make_strategy(self.exchange))
        # accept the MeshSpec's dict form (dataclasses.asdict round-trips
        # through api.spec flatten nested dataclasses into dicts)
        if isinstance(self.mesh, Mapping):
            object.__setattr__(self, "mesh", MeshSpec(**self.mesh))
        if self.mesh is not None:
            self.mesh.validate(self.n_replicas, self.n_chains)
            if self.mesh.replica > 1 and self.swap_mode != "temp":
                raise ValueError(
                    "swap_mode='state' exchanges O(R*L^2) lattice state and "
                    "is not supported across a sharded replica axis; use "
                    "swap_mode='temp' or mesh.replica=1"
                )

    @property
    def spec(self) -> StepSpec:
        interval = self.swap_interval if self.swap_interval > 0 else self.measure_interval
        return StepSpec(
            n_replicas=self.n_replicas,
            sweeps_per_interval=interval,
            do_swap=self.swap_interval > 0,
            criterion=self.criterion,
            swap_mode=self.swap_mode,
            exchange=self.exchange,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    """Donated device-resident engine state (checkpointable pytree).

    ``pt`` leaves are ``(R, ...)`` for a single chain or ``(C, R, ...)`` with
    the ensemble axis; ``betas`` is the *shared* ladder ``(R,)`` — a traced
    input, so retuning it never recompiles the mega-step.
    """

    pt: Any  # PTState
    stats: Any  # stats_lib.OnlineStats
    betas: jax.Array  # (R,) f32, rung order cold->hot


@dataclasses.dataclass
class RunResult:
    """Host-side outcome of `Engine.run`.

    Attributes:
      summary: `stats.summarize` of the final accumulators (per chain when
        C > 1; see `stats.combine_chains` for the pooled view).  In an
        adaptive run the moment accumulators restart at every retune, so
        ``mean_*``/``var_*`` estimate the *final* ladder only — never a pool
        of samples drawn at different temperatures.
      trace: concatenated per-interval trace (numpy, interval axis first for
        C == 1, chain-first ``(C, T, R)`` otherwise) or None.
      ladder_history: (n_retunes + 1, R) temperatures, initial ladder first.
      n_sweeps: sweeps advanced by this call (per chain).  Less than the
        requested budget when an ``on_chunk`` hook stopped the run early.
      stopped_early: an ``on_chunk`` hook returned truthy — also set when
        the request landed on the final chunk (``n_sweeps`` then equals the
        full budget, but callers must still see the stop to skip later
        work).
    """

    summary: dict[str, np.ndarray]
    trace: dict[str, np.ndarray] | None
    ladder_history: np.ndarray
    n_sweeps: int
    stopped_early: bool = False


@dataclasses.dataclass
class ChunkInfo:
    """Payload handed to the ``on_chunk`` hook after each compiled chunk.

    Attributes:
      index: chunk ordinal within this `Engine.run` call (1-based).
      sweeps_done: sweeps advanced so far in this call (per chain).
      n_sweeps: the call's total sweep budget.
      state: the live `EngineState` after this chunk (device arrays).
      trace: this chunk's streamed per-interval trace (numpy) when
        ``record_trace`` is on, else None — the streaming hook point.
    """

    index: int
    sweeps_done: int
    n_sweeps: int
    state: EngineState
    trace: dict[str, np.ndarray] | None


@dataclasses.dataclass
class AdaptInfo:
    """Payload handed to the ``on_adapt`` hook after a ladder retune.

    Attributes:
      round: cumulative retune count for this engine (1-based).
      temps: the new ladder (R,), cold->hot.
      acceptance: the window feedback signal that drove the retune — per-pair
        acceptance (R-1,) in "acceptance" mode, per-rung flow fraction f(T)
        (R,) in "flow" mode.
      sweeps_done: sweeps advanced in this call when the retune fired.
    """

    round: int
    temps: np.ndarray
    acceptance: np.ndarray
    sweeps_done: int


# -- observability (obs-on runs only; see repro.obs) --------------------------


class _EngineObs:
    """Pre-resolved metric handles for an instrumented engine.

    Built once when an `repro.obs.Observability` is attached (``engine.obs =
    obs``); never constructed on the obs-off path, where the host loop makes
    one ``is None`` test per metric site and allocates nothing.  The spans
    do not live here: the engine always holds a timeline (`Engine.timeline`,
    ``NULL`` with obs off), whose spans reach a running profiler either way.

    Every series derives from what the host loop already has: the host
    clock, compile bookkeeping, and the O(R) pooled counters at the points
    where the host reads them anyway (a retune check, the end of a run).
    Nothing here waits for the device.
    """

    __slots__ = (
        "obs", "compiles", "compile_seconds", "chunks", "sweeps",
        "chunk_seconds", "swap_acc", "flow_up", "adapt_rounds", "checkpoints",
        "degraded_kernel", "_last_counters",
    )

    def __init__(self, obs, config):
        self.obs = obs
        m = obs.metrics
        self.compiles = m.counter(
            "engine_compiles_total", "mega-step AOT compiles")
        self.compile_seconds = m.counter(
            "engine_compile_seconds_total", "wall seconds spent in AOT compile")
        self.chunks = m.counter(
            "engine_chunks_total", "compiled chunks executed")
        self.sweeps = m.counter(
            "engine_sweeps_total", "sweeps advanced (per chain)")
        self.chunk_seconds = m.histogram(
            "engine_chunk_seconds",
            "host seconds between successive chunk dispatch returns (the "
            "host loop's period; the device's time per chunk once the host "
            "runs ahead)")
        self.adapt_rounds = m.counter(
            "engine_adapt_rounds_total", "ladder retunes performed")
        self.checkpoints = m.counter(
            "engine_checkpoints_total", "engine-loop checkpoint saves")
        self.degraded_kernel = m.counter(
            "pt_degraded_kernel",
            "fused/Pallas compile failures degraded to the per-sweep path")
        # live per-rung diagnostics from the O(R) pooled counters the adapt
        # feedback already reads — label children resolved once, not per chunk
        acc = m.gauge("pt_swap_acceptance",
                      "live swap acceptance per rung pair", labels=("pair",))
        flow = m.gauge("pt_flow_up_fraction",
                       "live up-flow fraction f(k) per rung", labels=("rung",))
        self.swap_acc = [acc.labels(str(k)) for k in range(config.n_replicas - 1)]
        self.flow_up = [flow.labels(str(k)) for k in range(config.n_replicas)]
        # window deltas for the acceptance gauges: cumulative counters would
        # smear early-run transients over the whole series
        self._last_counters = None

    def record_rungs(self, counters: dict[str, np.ndarray]) -> None:
        """Refresh the per-rung gauges from the counter deltas since the
        last refresh."""
        last = self._last_counters
        self._last_counters = counters
        if last is not None:
            att = counters["attempts"] - last["attempts"]
            acc = counters["accepts"] - last["accepts"]
        else:
            att, acc = counters["attempts"], counters["accepts"]
        for k, g in enumerate(self.swap_acc):
            if att[k] > 0:
                g.set(acc[k] / att[k])
        lab = counters["labeled"]
        up = counters["up"]
        for k, g in enumerate(self.flow_up):
            if lab[k] > 0:
                g.set(up[k] / lab[k])


# -- the engine ---------------------------------------------------------------


class Engine:
    """AOT-compiled chunked PT driver over a `System`.

    One instance owns the compiled-executable cache; `init` builds fresh
    state, `run` advances it.  The same instance can run many states (e.g.
    checkpoint restarts) as long as shapes match.
    """

    def __init__(
        self,
        system: System,
        config: EngineConfig,
        observables: Mapping[str, Callable] | None = None,
        adapt: AdaptConfig | None = None,
        obs=None,
        faults=None,
        strict_kernels: bool = False,
        on_degrade: Callable[[], Any] | None = None,
    ):
        if adapt is not None and not config.track_stats:
            raise ValueError(
                "adaptive ladders need the online swap counters: "
                "EngineConfig(track_stats=True) is required with adapt"
            )
        if adapt is not None and adapt.mode == "flow" and config.swap_mode != "temp":
            raise ValueError(
                "flow-optimized ladders consume the rung-flow diagnostic, "
                "which only exists in swap_mode='temp' (in 'state' mode "
                "rungs are pinned to slots)"
            )
        self.system = system
        self.config = config
        self.observables = dict(observables or {})
        self.adapt = adapt
        # the concrete device mesh is engine state, not config: MeshSpec is
        # pure shape (serializable through RunSpec), build() binds devices
        self._mesh = None if config.mesh is None else config.mesh.build()
        self._names = ["energy"] + sorted(self.observables)
        self._executables: dict[int, Any] = {}
        # mega-step compiles performed by this engine — the instrumentation
        # the serving layer's compile-amortization contract is asserted
        # against (repro.serve packs N tenants into one engine, so N jobs
        # must show exactly one compile here)
        self.n_compiles = 0
        # retune count for AdaptConfig.max_rounds — per Engine (i.e. per
        # ladder lifetime), not per run() call, so repeated/resumed runs
        # respect the cap cumulatively
        self._adapt_rounds = 0
        # live adaptation window (counter baselines at the last retune) —
        # persists across run() calls so the feedback window spans chunk and
        # phase boundaries, and is exported/restored through checkpoint meta
        # (repro.api.session) so a resumed run is bit-equal to an
        # uninterrupted one even mid-adapt-phase
        self._adapt_state: AdaptState | None = None
        # float64 ladder behind the f32 betas in the state: f32(1/T) is not
        # exactly invertible, so re-deriving temps from betas at run() entry
        # would feed a retune ulp-different inputs than the uninterrupted
        # host loop saw — track the authoritative f64 temps here instead
        # (restored from checkpoint meta on resume)
        self._temps: np.ndarray | None = None
        # observability handle (repro.obs.Observability) — None keeps every
        # metric site down to a single `is None` test (the zero-overhead-off
        # contract pinned by tests/test_obs.py).  The timeline is always
        # there: NULL with obs off, whose spans reach only a running profiler
        self._eobs: _EngineObs | None = None
        self.timeline = NULL
        if obs is not None:
            self.obs = obs
        # fault-injection handle (repro.resilience.FaultPlan) — same
        # zero-cost-off contract as obs: None in production, one `is None`
        # test per host-loop site, never traced into the mega-step
        self._faults = faults
        # kernel degradation policy: a failed fused/Pallas compile falls
        # back to the per-sweep path unless strict_kernels demands the
        # compile error propagate (repro run --strict-kernels)
        self.strict_kernels = strict_kernels
        self._on_degrade = on_degrade
        self._degraded = False

    @property
    def obs(self):
        """The attached `repro.obs.Observability`, or None (obs off)."""
        return self._eobs.obs if self._eobs is not None else None

    @obs.setter
    def obs(self, value):
        # metric handles resolve once here, so the host loop's obs-on path
        # is attribute access + float ops — no name lookups per chunk
        self._eobs = (
            None if value is None
            else _EngineObs(value, self.config)
        )
        self.timeline = NULL if value is None else value.timeline

    # -- state construction ----------------------------------------------------
    def _init_single(self, key: jax.Array, quenched=None) -> PTState:
        # one chain = seed init verbatim (keeps pt-vs-engine bit-equality)
        return pt_init_replicas(self.system, self.config.n_replicas, key,
                                quenched=quenched)

    def _quenched(self, chains):
        """The quenched data of the chains with indices ``chains`` (stacked
        on a leading axis), or of chain 0 alone where ``chains`` is None;
        None for a system that holds none."""
        if not has_quenched(self.system):
            return None
        with self.timeline.span("repro.engine.quenched"):
            if chains is None:
                return self.system.init_quenched(0)
            return jax.vmap(self.system.init_quenched)(chains)

    def init(self, key: jax.Array, temps) -> EngineState:
        """Fresh engine state on the given temperature ladder.

        With an ensemble, chain ``c`` is seeded from ``fold_in(key, c)`` —
        independent of ``n_chains``, so growing the ensemble never perturbs
        existing chains.  A system's per-chain quenched data is built here,
        once, from each chain's index (`PTState.quenched`).
        """
        temps = np.asarray(temps, np.float64)
        if temps.shape != (self.config.n_replicas,):
            raise ValueError(
                f"ladder shape {temps.shape} != (n_replicas={self.config.n_replicas},)"
            )
        self._temps = temps.copy()
        # a fresh state restarts the swap counters at zero — stale window
        # baselines from a previous state would starve the feedback loop
        self._adapt_state = None
        return self.place(self._fresh_state(key, temps))

    def init_ensemble(self, keys: Sequence[jax.Array], temps) -> EngineState:
        """Fresh state where chain ``c`` is seeded from ``keys[c]`` verbatim.

        This is the packing hook for `repro.serve`: a multi-tenant bucket
        hands each chain slot the exact key a *solo* ``n_chains=1`` run would
        start from (``jax.random.key(seed)``), so every packed chain's
        trajectory is bit-equal to running its spec alone.  The per-chain
        states are built one at a time and stacked — bit-equality with the
        solo `init` holds by construction, not by a vmap-equivalence
        argument.  ``len(keys)`` must equal ``config.n_chains``.
        """
        if len(keys) != self.config.n_chains:
            raise ValueError(
                f"init_ensemble got {len(keys)} keys != "
                f"n_chains={self.config.n_chains}"
            )
        temps = np.asarray(temps, np.float64)
        if temps.shape != (self.config.n_replicas,):
            raise ValueError(
                f"ladder shape {temps.shape} != (n_replicas={self.config.n_replicas},)"
            )
        self._temps = temps.copy()
        self._adapt_state = None
        c = self.config.n_chains
        quenched = self._quenched(jnp.arange(c, dtype=jnp.uint32))
        per_chain = [
            self._init_single(
                k, None if quenched is None
                else jax.tree_util.tree_map(lambda q: q[i], quenched))
            for i, k in enumerate(keys)
        ]
        if c == 1:
            pt_st = per_chain[0]
        else:
            pt_st = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_chain
            )
        stats = stats_lib.init_stats(
            self.config.n_replicas, self._names, n_chains=0 if c == 1 else c
        )
        betas = jnp.asarray(1.0 / temps, jnp.float32)
        return self.place(EngineState(pt=pt_st, stats=stats, betas=betas))

    def _fresh_state(self, key: jax.Array, temps) -> EngineState:
        """`init` minus placement/host bookkeeping (eval_shape-safe)."""
        c = self.config.n_chains
        if c == 1:
            pt_st = self._init_single(key, self._quenched(None))
        else:
            chains = jnp.arange(c, dtype=jnp.uint32)
            keys = jax.vmap(jax.random.fold_in, (None, 0))(key, chains)
            pt_st = jax.vmap(self._init_single)(keys, self._quenched(chains))
        stats = stats_lib.init_stats(
            self.config.n_replicas, self._names, n_chains=0 if c == 1 else c
        )
        betas = jnp.asarray(1.0 / np.asarray(temps, np.float64), jnp.float32)
        return EngineState(pt=pt_st, stats=stats, betas=betas)

    def place(self, state: EngineState) -> EngineState:
        """Commit the state onto the mesh placement contract (DESIGN.md
        §Distributed); identity without a configured mesh.

        Placement is explicit `jax.device_put` with `NamedSharding`s — not a
        lazy constraint hint — so the AOT-lowered mega-step sees committed
        input shardings and never falls back to partitioner guessing.
        """
        if self._mesh is None:
            return state
        c = self.config.n_chains
        sh = EngineState(
            pt=dist_lib.named_shardings(
                self._mesh, dist_lib.pt_partition_specs(state.pt, c)
            ),
            stats=dist_lib.named_shardings(
                self._mesh, dist_lib.replicated_partition_specs(state.stats, c)
            ),
            betas=NamedSharding(self._mesh, P(None)),
        )
        return jax.device_put(state, sh)

    def reset_stats(self, state: EngineState) -> EngineState:
        """Zero the online accumulators (e.g. after burn-in).

        Flow labels (``direction``) are chain state, not statistics — they
        survive the reset so replicas keep their up/down identity and
        in-progress round trips complete in the new window.
        """
        c = self.config.n_chains
        stats = stats_lib.init_stats(
            self.config.n_replicas, self._names, n_chains=0 if c == 1 else c
        )
        stats = dataclasses.replace(stats, direction=state.stats.direction)
        if self._adapt_state is not None:
            # the swap counters just went back to zero — re-zero the adapt
            # window baselines with them or the window goes negative and the
            # feedback loop starves forever
            self._adapt_state.zero()
        return self.place(dataclasses.replace(state, stats=stats))

    # -- compiled mega-step ----------------------------------------------------
    def _make_mega(self, chunk_len: int, state: EngineState):
        cfg = self.config
        if self._mesh is not None:
            return self._make_mega_sharded(chunk_len, state)
        step = make_interval_step(self.system, cfg.spec, self.observables)

        def mega(pt_st, stats, betas):
            def body(carry, _):
                pt_st, stats = carry
                pt_st, rec = step(pt_st, betas)
                if cfg.track_stats:
                    with jax.named_scope("pt.stats"):
                        stats = stats_lib.update_stats(stats, rec, pt_st.rung)
                return (pt_st, stats), (rec if cfg.record_trace else None)

            (pt_st, stats), trace = jax.lax.scan(
                body, (pt_st, stats), None, length=chunk_len
            )
            return pt_st, stats, trace

        if cfg.n_chains > 1:
            mega = jax.vmap(mega, in_axes=(0, 0, None))
        return mega

    def _make_mega_sharded(self, chunk_len: int, state: EngineState):
        """The chunk program as an explicit `shard_map` over the device mesh.

        The whole chunk scan runs inside one shard_map region, so the only
        cross-device traffic in the compiled program is the per-interval
        O(R) energy/rung/observable all-gathers (`make_sharded_interval_step`)
        — verifiable by `repro.hlo.collectives.parse_collectives` on the
        lowered text.  ``check_vma=False``: replicated outputs (stats, phase,
        t) are *computed* redundantly from identical inputs, which the static
        replication checker cannot prove.
        """
        cfg = self.config
        step = make_sharded_interval_step(self.system, cfg.spec, self.observables)

        def chain_mega(pt_st, stats, betas):
            def body(carry, _):
                pt_st, stats = carry
                pt_st, rec, rung_full = step(pt_st, betas)
                if cfg.track_stats:
                    with jax.named_scope("pt.stats"):
                        stats = stats_lib.update_stats(stats, rec, rung_full)
                return (pt_st, stats), (rec if cfg.record_trace else None)

            (pt_st, stats), trace = jax.lax.scan(
                body, (pt_st, stats), None, length=chunk_len
            )
            return pt_st, stats, trace

        fn = chain_mega
        if cfg.n_chains > 1:
            # local chains only: the ensemble axis is carved by shard_map,
            # vmap batches over this device's C / ensemble chains
            fn = jax.vmap(chain_mega, in_axes=(0, 0, None))

        pt_specs = dist_lib.pt_partition_specs(state.pt, cfg.n_chains)
        stats_specs = dist_lib.replicated_partition_specs(state.stats, cfg.n_chains)
        trace_spec = P(CHAIN_AXIS) if cfg.n_chains > 1 else P()
        return jax.shard_map(
            fn,
            mesh=self._mesh,
            in_specs=(pt_specs, stats_specs, P(None)),
            out_specs=(pt_specs, stats_specs, trace_spec),
            check_vma=False,
        )

    def _compiled(self, state: EngineState, chunk_len: int):
        """AOT executable for a chunk of ``chunk_len`` intervals.

        At most two entries ever exist per run length pattern (steady chunk +
        remainder), so compile cost is O(1) in total sweeps.  State buffers
        are donated: the engine updates in place, betas stay reusable.
        """
        exe = self._executables.get(chunk_len)
        if exe is None:
            eo = self._eobs
            sds = lambda tree: jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None)), tree
            )
            donate = (0, 1) if self.config.donate else ()
            # mega construction stays OUTSIDE the try: unsupported-spec
            # errors (e.g. the fused-round preconditions in _round_interval)
            # are configuration mistakes and must stay loud, never silently
            # degraded
            jitted = jax.jit(self._make_mega(chunk_len, state), donate_argnums=donate)
            with self.timeline.span(
                "repro.engine.compile", cat="compile",
                chunk_intervals=chunk_len, n_replicas=self.config.n_replicas,
                n_chains=self.config.n_chains,
            ):
                t0 = time.perf_counter()
                try:
                    if self._faults is not None:
                        self._faults.fire("engine.compile")
                    lowered = jitted.lower(
                        sds(state.pt), sds(state.stats), sds(state.betas)
                    )
                    # The pt.* scopes live in the program's debug info,
                    # which JAX's persistent cache key leaves out: a cached
                    # executable built from the same program without them
                    # (or with others) would be loaded in its place, and
                    # its ops would carry that program's names.  Keyed with
                    # the metadata, an executable always has its own scopes.
                    with jax_config.compilation_cache_include_metadata_in_key(True):
                        exe = lowered.compile()
                except Exception as err:
                    # the per-sweep recompile nests its own compile span
                    return self._degrade(err, state, chunk_len)
            self._executables[chunk_len] = exe
            self.n_compiles += 1
            if eo is not None:
                eo.compiles.inc()
                eo.compile_seconds.inc(time.perf_counter() - t0)
        return exe

    def _degrade(self, err: Exception, state: EngineState, chunk_len: int):
        """Graceful kernel degradation: recompile on the per-sweep path.

        A fused-round / interval-fused / Pallas compile failure (a backend
        without Mosaic support, a VMEM overflow at an untested shape, an
        injected ``engine.compile`` fault) falls back to the plain per-sweep
        XLA path — statistically identical results (the fused counter-PRNG
        stream was never bit-equal to per-sweep anyway; the degraded run IS
        bit-equal to a never-fused run of the same spec).  ``strict_kernels``
        turns the fallback into a loud error; systems with no kernel flags
        set have nothing to fall back to, so their compile errors always
        propagate (the serve Supervisor treats those as transient).
        """
        flags = [
            f for f in ("use_fused_round", "use_fused", "use_pallas",
                        "pack_bits")
            if getattr(self.system, f, False)
        ]
        if self.strict_kernels or not flags or self._degraded:
            raise err
        self._degraded = True
        warnings.warn(
            f"mega-step compile failed with {', '.join(flags)} enabled "
            f"({err!r}); degrading to the per-sweep path (statistically "
            "identical, not bit-equal to the fused stream).  Pass "
            "strict_kernels to make this fatal.",
            RuntimeWarning,
            stacklevel=3,
        )
        self.system = dataclasses.replace(
            self.system, **{f: False for f in flags}
        )
        self._executables.clear()
        if self._eobs is not None:
            self._eobs.degraded_kernel.inc()
        if self._on_degrade is not None:
            self._on_degrade()
        return self._compiled(state, chunk_len)

    # -- the host loop ---------------------------------------------------------
    def run(
        self,
        state: EngineState,
        n_sweeps: int,
        *,
        checkpoint=None,
        checkpoint_every_chunks: int = 0,
        on_chunk: Callable[[ChunkInfo], Any] | None = None,
        on_adapt: Callable[[AdaptInfo], Any] | None = None,
        keep_trace: bool = True,
    ) -> tuple[EngineState, RunResult]:
        """Advance ``n_sweeps`` sweeps (per chain) through compiled chunks.

        Between chunks the host loop (a) streams the opt-in trace out,
        (b) feeds measured swap acceptance to the ladder feedback when
        ``adapt`` is configured (re-entering the same executable with retuned
        betas), and (c) checkpoints the whole `EngineState` every
        ``checkpoint_every_chunks`` chunks via ``checkpoint`` (a
        `repro.checkpoint.manager.CheckpointManager`).

        ``on_chunk`` / ``on_adapt`` are the host-loop hook points the
        `repro.api.Session` callback pipeline rides on: ``on_chunk(info)``
        fires after every compiled chunk (checkpoint included) and may return
        truthy to stop the run early (``RunResult.stopped_early``);
        ``on_adapt(info)`` fires after each ladder retune.

        ``keep_trace=False`` (with ``record_trace`` on) hands each chunk's
        trace to ``on_chunk`` but does *not* accumulate it for
        ``RunResult.trace`` — host memory stays O(chunk) when a streaming
        consumer (e.g. `repro.api.TraceWriterCallback`) owns the trace.

        ``n_sweeps`` must be a multiple of the interval length
        (``swap_interval``, or ``measure_interval`` when swaps are off).
        """
        spi = self.config.spec.sweeps_per_interval
        if n_sweeps % spi != 0:
            raise ValueError(
                f"n_sweeps={n_sweeps} not a multiple of the interval ({spi} sweeps)"
            )
        n_intervals = n_sweeps // spi
        many = self.config.n_chains > 1
        # commit placement before the first donated call: an externally
        # built/restored state may still live on the default device
        state = self.place(state)
        temps = self._temps
        if temps is None or not np.array_equal(
            np.asarray(state.betas), (1.0 / temps).astype(np.float32)
        ):
            # unknown or different state (e.g. a fresh init on this engine):
            # fall back to inverting the f32 betas
            temps = 1.0 / np.asarray(state.betas, np.float64)
        ladder_history = [temps.astype(np.float32)]
        adapt_st = self._adapt_state
        if adapt_st is None:
            adapt_st = AdaptState.fresh(self.config.n_replicas)
            if self.adapt is not None:
                # First adaptive window of this engine: baselines start at
                # the *current* counters, so a raw restored state doesn't
                # double-count pre-checkpoint attempts.  From then on the
                # window persists across run() calls (baselines move only at
                # retunes / stats resets).
                adapt_st.rebase(self._pooled_counters(state))
        # the retune count carries across run() calls (max_rounds is per
        # ladder lifetime)
        adapt_st.rounds = self._adapt_rounds
        if self.adapt is not None:
            self._adapt_state = adapt_st
        chunks: list[dict[str, np.ndarray]] = []

        done = 0
        chunk_idx = 0
        stopped = False
        eo = self._eobs
        tl = self.timeline
        # the one-shot jax.profiler window, if armed, covers this whole run:
        # it closes once the summary below has read the last chunk's stats
        profiling = eo is not None and eo.obs.start_jax_profile()
        t_dispatched = None  # host clock at the previous dispatch return
        with gc_spans:
            while done < n_intervals:
                this = min(self.config.chunk_intervals, n_intervals - done)
                chunk_idx += 1
                with tl.span("repro.engine.chunk", chunk=chunk_idx,
                             intervals=this):
                    if self._faults is not None:
                        f = self._faults.check("engine.chunk.stall")
                        if f is not None:
                            time.sleep(f.duration)
                        self._faults.fire("engine.chunk.launch")
                    exe = self._compiled(state, this)
                    # no wait for the device here, obs on or off: the host
                    # runs ahead and the trace shows where the device idles
                    with tl.span("repro.engine.dispatch"):
                        pt_st, stats, trace = exe(
                            state.pt, state.stats, state.betas
                        )
                    if eo is not None:
                        t = time.perf_counter()
                        if t_dispatched is not None:
                            eo.chunk_seconds.observe(t - t_dispatched)
                        t_dispatched = t
                    state = EngineState(pt=pt_st, stats=stats, betas=state.betas)
                    if self._faults is not None:
                        f = self._faults.check("engine.energy.nonfinite")
                        if f is not None:
                            # a failing device lane: poison one chain's
                            # energies on host (chains are independent — NaN
                            # never crosses the ensemble axis, so only the
                            # owning tenant is affected)
                            e = np.asarray(state.pt.energy).copy()
                            if e.ndim == 2:
                                e[f.chain % e.shape[0]] = np.nan
                            else:
                                e[:] = np.nan
                            state = self.place(dataclasses.replace(
                                state,
                                pt=dataclasses.replace(
                                    state.pt,
                                    energy=jnp.asarray(e, state.pt.energy.dtype),
                                ),
                            ))
                    done += this
                    if eo is not None:
                        eo.chunks.inc()
                        eo.sweeps.inc(this * spi)
                    chunk_np = None
                    if self.config.record_trace:
                        with tl.span("repro.engine.trace_drain"):
                            chunk_np = {k: np.asarray(v) for k, v in trace.items()}
                        if keep_trace:
                            chunks.append(chunk_np)
                    if self.adapt is not None and done < n_intervals:
                        with tl.span("repro.engine.adapt") as sp:
                            counters = self._pooled_counters(state)
                            if eo is not None and self.config.track_stats:
                                eo.record_rungs(counters)
                            new_temps, acceptance = maybe_adapt(
                                temps, counters, self.adapt, adapt_st
                            )
                            sp.annotate(retuned=new_temps is not None,
                                        round=adapt_st.rounds)
                            if new_temps is not None:
                                temps = np.asarray(new_temps, np.float64)
                                self._temps = temps
                                ladder_history.append(temps.astype(np.float32))
                                self._adapt_rounds = adapt_st.rounds
                                state = self._retuned(state, temps)
                                if eo is not None:
                                    eo.adapt_rounds.inc()
                                if on_adapt is not None:
                                    on_adapt(AdaptInfo(
                                        round=adapt_st.rounds,
                                        temps=temps.astype(np.float32).copy(),
                                        acceptance=np.asarray(acceptance, np.float64),
                                        sweeps_done=done * spi,
                                    ))
                    if (
                        checkpoint is not None
                        and checkpoint_every_chunks > 0
                        and (chunk_idx % checkpoint_every_chunks == 0
                             or done == n_intervals)
                    ):
                        sweep = int(np.asarray(pt_st.t).reshape(-1)[0])
                        # same meta contract as repro.api.CheckpointCallback:
                        # the exact f64 ladder plus the adaptation
                        # bookkeeping, so either checkpoint path resumes
                        # bit-equal
                        meta = {
                            "temps": [float(t) for t in temps],
                            "adapt_rounds": self._adapt_rounds,
                        }
                        if self._adapt_state is not None:
                            meta.update(self._adapt_state.to_meta())
                        with tl.span("repro.engine.checkpoint", sweep=sweep):
                            checkpoint.save(sweep, state, meta=meta)
                        if eo is not None:
                            eo.checkpoints.inc()
                    if on_chunk is not None:
                        info = ChunkInfo(
                            index=chunk_idx,
                            sweeps_done=done * spi,
                            n_sweeps=n_sweeps,
                            state=state,
                            trace=chunk_np,
                        )
                        with tl.span("repro.engine.callbacks"):
                            stop = on_chunk(info)
                        if stop:
                            # a stop request on the final chunk still
                            # counts: the caller (e.g. Session) must see it
                            # to skip later phases
                            stopped = True
                            break
            # reads the last chunk's stats back: the host waits for the device
            with tl.span("repro.engine.summary"):
                summary = stats_lib.summarize(state.stats)

        trace_out = None
        if chunks:
            axis = 1 if many else 0
            trace_out = {
                k: np.concatenate([c[k] for c in chunks], axis=axis)
                for k in chunks[0]
            }
        result = RunResult(
            summary=summary,
            trace=trace_out,
            ladder_history=np.stack(ladder_history),
            n_sweeps=done * spi,
            stopped_early=stopped,
        )
        if eo is not None:
            # the summary has read the last chunk's stats: the counters are
            # on the host already, and the profiler has the run's device work
            if self.config.track_stats:
                eo.record_rungs(self._pooled_counters(state))
            if profiling:
                eo.obs.stop_jax_profile()
        return state, result

    def _retuned(self, state: EngineState, temps: np.ndarray) -> EngineState:
        """State on a retuned ladder, with the moment accumulators restarted.

        Per-rung means/vars must not pool samples drawn at two different
        ladders (swap counters stay — the adapt window is baselined, and
        flow/round-trip labels are chain state).  The weight totals are part
        of the moment state — a stale weight_sum would deflate post-retune
        variances and freeze the weighted (VMPT) mean updates.
        """
        zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
        stats = dataclasses.replace(
            state.stats,
            n_records=zeros(state.stats.n_records),
            weight_sum=zeros(state.stats.weight_sum),
            mean=zeros(state.stats.mean),
            m2=zeros(state.stats.m2),
        )
        return self.place(dataclasses.replace(
            state,
            stats=stats,
            betas=jnp.asarray(1.0 / temps, jnp.float32),
        ))

    def _pooled_counters(self, state: EngineState) -> dict[str, np.ndarray]:
        """Feedback counters pooled over the ensemble axis (host numpy).

        Returns the cumulative per-rung ``attempts``/``accepts`` swap
        counters and ``up``/``labeled`` flow-visit counters the two adapt
        modes consume (`repro.engine.adapt.maybe_adapt`).
        """
        out = {}
        for name, leaf in (
            ("attempts", state.stats.swap_attempts),
            ("accepts", state.stats.swap_accepts),
            ("up", state.stats.up_visits),
            ("labeled", state.stats.labeled_visits),
        ):
            arr = np.asarray(leaf, np.float64)
            out[name] = arr.sum(axis=0) if arr.ndim == 2 else arr
        return out

    # -- checkpoint integration ------------------------------------------------
    def restore(self, checkpoint):
        """Resume the latest engine checkpoint (or None if none exists).

        The shape template is built abstractly (`eval_shape` — no system
        init or energy evaluation runs) and every leaf is overwritten by the
        restored arrays.  Returns ``(EngineState, meta)`` with betas exactly
        as saved (including any mid-run adaptation).
        """
        temps = np.full((self.config.n_replicas,), 1.0, np.float32)
        shapes = jax.eval_shape(
            lambda k: self._fresh_state(k, temps), jax.random.key(0)
        )

        def materialize(s):
            if jax.dtypes.issubdtype(s.dtype, jax.dtypes.prng_key):
                return jnp.broadcast_to(jax.random.key(0), s.shape)
            return jnp.zeros(s.shape, s.dtype)

        template = jax.tree_util.tree_map(materialize, shapes)
        out = checkpoint.restore_latest(template)
        if out is None:
            return None
        state, meta = out
        # checkpoints are mesh-shape independent (gathered numpy on save);
        # re-commit onto THIS engine's placement, whatever mesh wrote them
        return self.place(state), meta
