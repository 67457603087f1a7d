"""Parallel-Tempering driver: replica-parallel MH with interval-scheduled swaps.

Maps the paper's execution scheme (section 3, Fig. 2) onto JAX:

* replicas advance **in parallel** between swap iterations — here the replica
  axis is a leading array dimension, vectorized over VPU lanes and sharded
  over the device mesh (`repro.core.distributed`);
* computation is scheduled in *intervals*: an inner `lax.scan` of
  ``swap_interval`` sweeps, then one parallel swap phase (`repro.core.swap`);
* the whole simulation — all intervals — is a single jitted `lax.scan`:
  state never leaves device memory (the paper's CUDA device-residency
  insight, DESIGN.md §2).

This module is the **monolithic compatibility shim**: the physics of one
interval lives in `repro.engine.driver.make_interval_step`, shared with the
chunked streaming engine (`repro.engine.Engine`, DESIGN.md §1).  `run` here
keeps the seed API — one jitted program per ``n_sweeps`` and a full
O(intervals x R) trace — which is convenient for tests and short runs but
recompiles per sweep count; long or adaptive runs should use the engine.

Swap modes:

* ``state``  — faithful to the paper: temperature is bound to the replica
  index and accepted pairs exchange their *states* (O(L²) bytes per pair).
* ``temp``   — optimized: accepted pairs exchange *rungs* (temperature
  indices); states stay put and the chain-per-temperature is reconstructed
  from the tracked permutation. O(1) bytes per pair — this is what makes the
  swap phase free on a multi-pod mesh (DESIGN.md §Perf).

Both produce the same extended-ensemble Markov chain law.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.systems import System, batched_energy, batched_init, has_quenched

__all__ = ["PTConfig", "PTState", "init", "init_replicas", "run", "make_run"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PTState:
    """Device-resident simulation state (a pytree; donate-able)."""

    states: Any  # system states, leaves shaped (R, ...)
    energy: jax.Array  # (R,) f32 — tracked incrementally from step deltas
    rung: jax.Array  # (R,) i32 — rung (ladder position) held by each slot
    key: jax.Array  # PRNG key
    phase: jax.Array  # i32 swap-phase alternator (paper Fig. 2)
    t: jax.Array  # i32 sweep counter
    # the chain's quenched data (`repro.core.systems.has_quenched`), with no
    # replica axis; None for systems without it
    quenched: Any = None


@dataclasses.dataclass(frozen=True)
class PTConfig:
    """Static PT configuration.

    Attributes:
      n_replicas: |R|.
      temps: ladder, cold->hot, tuple of float (hashable for jit static use).
      swap_interval: sweeps between swap iterations (0 disables swaps — the
        paper's "without swaps" baseline used for its speed-up figures).
      criterion: "logistic" (paper) | "metropolis".
      swap_mode: "temp" (optimized) | "state" (faithful).
      record_interval: record diagnostics every k-th interval (1 = all).
    """

    n_replicas: int
    temps: tuple
    swap_interval: int = 100
    criterion: str = "logistic"
    swap_mode: str = "temp"
    record_interval: int = 1

    @property
    def betas(self) -> np.ndarray:
        return 1.0 / np.asarray(self.temps, dtype=np.float32)

    def __post_init__(self):
        if len(self.temps) != self.n_replicas:
            raise ValueError(
                f"ladder has {len(self.temps)} rungs != n_replicas={self.n_replicas}"
            )
        if self.swap_mode not in ("temp", "state"):
            raise ValueError(f"bad swap_mode {self.swap_mode!r}")

    def step_spec(self, n_sweeps: int):
        """The engine `StepSpec` + interval count equivalent to this config."""
        from repro.engine.driver import StepSpec

        interval = self.swap_interval if self.swap_interval > 0 else n_sweeps
        spec = StepSpec(
            n_replicas=self.n_replicas,
            sweeps_per_interval=interval,
            do_swap=self.swap_interval > 0,
            criterion=self.criterion,
            swap_mode=self.swap_mode,
        )
        return spec, max(n_sweeps // interval, 1)


def init_replicas(
    system: System, n_replicas: int, key: jax.Array, *, shard=None,
    quenched=None,
) -> PTState:
    """Build the initial PT state (paper's "initialization phase").

    The single source of truth for state construction — the engine
    (`repro.engine.driver`) and the `init` wrapper below both use it, which
    keeps their PRNG streams (and hence trajectories) identical.
    ``quenched`` is the chain's quenched data; a system that holds such
    data gets chain 0's where none is given.
    """
    if quenched is None and has_quenched(system):
        quenched = system.init_quenched(0)
    k_init, k_run = jax.random.split(key)
    states = batched_init(system, k_init, n_replicas)
    if shard is not None:
        states = jax.lax.with_sharding_constraint(states, shard)
    energy = batched_energy(system, states, quenched)
    return PTState(
        states=states,
        energy=energy.astype(jnp.float32),
        rung=jnp.arange(n_replicas, dtype=jnp.int32),
        key=k_run,
        phase=jnp.int32(0),
        t=jnp.int32(0),
        quenched=quenched,
    )


def init(system: System, config: PTConfig, key: jax.Array, *, shard=None) -> PTState:
    """Seed-compatible `init` (see `init_replicas`)."""
    return init_replicas(system, config.n_replicas, key, shard=shard)


@partial(
    jax.jit,
    static_argnames=("system", "config", "n_sweeps", "observables_tuple", "shard"),
)
def _run_jit(system, config, state, n_sweeps, observables_tuple, shard=None):
    from repro.engine.driver import make_interval_step

    spec, n_intervals = config.step_spec(n_sweeps)
    step = make_interval_step(system, spec, dict(observables_tuple), shard)
    betas = jnp.asarray(config.betas)

    def interval_body(st, _):
        return step(st, betas)

    state, trace = jax.lax.scan(interval_body, state, None, length=n_intervals)
    return state, trace


def run(
    system: System,
    config: PTConfig,
    state: PTState,
    n_sweeps: int,
    observables: Mapping[str, Callable] | None = None,
    shard=None,
):
    """Run ``n_sweeps`` sweeps of PT; returns (final_state, trace).

    ``trace`` holds per-interval, per-rung arrays: ``energy``, each observable,
    ``swap_accept``/``swap_prob`` (at the lower rung of each attempted pair).
    The full simulation is one XLA program — no host round-trips (paper §3:
    "all the simulation information is located inside the device").
    ``shard``: optional NamedSharding for the replica axis, enforced through
    the loop (see `repro.core.distributed.replica_sharding`).

    For long, adaptive, or many-chain runs prefer `repro.engine.Engine`: same
    per-interval physics (bit-equal PRNG streams), O(1) compile cost and O(R)
    streaming diagnostics instead of this full trace.
    """
    obs = tuple(sorted((observables or {}).items()))
    return _run_jit(system, config, state, n_sweeps, obs, shard)


def make_run(system: System, config: PTConfig, n_sweeps: int, observables=None,
             shard=None):
    """AOT-compilable closure (used by benchmarks and the dry-run)."""

    def fn(state):
        return run(system, config, state, n_sweeps, observables, shard=shard)

    return fn
