"""Distributed replica placement: the (ensemble x replica) PT mesh layout.

The paper distributes replicas over OpenMP/CUDA threads (|R|/H replicas per
thread).  Here the same decomposition is a named 2-D device mesh
(`MeshSpec`): the ``chains`` axis holds whole independent chains (the
embarrassingly parallel ensemble layout) and the ``replicas`` axis splits
each chain's rung population into contiguous slot blocks.  Each device
advances its ``R / replica`` replicas between swap iterations with zero
communication; at a swap iteration:

* ``temp`` swap mode: the decision needs only the (R,) energy/rung rows —
  one ``all-gather`` of O(R) *scalars* per exchange, computed redundantly on
  every device, and *no lattice movement* (rung labels permute in place).
  This is the O(R·L²) → O(R) swap-traffic reduction measured by
  `benchmarks.swap_overhead` via `repro.hlo.collectives`.
* ``state`` swap mode (faithful): accepted pairs exchange (L,L) lattices, so
  the explicit shard_map path only supports it with ``replica == 1`` (whole
  rung populations per device); sharding the replica axis requires ``temp``
  mode — the engine raises otherwise instead of silently moving O(R·L²)
  bytes per swap.

The placement contract (consumed by `repro.engine.driver.Engine`):

=====================  =========================  =========================
state leaf             C == 1                     C > 1 (ensemble)
=====================  =========================  =========================
``pt.states`` leaves   P('replicas', ...)         P('chains', 'replicas', ...)
``pt.energy/rung``     P('replicas')              P('chains', 'replicas')
``pt.key/phase/t``     P() (replicated)           P('chains')
``pt.quenched``        P() (replicated)           P('chains')
``stats`` leaves       P(None, ...) (replicated)  P('chains', None, ...)
``betas``              P(None) (replicated)       P(None) (replicated)
=====================  =========================  =========================

O(R) rows (stats, betas, the swap decision) are replicated along the
replica axis and kept identical on every device — which is what makes the
sharded mega-step bit-equal to the single-device path.

Elastic scaling: replicas are independent between swaps, so PT is
*embarrassingly elastic* — `rebalance_state` reshapes the replica population
onto a new ladder size, growing by cloning (with fresh PRNG noise injected
by subsequent sweeps) or shrinking by dropping interior rungs while
preserving the ladder endpoints.

`replica_sharding` / `shard_state` remain as the legacy single-launch GSPMD
constraint-hint path used by the monolithic `repro.core.pt.run` shim; the
chunked engine now places state explicitly through `MeshSpec` instead.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.pt import PTState

__all__ = [
    "CHAIN_AXIS",
    "REPLICA_AXIS",
    "MeshSpec",
    "pt_partition_specs",
    "replicated_partition_specs",
    "named_shardings",
    "replica_sharding",
    "shard_state",
    "rebalance_ladder",
    "rebalance_state",
]

CHAIN_AXIS = "chains"
REPLICA_AXIS = "replicas"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Serializable description of the (ensemble x replica) device mesh.

    ``ensemble`` devices along the ``chains`` axis (whole chains per device)
    times ``replica`` devices along the ``replicas`` axis (contiguous rung
    slot blocks per device).  ``MeshSpec(1, 1)`` still runs the explicit
    shard_map mega-step — on a 1-device mesh — which is what lets tier-1
    pin sharded-vs-plain bit-equality without a multi-device host.
    """

    ensemble: int = 1
    replica: int = 1

    def __post_init__(self):
        if self.ensemble < 1 or self.replica < 1:
            raise ValueError(
                f"mesh axes must be >= 1, got ensemble={self.ensemble} "
                f"replica={self.replica}"
            )

    @property
    def n_devices(self) -> int:
        return self.ensemble * self.replica

    def validate(self, n_replicas: int, n_chains: int) -> None:
        """Check the run shape divides onto this mesh (fail at config time)."""
        if n_replicas % self.replica != 0:
            raise ValueError(
                f"n_replicas={n_replicas} does not divide over the "
                f"{self.replica}-way replica mesh axis"
            )
        if n_chains % self.ensemble != 0:
            raise ValueError(
                f"n_chains={n_chains} does not divide over the "
                f"{self.ensemble}-way ensemble mesh axis"
            )

    def build(self, devices=None) -> Mesh:
        """The concrete `jax.sharding.Mesh` (first ``n_devices`` by default).

        Device order is deterministic (`jax.devices()` order, ensemble-major)
        so the slot -> device assignment — and therefore the all-gather row
        order — is reproducible across processes.
        """
        devices = list(jax.devices()) if devices is None else list(devices)
        if len(devices) < self.n_devices:
            raise ValueError(
                f"mesh {self.ensemble}x{self.replica} needs "
                f"{self.n_devices} devices, only {len(devices)} available "
                "(simulate with XLA_FLAGS=--xla_force_host_platform_device_count=N)"
            )
        arr = np.array(devices[: self.n_devices]).reshape(
            self.ensemble, self.replica
        )
        return Mesh(arr, (CHAIN_AXIS, REPLICA_AXIS))


def _is_spec(x) -> bool:
    return isinstance(x, P)


def pt_partition_specs(state: PTState, n_chains: int) -> PTState:
    """PartitionSpec tree for a `PTState` under the placement contract.

    Replica-population leaves shard their slot axis over ``replicas`` (and
    the leading chain axis over ``chains`` with an ensemble); per-chain
    scalars (key/phase/t) and quenched data replicate along ``replicas``.
    """
    lead = (CHAIN_AXIS,) if n_chains > 1 else ()
    nl = len(lead)

    def rep(x):
        return P(*lead, REPLICA_AXIS, *([None] * (x.ndim - nl - 1)))

    def chain_only(x):
        return P(*lead)

    return PTState(
        states=jax.tree_util.tree_map(rep, state.states),
        energy=rep(state.energy),
        rung=rep(state.rung),
        key=chain_only(state.key),
        phase=chain_only(state.phase),
        t=chain_only(state.t),
        quenched=jax.tree_util.tree_map(chain_only, state.quenched),
    )


def replicated_partition_specs(tree, n_chains: int):
    """Specs for O(R) diagnostic trees (stats): chain-sharded, replica-replicated.

    Every device along the replica axis carries the full (R,) rows and
    updates them redundantly from the all-gathered record — identical values
    by construction, so no reduction is ever needed.
    """
    lead = (CHAIN_AXIS,) if n_chains > 1 else ()
    nl = len(lead)

    def spec(x):
        return P(*lead, *([None] * (x.ndim - nl)))

    return jax.tree_util.tree_map(spec, tree)


def named_shardings(mesh: Mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree (for `jax.device_put`)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=_is_spec
    )


def replica_sharding(mesh: Mesh, axes=None) -> NamedSharding:
    """NamedSharding placing the leading replica axis over the given mesh axes.

    Legacy GSPMD-hint layout (used by the monolithic `repro.core.pt.run`
    path): replicas are embarrassingly parallel between swap iterations, so
    the default shards them over EVERY mesh axis — the paper's "one replica
    per thread" at mesh scale.  The chunked engine uses `MeshSpec` instead."""
    axes = mesh.axis_names if axes is None else axes
    use = tuple(a for a in axes if a in mesh.axis_names)
    return NamedSharding(mesh, P(use if use else None))


def shard_state(state: PTState, shard: NamedSharding) -> PTState:
    """Constrain all (R, ...) leaves of the PT state to the replica sharding."""

    def constrain(x):
        if hasattr(x, "ndim") and x.ndim >= 1:
            return jax.lax.with_sharding_constraint(x, shard)
        return x

    return dataclasses.replace(
        state,
        states=jax.tree_util.tree_map(constrain, state.states),
        energy=constrain(state.energy),
        rung=constrain(state.rung),
    )


def rebalance_ladder(temps: np.ndarray, new_r: int) -> np.ndarray:
    """Resample a ladder to ``new_r`` rungs, preserving endpoints (geometric
    interpolation in log-T)."""
    temps = np.asarray(temps, dtype=np.float64)
    x_old = np.linspace(0.0, 1.0, len(temps))
    x_new = np.linspace(0.0, 1.0, new_r)
    return np.exp(np.interp(x_new, x_old, np.log(temps))).astype(np.float32)


def rebalance_state(state: PTState, new_r: int) -> PTState:
    """Elastically grow/shrink the replica population to ``new_r``.

    Growing tiles existing replicas (their chains decorrelate after a few
    sweeps — each slot gets an independent PRNG stream via fold_in(slot)).
    Shrinking keeps an endpoint-preserving subsample in rung order.
    Rungs are re-assigned to the identity; callers pair this with
    `rebalance_ladder` for the new temperature ladder.
    """
    r_old = state.energy.shape[0]
    if new_r == r_old:
        return state
    if new_r > r_old:
        sel = jnp.arange(new_r, dtype=jnp.int32) % r_old
    else:
        # Endpoint-preserving subsample in rung order.
        pick = np.unique(np.round(np.linspace(0, r_old - 1, new_r)).astype(np.int64))
        while len(pick) < new_r:  # guard duplicates on tiny ladders
            extra = np.setdiff1d(np.arange(r_old), pick)[: new_r - len(pick)]
            pick = np.sort(np.concatenate([pick, extra]))
        inv = jnp.argsort(state.rung)
        sel = inv[jnp.asarray(pick, dtype=jnp.int32)]
    states = jax.tree_util.tree_map(lambda x: jnp.take(x, sel, axis=0), state.states)
    return dataclasses.replace(
        state,
        states=states,
        energy=jnp.take(state.energy, sel),
        rung=jnp.arange(new_r, dtype=jnp.int32),
    )
