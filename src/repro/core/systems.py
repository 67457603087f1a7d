"""The ``System`` interface: what a model must expose to be PT-sampled.

A *system* is the object being simulated (the paper's: a 2-D Ising model).
MH/PT is generic over systems — the paper notes its implementation "allows
inserting and running another model" as future work; here that generality is
first-class.

All methods are written for a **single replica** and are `vmap`-ed by the PT
driver over the replica axis (the paper's replica-level parallelism).  The
state may be any pytree.

Two registries live here (DESIGN.md §API, §Validate):

* `CONSTRUCTORS` — the **constructor registry**: every in-tree system is
  nameable (``make_system("ising", {"length": 32})``) and carries a
  **named-observable registry** (``named_observables("ising", sys,
  ["absmag"])``), so a run description can reference systems and observables
  by string instead of un-serializable lambdas.  This is what
  `repro.api.SystemSpec` resolves through.
* `REGISTRY` — the validation **system zoo**: one small exact-answerable
  instance per implemented system, with the observables and engine settings
  the statistical conformance suite (`tests/test_conformance.py`, backed by
  `repro.validate`) runs against ground truth.  Zoo entries are declared by
  constructor params + observable names, so each entry compiles to a
  `repro.api.RunSpec`.  Register new systems in both and they are
  conformance-tested automatically.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Protocol, Sequence, runtime_checkable

import jax

State = Any  # pytree


@runtime_checkable
class System(Protocol):
    """Protocol for MH/PT-sampleable systems."""

    def init_state(self, key: jax.Array) -> State:
        """Random initial state for one replica."""
        ...

    def energy(self, state: State) -> jax.Array:
        """Scalar energy E(state); the target density is exp(-beta * E)."""
        ...

    def mcmc_step(self, key: jax.Array, state: State, beta: jax.Array):
        """One MH iteration at inverse temperature ``beta``.

        Returns ``(new_state, delta_e, n_accepted)`` where ``delta_e`` is the
        exact energy change (so the driver can track energies incrementally —
        device-resident, no O(L^2) recomputation per iteration) and
        ``n_accepted`` counts accepted proposals (for diagnostics).
        """
        ...


# A system may also hold *per-chain quenched data* (a spin glass's bonds):
# it then defines ``init_quenched(chain) -> pytree``, built from the chain
# index, and takes that pytree as a last argument of ``energy(state, q)``
# and ``mcmc_step(key, state, beta, q)``.  The engine builds it once per
# chain at init and passes it un-batched over replicas.


def has_quenched(system) -> bool:
    """Whether ``system`` (an instance or a class) holds per-chain quenched
    data."""
    return callable(getattr(system, "init_quenched", None))


def batched_init(system: System, key: jax.Array, n_replicas: int) -> State:
    """Initialize ``n_replicas`` independent replica states.

    Systems may provide a natively-batched `init_state_batched` fast path
    (e.g. the PT-LM system, whose states are token matrices); otherwise the
    per-replica `init_state` is vmapped.
    """
    fast = getattr(system, "init_state_batched", None)
    if fast is not None:
        return fast(key, n_replicas)
    keys = jax.random.split(key, n_replicas)
    return jax.vmap(system.init_state)(keys)


def batched_energy(system: System, states: State, quenched=None) -> jax.Array:
    if quenched is not None:
        return jax.vmap(system.energy, in_axes=(0, None))(states, quenched)
    fast = getattr(system, "batched_energy", None)
    if fast is not None:
        return fast(states)
    return jax.vmap(system.energy)(states)


# -- constructor + named-observable registry -----------------------------------


@dataclasses.dataclass(frozen=True)
class SystemEntry:
    """One nameable system family: constructor + named observables.

    Attributes:
      name: registry key (the `repro.api.SystemSpec.name` namespace).
      build: constructor called as ``build(**params)``; params must stay
        JSON-representable (numbers, strings, bools, tuples) so a
        `SystemSpec` round-trips losslessly.
      observables: observable name -> factory ``(system) -> per-replica fn``.
        The factory closes over instance attributes (e.g. the Potts ``q``),
        which is exactly what a bare lambda in an example used to do — but
        here the closure is *reconstructible from the name*, so run
        descriptions serialize.
    """

    name: str
    build: Callable[..., Any]
    observables: Mapping[str, Callable[[Any], Callable]]


CONSTRUCTORS: dict[str, SystemEntry] = {}


def register_constructor(
    name: str,
    build: Callable[..., Any],
    observables: Mapping[str, Callable[[Any], Callable]] | None = None,
) -> SystemEntry:
    if name in CONSTRUCTORS:
        raise ValueError(f"system constructor {name!r} already registered")
    entry = SystemEntry(name=name, build=build, observables=dict(observables or {}))
    CONSTRUCTORS[name] = entry
    return entry


def make_system(name: str, params: Mapping[str, Any] | None = None):
    """Instantiate a registered system family from JSON-able params."""
    if name not in CONSTRUCTORS:
        raise KeyError(
            f"unknown system {name!r}; registered: {sorted(CONSTRUCTORS)}"
        )
    return CONSTRUCTORS[name].build(**dict(params or {}))


def named_observables(
    name: str, system: Any, names: "Sequence[str]"
) -> dict[str, Callable]:
    """Resolve observable names to per-replica functions for ``system``."""
    avail = CONSTRUCTORS[name].observables
    out = {}
    for obs in names:
        if obs not in avail:
            raise KeyError(
                f"system {name!r} has no observable {obs!r}; "
                f"registered: {sorted(avail)}"
            )
        out[obs] = avail[obs](system)
    return out


# -- validation system zoo -----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RegisteredSystem:
    """One system-zoo entry: a small instance with an exact ground truth.

    The conformance suite runs the chunked engine (adaptive ladder on,
    ensemble axis on) on ``make()`` and checks every registered observable
    against exact enumeration / analytic values within MCSE-derived
    tolerances (`repro.validate.conformance`).

    Entries are *declarative*: the instance is named by ``params`` through
    the constructor registry and observables by ``observable_names`` through
    the named-observable registry, so every entry compiles to a serializable
    `repro.api.RunSpec` (`repro.validate.conformance.entry_runspec`).

    Attributes:
      name: registry key; `repro.validate.conformance.EXACT` maps it to the
        matching exact-reference function, and `CONSTRUCTORS` to the builder.
      params: constructor params of the validation-scale instance.
      observable_names: named observables the conformance gate checks.
      temps: initial ladder, cold->hot (the adaptive run retunes the
        interior; exact references are evaluated at the *final* ladder).
      swap_interval / n_chains / chunk_intervals: engine settings.
      burn_sweeps: adaptation + equilibration sweeps discarded before
        measurement (sized so `adapt_rounds` retunes all fire here).
      n_batches / sweeps_per_batch: batch-means measurement schedule.
      adapt_rounds: AdaptConfig.max_rounds for the validation run.
      slow: exact reference costs > ~10 s -> conformance case runs in the
        `slow` test tier, keeping tier-1 latency flat.
    """

    name: str
    params: Mapping[str, Any]
    observable_names: tuple
    temps: tuple
    swap_interval: int = 2
    n_chains: int = 2
    chunk_intervals: int = 25
    burn_sweeps: int = 1200
    n_batches: int = 8
    sweeps_per_batch: int = 400
    adapt_rounds: int = 2
    slow: bool = False

    def make(self) -> Any:
        """The validation-scale system instance (via the constructor registry)."""
        return make_system(self.name, self.params)

    def observables(self, system: Any) -> dict[str, Callable]:
        """Resolved per-replica observable fns for ``system``."""
        return named_observables(self.name, system, self.observable_names)


REGISTRY: dict[str, RegisteredSystem] = {}


def register(entry: RegisteredSystem) -> RegisteredSystem:
    if entry.name in REGISTRY:
        raise ValueError(f"system {entry.name!r} already registered")
    REGISTRY[entry.name] = entry
    return entry


def _register_zoo():
    """Populate the constructor registry and the default zoo.

    System imports live inside this function (not at module top level)
    because system modules import *this* module for the `System` protocol —
    top-level imports here would be a cycle waiting to happen.
    """
    import jax.numpy as jnp

    from repro.core.gaussian import GaussianMixture
    from repro.core.hp import HPChain, radius_of_gyration_sq
    from repro.core.ising import IsingSystem, magnetization
    from repro.core.potts import PottsSystem, potts_magnetization
    from repro.core.spin_glass import EAGlass, EASpinGlass

    register_constructor(
        "ising",
        IsingSystem,
        observables={
            "mag": lambda s: magnetization,
            "absmag": lambda s: (lambda x: jnp.abs(magnetization(x))),
            "energy_per_site": lambda s: (
                lambda x: s.energy(x) / (s.length * s.length)
            ),
        },
    )
    register_constructor(
        "gaussian",
        GaussianMixture,
        observables={
            "x": lambda s: (lambda x: x),
            "absx": lambda s: jnp.abs,
        },
    )
    register_constructor(
        "potts",
        PottsSystem,
        observables={
            "pmag": lambda s: (lambda x: potts_magnetization(x, s.q)),
        },
    )
    register_constructor(
        "ea_spin_glass",
        EASpinGlass,
        observables={
            "absmag": lambda s: (
                lambda x: jnp.abs(jnp.mean(x["spins"].astype(jnp.float32)))
            ),
        },
    )
    register_constructor(
        "ea_glass",
        EAGlass,
        observables={
            "absmag": lambda s: (
                lambda x: jnp.abs(jnp.mean(x.astype(jnp.float32)))
            ),
        },
    )
    register_constructor(
        "hp_protein",
        HPChain,
        observables={
            "rg2": lambda s: radius_of_gyration_sq,
        },
    )

    # Glauber per-site acceptance everywhere checkerboard updates run:
    # strictly stochastic flips keep the simultaneous update aperiodic on
    # the tiny validation lattices (see repro.kernels.ref.accept_prob).
    register(RegisteredSystem(
        name="ising",
        params={"length": 4, "accept_rule": "glauber"},
        observable_names=("absmag",),
        temps=(1.5, 2.0, 2.6, 3.4, 4.4),
    ))
    register(RegisteredSystem(
        name="gaussian",
        params={"mus": (-3.0, 3.0), "sigmas": (0.8, 0.8),
                "weights": (0.5, 0.5), "step_size": 1.0},
        observable_names=("absx",),
        temps=(1.0, 1.8, 3.2, 5.6, 10.0),
    ))
    register(RegisteredSystem(
        name="potts",
        params={"shape": (4, 4), "q": 3, "accept_rule": "glauber",
                "use_pallas": True},
        observable_names=("pmag",),
        temps=(0.7, 1.0, 1.4, 2.0, 2.9),
        slow=True,  # exact reference enumerates 3^16 ~ 43M states (~20 s)
    ))
    register(RegisteredSystem(
        name="ea_spin_glass",
        params={"shape": (4, 4), "disorder_seed": 1, "accept_rule": "glauber"},
        observable_names=("absmag",),
        temps=(0.8, 1.2, 1.8, 2.7, 4.0),
    ))
    # copies == n_chains: every chain holds sample 0, the one the exact
    # reference enumerates (repro.validate.exact.ea_glass_exact)
    register(RegisteredSystem(
        name="ea_glass",
        params={"shape": (2, 2, 4), "disorder_seed": 1, "copies": 2,
                "accept_rule": "glauber"},
        observable_names=("absmag",),
        temps=(0.8, 1.2, 1.8, 2.7, 4.0),
    ))
    register(RegisteredSystem(
        name="hp_protein",
        params={"sequence": "HPHPPHHPHH"},
        observable_names=("rg2",),
        temps=(0.6, 0.9, 1.4, 2.2, 3.4),
        # chain moves are sequential fori_loop iterations — keep the
        # measurement window lighter than the lattice systems'
        sweeps_per_batch=300,
        burn_sweeps=900,
    ))


_register_zoo()
