"""Engine-vs-ground-truth conformance harness (DESIGN.md §Validate).

`run_conformance` compiles one `repro.core.systems.REGISTRY` entry to a
declarative `repro.api.RunSpec` (`entry_runspec`) and executes it through
the *production* sampling path — `repro.api.Session` over the chunked
streaming engine with the adaptive ladder enabled and the ensemble axis on —
then compares every registered observable (plus the energy) at every rung
against the system's exact reference, evaluated at the **final adapted
ladder** (adaptation pins the endpoints but moves interior rungs; exact
answers are a function of temperature, so the reference simply follows).

Schedule per entry (one `ScheduleSpec`):

1. burn-in phase: ``burn_sweeps`` with ``adapt=True`` and
   `AdaptSpec(max_rounds=adapt_rounds)` — all retunes fire here; the run
   *uses* the adaptive machinery rather than bypassing it;
2. measurement phases: ``n_batches`` windows of ``sweeps_per_batch`` sweeps,
   each with ``reset_stats=True`` so the O(R) moment accumulators restart;
   each chain x window Welford mean is one batch mean (`repro.validate.mcse`);
3. verdict: ``z = (grand mean - exact) / MCSE`` per series per rung, plus a
   first-half vs second-half Geweke drift score.  A ladder retune during
   measurement would invalidate the reference and raises instead.

`assert_conforms` is the test-facing gate: |z| <= z_max (default 4 — a
~6e-5 two-sided tail per comparison under normality) with a small absolute
floor guarding saturated observables whose MCSE collapses to ~0.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.api import (
    AdaptSpec,
    Callback,
    EngineSpec,
    ExchangeSpec,
    LadderSpec,
    PhaseSpec,
    RunSpec,
    ScheduleSpec,
    Session,
    SystemSpec,
)
from repro.core.systems import RegisteredSystem
from repro.validate import exact as exact_lib
from repro.validate.mcse import batch_mean_stats, effective_sample_size, geweke_z

__all__ = [
    "EXACT",
    "ConformanceReport",
    "entry_runspec",
    "run_conformance",
    "assert_conforms",
]


# Registry name -> exact-reference function (system, temps) -> {series: (R,)}.
EXACT = {
    "ising": exact_lib.ising_exact,
    "gaussian": exact_lib.gaussian_exact,
    "potts": exact_lib.potts_exact,
    "ea_spin_glass": exact_lib.ea_exact,
    "ea_glass": exact_lib.ea_glass_exact,
    "hp_protein": exact_lib.hp_exact,
}


@dataclasses.dataclass
class ConformanceReport:
    """Outcome of one conformance run (all arrays rung-ordered, cold->hot)."""

    name: str
    temps: np.ndarray  # final adapted ladder (R,)
    n_retunes: int  # ladder retunes that fired during burn-in
    means: dict[str, np.ndarray]  # engine grand means per series (R,)
    mcse: dict[str, np.ndarray]  # batch-means standard errors (R,)
    exact: dict[str, np.ndarray]  # ground truth at `temps` (R,)
    z: dict[str, np.ndarray]  # (means - exact) / mcse (R,)
    ess: dict[str, np.ndarray]  # implied effective sample size (R,)
    geweke: dict[str, np.ndarray]  # first-vs-second-half drift z (R,)
    n_batches: int  # chain x window batch count

    def worst(self) -> tuple[str, float]:
        """(series, max |z|) — the closest-to-failing comparison."""
        name, val = "", 0.0
        for k, zk in self.z.items():
            m = float(np.abs(zk).max())
            if m >= val:
                name, val = k, m
        return name, val


def entry_runspec(
    entry: RegisteredSystem,
    seed: int = 0,
    exchange: str | ExchangeSpec | None = None,
    system_params: dict | None = None,
    mesh=None,
) -> RunSpec:
    """Compile a zoo entry to the declarative `RunSpec` conformance executes.

    One burn-in phase with the ladder feedback on, then ``n_batches``
    measurement phases whose ``reset_stats`` makes each a self-contained
    batch-means window.  The spec is fully serializable — ``python -m repro
    run`` on its JSON form performs the identical simulation.

    ``exchange`` selects the replica-exchange strategy (name or
    `ExchangeSpec`; None = the default "deo") — the gate that makes the
    strategy × system conformance matrix (`tests/test_conformance.py`) a
    one-argument sweep.  ``system_params`` overlays the entry's constructor
    params — how kernel-option variants (e.g. ``use_fused=True``, whose
    random stream is deliberately *not* bit-equal to the per-sweep path)
    join the same matrix without duplicating zoo entries.  ``mesh`` (a
    `repro.core.distributed.MeshSpec`) runs the same conformance simulation
    through the sharded shard_map mega-step — the multi-device entry of the
    matrix.
    """
    if exchange is None:
        exchange = ExchangeSpec()
    elif isinstance(exchange, str):
        exchange = ExchangeSpec(strategy=exchange)
    if entry.n_chains < 2:
        raise ValueError("conformance requires the ensemble axis (n_chains >= 2)")
    phases = [PhaseSpec(name="burn", n_sweeps=entry.burn_sweeps, adapt=True)]
    for b in range(entry.n_batches):
        # adapt stays ON during measurement on purpose: with a well-sized
        # burn all `adapt_rounds` retunes already fired (max_rounds makes
        # further retunes a no-op, bit-identical trajectory), but a too-thin
        # burn lets a leftover retune fire here and trip the frozen-ladder
        # guard in run_conformance instead of silently skewing the reference.
        phases.append(PhaseSpec(
            name=f"batch{b:02d}", n_sweeps=entry.sweeps_per_batch,
            adapt=True, reset_stats=True,
        ))
    return RunSpec(
        system=SystemSpec(
            name=entry.name, params={**entry.params, **(system_params or {})}
        ),
        ladder=LadderSpec(
            kind="custom", n_replicas=len(entry.temps), temps=entry.temps
        ),
        engine=EngineSpec(
            swap_interval=entry.swap_interval,
            chunk_intervals=entry.chunk_intervals,
            n_chains=entry.n_chains,
            mesh=mesh,
        ),
        exchange=exchange,
        adapt=AdaptSpec(
            target=0.3, min_attempts_per_pair=10, max_rounds=entry.adapt_rounds
        ),
        schedule=ScheduleSpec(phases=tuple(phases)),
        observables=entry.observable_names,
        seed=seed,
    )


def run_conformance(
    entry: RegisteredSystem,
    seed: int = 0,
    exact_fn=None,
    exchange=None,
    system_params: dict | None = None,
    mesh=None,
) -> ConformanceReport:
    """Run one zoo entry through the adaptive ensemble Session vs ground truth."""
    if exact_fn is None:
        exact_fn = EXACT[entry.name]
    spec = entry_runspec(
        entry, seed=seed, exchange=exchange, system_params=system_params,
        mesh=mesh,
    )

    # A tiny callback freezes the post-burn ladder so the measurement phases
    # can be audited against it — the callback pipeline replacing what used
    # to be hand-rolled driver code between engine calls.
    frozen: dict[str, np.ndarray] = {}

    class _FreezeLadder(Callback):
        def on_phase_end(self, session, phase, result):
            if phase.name == "burn":
                frozen["betas"] = np.asarray(session.state.betas).copy()

    session = Session(spec, callbacks=[_FreezeLadder()])
    outcome = session.run()
    system = session.system

    # 1. burn-in — equilibration plus every allowed ladder retune.
    burn = outcome.phases["burn"]
    betas_frozen = frozen["betas"]
    temps = 1.0 / betas_frozen.astype(np.float64)

    # 2. measurement — batch means over chain x window cells.
    series = ["energy"] + sorted(entry.observable_names)
    bm = {k: [] for k in series}  # per-window (C, R) means
    pv = {k: [] for k in series}  # per-window (C, R) variances
    for phase in spec.schedule.phases[1:]:
        res = outcome.phases[phase.name]
        for k in series:
            bm[k].append(np.atleast_2d(res.summary[f"mean_{k}"]))
            pv[k].append(np.atleast_2d(res.summary[f"var_{k}"]))
    if not np.array_equal(np.asarray(outcome.state.betas), betas_frozen):
        raise RuntimeError(
            f"{entry.name}: ladder retuned during measurement — increase "
            "burn_sweeps so all adapt_rounds fire before the batches start"
        )

    # 3. verdict vs exact at the adapted ladder.
    exact = {k: np.asarray(v, np.float64) for k, v in exact_fn(system, temps).items()}
    means, mcse, z, ess, geweke = {}, {}, {}, {}, {}
    half = entry.n_batches // 2
    for k in series:
        cells = np.concatenate(bm[k], axis=0)  # (B*C, R)
        grand, se, _ = batch_mean_stats(cells)
        means[k], mcse[k] = grand, se
        z[k] = (grand - exact[k]) / np.maximum(se, 1e-300)
        ess[k] = effective_sample_size(
            np.concatenate(pv[k], axis=0).mean(axis=0), se
        )
        geweke[k] = geweke_z(
            np.concatenate(bm[k][:half], axis=0),
            np.concatenate(bm[k][half:], axis=0),
        )
    return ConformanceReport(
        name=entry.name,
        temps=temps,
        n_retunes=len(burn.ladder_history) - 1,
        means=means,
        mcse=mcse,
        exact=exact,
        z=z,
        ess=ess,
        geweke=geweke,
        n_batches=entry.n_batches * entry.n_chains,
    )


def assert_conforms(
    report: ConformanceReport,
    z_max: float = 4.0,
    geweke_max: float = 4.0,
    atol: float = 2e-3,
) -> None:
    """Raise AssertionError unless every series conforms at every rung.

    ``|mean - exact| <= z_max * MCSE + atol * (1 + |exact|)`` — the absolute
    floor covers saturated observables (e.g. |m| -> 1 at the cold end) whose
    batch means collapse to near-identical values and make raw z unstable.
    The Geweke score guards stationarity of the measurement window itself.
    """
    for k in report.means:
        err = np.abs(report.means[k] - report.exact[k])
        tol = z_max * report.mcse[k] + atol * (1.0 + np.abs(report.exact[k]))
        assert np.all(err <= tol), (
            f"{report.name}/{k}: engine mean disagrees with exact reference\n"
            f"  temps={report.temps.round(4)}\n  mean ={report.means[k]}\n"
            f"  exact={report.exact[k]}\n  mcse ={report.mcse[k]}\n"
            f"  |z|  ={np.abs(report.z[k]).round(2)} (max {z_max})"
        )
        g = np.abs(report.geweke[k])
        assert np.all(g <= geweke_max), (
            f"{report.name}/{k}: Geweke drift |z|={g.round(2)} exceeds "
            f"{geweke_max} — measurement window not stationary"
        )
