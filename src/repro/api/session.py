"""`Session`: compile a `RunSpec` into an `Engine` and execute its schedule.

The Session is the *single execution path* behind every front door (script,
test, benchmark, conformance harness, ``python -m repro``): it resolves the
spec's names through the registries, builds the chunked streaming engine,
runs the phase schedule, and threads a **callback pipeline** through the
engine's host loop — checkpointing, trace streaming, progress logging and
early stopping are composable `Callback`s instead of hardwired driver flags
(DESIGN.md §API).

Determinism contract: a Session run is bit-equal to hand-driving the raw
engine with the same spec fields — `Session.run` does exactly
``Engine.init(key(seed), ladder)`` followed by one ``Engine.run`` per phase,
and callbacks only *observe* device state, they never perturb the PRNG
stream.  ``tests/test_api.py`` pins this with a Session-vs-Engine
final-energy equality check.

Resume contract: `CheckpointCallback` persists ``(spec, EngineState)``;
`Session.from_checkpoint` rebuilds the Session from the saved spec alone,
restores the newest state, and replays the *remaining* sweeps of the
schedule — the sweep counter inside the state locates the run within the
phase schedule, so no extra driver bookkeeping is stored anywhere.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
from typing import Any, Sequence

import jax
import numpy as np

from repro.api.spec import PhaseSpec, RunSpec
from repro.checkpoint.manager import CheckpointManager
from repro.engine import AdaptInfo, ChunkInfo, Engine, EngineState, RunResult
from repro.engine.adapt import AdaptState
from repro.obs.timeline import NULL

__all__ = [
    "Callback",
    "CheckpointCallback",
    "EarlyStopCallback",
    "ObsCallback",
    "ProgressCallback",
    "TraceWriterCallback",
    "Session",
    "SessionResult",
]


# -- the callback pipeline -----------------------------------------------------


class Callback:
    """Observer hooks along a Session run.  Subclass and override.

    ``on_chunk`` may return truthy to stop the whole run early (the engine
    finishes the current chunk, the Session skips the remaining phases and
    marks the result ``stopped_early``).  Callbacks must treat the engine
    state as read-only: they run between compiled chunks on the host and are
    invisible to the PRNG stream only as long as they don't mutate state.

    ``consumes_trace = True`` declares that the callback takes ownership of
    the streamed per-chunk trace (`ChunkInfo.trace`): the Session then tells
    the engine not to also accumulate the chunks for ``RunResult.trace``, so
    host memory stays O(chunk) on arbitrarily long traced runs.
    """

    consumes_trace = False

    def on_phase_start(self, session: "Session", phase: PhaseSpec) -> None:
        pass

    def on_chunk(self, session: "Session", info: ChunkInfo):
        pass

    def on_adapt(self, session: "Session", info: AdaptInfo) -> None:
        pass

    def on_phase_end(
        self, session: "Session", phase: PhaseSpec, result: RunResult
    ) -> None:
        pass

    def on_checkpoint(self, session: "Session", step: int) -> None:
        pass


class ProgressCallback(Callback):
    """Phase/chunk progress lines on stderr (rate-limited by ``every``)."""

    def __init__(self, every: int = 1, stream=None):
        self.every = max(1, every)
        self.stream = stream if stream is not None else sys.stderr

    def on_phase_start(self, session, phase):
        print(
            f"[{phase.name}] {phase.n_sweeps} sweeps"
            + (" (adapt)" if phase.adapt else ""),
            file=self.stream,
        )

    def on_chunk(self, session, info):
        if info.index % self.every == 0 or info.sweeps_done == info.n_sweeps:
            print(
                f"[{session.current_phase.name}] sweep "
                f"{info.sweeps_done}/{info.n_sweeps}",
                file=self.stream,
            )

    def on_adapt(self, session, info):
        print(
            f"[{session.current_phase.name}] ladder retune #{info.round}: "
            f"T = {np.round(info.temps, 3).tolist()}",
            file=self.stream,
        )


class CheckpointCallback(Callback):
    """Periodic ``(spec, EngineState)`` checkpointing via `CheckpointManager`.

    The spec is saved once per directory (`save_spec`), states every
    ``every_chunks`` compiled chunks and at every phase end — so
    `Session.from_checkpoint` can resume from the directory alone.
    """

    def __init__(self, directory_or_manager, every_chunks: int = 1, keep: int = 3):
        if isinstance(directory_or_manager, CheckpointManager):
            self.manager = directory_or_manager
        else:
            self.manager = CheckpointManager(str(directory_or_manager), keep=keep)
        self.every_chunks = max(1, every_chunks)
        self._spec_saved = False
        self._last_sweep: int | None = None

    def _save(self, session, state: EngineState):
        if not self._spec_saved:
            self.manager.save_spec(session.spec.to_json())
            self._spec_saved = True
        sweep = int(np.asarray(state.pt.t).reshape(-1)[0])
        if sweep == self._last_sweep:
            return  # phase end right after an on_chunk save — same state
        self._last_sweep = sweep
        # the AUTHORITATIVE f64 ladder, not 1/f32(betas): f32 inversion is
        # ulp-lossy and would desync a resumed retune from the uninterrupted
        # host loop
        temps = session.engine._temps
        if temps is None:
            temps = 1.0 / np.asarray(state.betas, np.float64)
        # The adaptation bookkeeping rides in the meta so a resumed engine
        # keeps honouring AdaptConfig.max_rounds cumulatively AND re-enters
        # the same feedback window — resume stays bit-equal even mid-phase.
        meta = {"temps": np.asarray(temps, np.float64).tolist(),
                "adapt_rounds": session.engine._adapt_rounds}
        adapt_st = session.engine._adapt_state
        if adapt_st is not None:
            meta.update(adapt_st.to_meta())
        # one name with the engine's own checkpoint path: both save the
        # engine state
        with session.engine.timeline.span("repro.engine.checkpoint",
                                          cat="session", sweep=sweep):
            self.manager.save(sweep, state, meta=meta)
        session.dispatch("on_checkpoint", sweep)

    def on_chunk(self, session, info):
        if info.index % self.every_chunks == 0:
            self._save(session, info.state)

    def on_phase_end(self, session, phase, result):
        self._save(session, session.state)


class EarlyStopCallback(Callback):
    """Stop the run when ``predicate(ChunkInfo) -> truthy``.

    The predicate reads the live engine state (e.g. an online mean crossing
    a threshold) — the streaming replacement for "run long, inspect the
    trace, truncate".
    """

    def __init__(self, predicate):
        self.predicate = predicate

    def on_chunk(self, session, info):
        return self.predicate(info)


class TraceWriterCallback(Callback):
    """Stream the opt-in per-chunk trace to disk as it is produced.

    Requires ``EngineSpec(record_trace=True)``.  Each chunk lands in
    ``<dir>/trace_<phase>_<chunk>.npz`` — and because this callback declares
    ``consumes_trace``, the engine skips accumulating ``RunResult.trace``,
    so host *and* device trace memory stay bounded by one chunk regardless
    of run length.
    """

    consumes_trace = True

    def __init__(self, directory: str):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)

    def on_chunk(self, session, info):
        if info.trace is None:
            return
        path = os.path.join(
            self.directory,
            f"trace_{session.current_phase.name}_{info.index:06d}.npz",
        )
        np.savez(path, **info.trace)


class ObsCallback(Callback):
    """Attach a `repro.obs.Observability` to the run and export its artifacts.

    The composition point between the callback pipeline and the telemetry
    layer (DESIGN.md §Observability): on phase start the bundle is attached
    to the Session's engine (so the engine's and the Session's spans land
    in its timeline, and the host loop feeds its metrics), and after
    *every* phase the timeline/metrics files are (re)written atomically — a
    run that dies mid-schedule still leaves loadable artifacts on disk.

    Args:
      obs: an existing `Observability` to ride on; built fresh when None.
      timeline_path: where `write()` puts the Chrome-trace JSON (skipped
        when None or when the bundle records no timeline).
      metrics_path: where `write()` puts the Prometheus text exposition.
      jax_profile_dir: arm the one-shot ``jax.profiler`` window around the
        first engine chunk (only honoured when ``obs`` is built here).
    """

    def __init__(
        self,
        obs=None,
        timeline_path: str | None = None,
        metrics_path: str | None = None,
        jax_profile_dir: str | None = None,
    ):
        if obs is None:
            from repro.obs import Observability

            obs = Observability.create(
                timeline=timeline_path is not None,
                jax_profile_dir=jax_profile_dir,
            )
        self.obs = obs
        self.timeline_path = timeline_path
        self.metrics_path = metrics_path

    def on_phase_start(self, session, phase):
        if session.engine.obs is not self.obs:
            session.engine.obs = self.obs

    def on_phase_end(self, session, phase, result):
        self.write()

    def write(self) -> dict:
        """Write the requested artifacts (atomic); returns ``{kind: path}``."""
        out = {}
        if self.timeline_path and getattr(self.obs.timeline, "enabled", False):
            out["timeline"] = self.obs.timeline.write(self.timeline_path)
        if self.metrics_path:
            from repro.obs import write_prometheus

            out["metrics"] = write_prometheus(self.obs.metrics, self.metrics_path)
        return out


# -- results -------------------------------------------------------------------


@dataclasses.dataclass
class SessionResult:
    """Outcome of `Session.run`: per-phase results + the final state.

    Attributes:
      spec: the spec that produced this result.
      phases: phase name -> `repro.engine.RunResult`, schedule order
        (phases skipped by an early stop or already completed before a
        resume are absent).
      state: final `EngineState` (live device arrays).
      stopped_early: a callback stopped the run before the schedule ended.
    """

    spec: RunSpec
    phases: dict[str, RunResult]
    state: EngineState
    stopped_early: bool = False

    @property
    def final(self) -> RunResult:
        """The last executed phase's result."""
        return next(reversed(self.phases.values()))

    def final_energies(self) -> np.ndarray:
        """Final per-rung energies, cold->hot (``(R,)`` or ``(C, R)``)."""
        e = np.asarray(self.state.pt.energy)
        rung = np.asarray(self.state.pt.rung)
        if e.ndim == 1:
            return e[np.argsort(rung)]
        return np.stack([ec[np.argsort(rc)] for ec, rc in zip(e, rung)])

    def manifest(self) -> dict:
        """JSON-able result manifest (what the CLI writes next to a run)."""
        phases = {}
        for name, res in self.phases.items():
            phases[name] = {
                "n_sweeps": int(res.n_sweeps),
                "stopped_early": bool(res.stopped_early),
                "ladder_history": np.asarray(res.ladder_history, np.float64).tolist(),
                "summary": {
                    k: np.asarray(v, np.float64).tolist()
                    for k, v in res.summary.items()
                },
            }
        t = np.asarray(self.state.pt.t).reshape(-1)
        return {
            "spec": self.spec.to_dict(),
            "spec_version": self.spec.spec_version,
            "phases": phases,
            "stopped_early": bool(self.stopped_early),
            "final": {
                "sweep": int(t[0]),
                "temps": (1.0 / np.asarray(self.state.betas, np.float64)).tolist(),
                "energy": self.final_energies().tolist(),
            },
        }

    def write_manifest(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.manifest(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)
        return path


# -- the session ---------------------------------------------------------------


class Session:
    """Compiled form of a `RunSpec`: system + engine + schedule + callbacks.

    One Session owns one `Engine` (and therefore one compiled-executable
    cache and one cumulative adapt-round counter).  ``run()`` executes the
    spec's schedule from a fresh ``init`` — or, after `from_checkpoint`,
    from the restored state, replaying only the remaining sweeps.
    """

    def __init__(
        self,
        spec: RunSpec,
        callbacks: Sequence[Callback] = (),
        strict_kernels: bool = False,
    ):
        self.spec = spec
        self.callbacks = list(callbacks)
        # no engine yet, so no timeline: the span reaches a profiler alone
        with NULL.span("repro.session.build", cat="session"):
            self.system = spec.system.build()
            self.temps = spec.ladder.build()
            self.observables = spec.system.observables(
                self.system, spec.observables)
            self._adapt = spec.adapt.build() if spec.adapt is not None else None
            self.engine = Engine(
                self.system,
                spec.engine.build(
                    spec.ladder.n_replicas, exchange=spec.exchange.build()
                ),
                observables=self.observables,
                # Engine.adapt is toggled per phase; constructing with it
                # also validates it against the engine config (track_stats).
                adapt=self._adapt,
                # a failed fused/Pallas compile normally degrades to the
                # per-sweep path with a warning; --strict-kernels makes it
                # fatal
                strict_kernels=strict_kernels,
            )
        self.state: EngineState | None = None
        self.current_phase: PhaseSpec | None = None
        self._restored_sweeps = 0

    # -- callback dispatch -----------------------------------------------------
    def dispatch(self, hook: str, *args):
        """Fan one hook out to every callback; truthy results OR together."""
        stop = False
        for cb in self.callbacks:
            if getattr(cb, hook)(self, *args):
                stop = True
        return stop

    # -- state construction / resume -------------------------------------------
    def init_state(self) -> EngineState:
        """Fresh engine state exactly as the spec describes it."""
        with self.engine.timeline.span("repro.session.init", cat="session"):
            return self.engine.init(jax.random.key(self.spec.seed), self.temps)

    @classmethod
    def from_checkpoint(
        cls,
        directory: str,
        callbacks: Sequence[Callback] = (),
        strict_kernels: bool = False,
    ) -> "Session":
        """Rebuild a Session from ``(spec.json, newest checkpoint)`` alone.

        The returned Session's ``run()`` continues the schedule from the
        restored sweep counter, re-entering the checkpointed adaptation
        window (baselines + retune count ride in the step meta) so the
        resumed trajectory is bit-equal to the uninterrupted one.  Unless a
        `CheckpointCallback` is already among ``callbacks``, one pointing at
        the same directory is appended with the default cadence (pass your
        own to control ``every_chunks``).
        """
        manager = CheckpointManager(directory)
        data = manager.load_spec()
        if data is None:
            raise FileNotFoundError(f"no spec.json in {directory!r}")
        spec = RunSpec.from_json(data)
        session = cls(spec, callbacks=callbacks, strict_kernels=strict_kernels)
        out = session.engine.restore(manager)
        if out is None:
            raise FileNotFoundError(f"no restorable checkpoint in {directory!r}")
        state, meta = out
        session.state = state
        session._restored_sweeps = int(np.asarray(state.pt.t).reshape(-1)[0])
        session.engine._adapt_rounds = int(meta.get("adapt_rounds", 0))
        if "temps" in meta:
            # the exact f64 ladder — f32 betas alone can't reproduce it
            session.engine._temps = np.asarray(meta["temps"], np.float64)
        restored_adapt = AdaptState.from_meta(
            meta, rounds=session.engine._adapt_rounds
        )
        if restored_adapt is not None:
            session.engine._adapt_state = restored_adapt
        if not any(isinstance(cb, CheckpointCallback) for cb in session.callbacks):
            session.callbacks.append(CheckpointCallback(manager))
        return session

    @property
    def remaining_sweeps(self) -> int:
        """Schedule sweeps still to run (0 when a resumed run is complete)."""
        return max(0, self.spec.schedule.total_sweeps - self._restored_sweeps)

    # -- execution -------------------------------------------------------------
    def run(self) -> SessionResult:
        """Execute the schedule (or its remainder, when resumed)."""
        if self.state is None:
            self.state = self.init_state()
        skip = self._restored_sweeps
        self._restored_sweeps = 0
        results: dict[str, RunResult] = {}
        stopped = False
        for phase in self.spec.schedule.phases:
            if skip >= phase.n_sweeps:
                skip -= phase.n_sweeps  # phase fully done before the resume
                continue
            budget = phase.n_sweeps - skip
            fresh_phase = skip == 0
            skip = 0
            self.current_phase = phase
            self.dispatch("on_phase_start", phase)
            # after on_phase_start, which may attach obs to the engine
            with self.engine.timeline.span("repro.session.phase",
                                           cat="session", phase=phase.name) as sp:
                # Resuming mid-phase keeps the checkpointed accumulators: the
                # reset already happened in the original run's phase start.
                if phase.reset_stats and fresh_phase:
                    self.state = self.engine.reset_stats(self.state)
                self.engine.adapt = self._adapt if phase.adapt else None
                self.state, result = self.engine.run(
                    self.state,
                    budget,
                    on_chunk=lambda info: self.dispatch("on_chunk", info),
                    on_adapt=lambda info: self.dispatch("on_adapt", info),
                    # a trace-consuming callback owns the stream: don't also
                    # buffer every chunk for RunResult.trace
                    keep_trace=not any(
                        getattr(cb, "consumes_trace", False)
                        for cb in self.callbacks
                    ),
                )
                sp.annotate(n_sweeps=int(result.n_sweeps),
                            stopped_early=bool(result.stopped_early))
            results[phase.name] = result
            self.dispatch("on_phase_end", phase, result)
            if result.stopped_early:
                stopped = True
                break
        self.current_phase = None
        if not results:
            raise RuntimeError(
                "nothing to run: the checkpointed sweep counter already "
                "covers the whole schedule"
            )
        return SessionResult(
            spec=self.spec, phases=results, state=self.state, stopped_early=stopped
        )
