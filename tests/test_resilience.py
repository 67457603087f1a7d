"""Fault injection, supervised recovery, graceful degradation (DESIGN.md
§Resilience).

The contracts pinned here:

* **chaos invariant** — under any injected fault schedule, every served job
  either completes with results bit-equal to its fault-free run, or fails
  cleanly with a typed error — and the on-disk checkpoint directories stay
  restorable either way;
* **zero-cost-off** — with ``faults=None`` the `FaultPlan` class is never
  consulted (booby-trapped methods), and the mega-step jaxpr is
  byte-identical with a plan armed or absent;
* **supervision** — transient faults retry with deterministic backoff and
  recover bit-equal from the last intact checkpoint; exhausted retries (or
  a wedged watchdog) quarantine the bucket with a ``quarantine.json``
  manifest while bucket-mates in *other* buckets keep serving;
* **degradation** — a failed fused-kernel compile falls back to the
  per-sweep path (warning + counter), bit-equal to a never-fused run;
  ``strict_kernels`` makes it fatal;
* **lifecycle hygiene** — `Scheduler.shutdown` drains PENDING jobs into a
  typed `SchedulerStopped` failure, and a bounded intake queue rejects with
  `QueueFull` instead of accepting unbounded work.
"""
import dataclasses
import json
import os
import warnings

import jax
import numpy as np
import pytest

from repro.api import (
    EngineSpec,
    LadderSpec,
    PhaseSpec,
    RunSpec,
    ScheduleSpec,
    SystemSpec,
)
from repro.core.ising import IsingSystem
from repro.engine import Engine, EngineConfig
from repro.resilience import (
    SITES,
    BucketQuarantined,
    CompileTimeout,
    Fault,
    FaultPlan,
    InjectedCrash,
    InjectedFault,
    QuantumOutcome,
    RetryPolicy,
    Supervisor,
    WatchdogTimeout,
)
from repro.resilience.supervisor import QUARANTINE_NAME
from repro.serve import (
    JobFailedError,
    JobState,
    QueueFull,
    Scheduler,
    SchedulerStopped,
)


def serve_spec(seed=0, length=4, sweeps=(8, 8)) -> RunSpec:
    phases = [PhaseSpec("burn", sweeps[0])]
    if len(sweeps) > 1:
        phases.append(PhaseSpec("measure", sweeps[1], reset_stats=True))
    return RunSpec(
        system=SystemSpec("ising", {"length": length}),
        ladder=LadderSpec(kind="geometric", n_replicas=4, t_min=1.5, t_max=3.5),
        engine=EngineSpec(swap_interval=2, chunk_intervals=2),
        schedule=ScheduleSpec(phases=tuple(phases)),
        observables=("mag",),
        seed=seed,
    )


def run_serve(faults=None, ckdir=None, n_jobs=3, **kw):
    """One scheduler pass over ``n_jobs`` seed-variant tenants."""
    kw.setdefault("retry_backoff_s", 0.001)
    sched = Scheduler(checkpoint_dir=ckdir, checkpoint_every_quanta=1,
                      faults=faults, **kw)
    handles = [
        sched.submit(serve_spec(seed=s), job_id=f"j{s}") for s in range(n_jobs)
    ]
    sched.run_until_idle()
    return sched, handles


@pytest.fixture(scope="module")
def baseline():
    """Fault-free reference results, one scheduler pass (module-cached)."""
    _, handles = run_serve()
    return {h.id: h.result(timeout=0) for h in handles}


def assert_bit_equal(result, ref):
    assert np.array_equal(
        np.asarray(result.final_energy), np.asarray(ref.final_energy)
    )
    assert set(result.phases) == set(ref.phases)
    for pname in ref.phases:
        for k, v in ref.phases[pname].items():
            assert np.array_equal(
                np.asarray(result.phases[pname][k]), np.asarray(v)
            ), (pname, k)


# -- FaultPlan semantics -------------------------------------------------------


def test_fault_rejects_unknown_site():
    with pytest.raises(ValueError, match="unknown fault site"):
        Fault("engine.warp.core_breach")


def test_fault_plan_counts_occurrences_per_site():
    plan = FaultPlan([Fault("engine.chunk.launch", at=(1,))])
    assert plan.check("engine.chunk.launch") is None      # occurrence 0
    assert plan.check("engine.chunk.launch") is not None  # occurrence 1
    assert plan.check("engine.chunk.launch") is None      # occurrence 2
    assert plan.log == [("engine.chunk.launch", 1)]
    assert plan.fired() == 1
    assert plan.fired("engine.chunk.launch") == 1
    assert plan.fired("serve.callback") == 0


def test_fault_plan_fire_raises_typed():
    plan = FaultPlan([Fault("serve.callback", at=(0,))])
    with pytest.raises(InjectedFault):
        plan.fire("serve.callback")
    plan.fire("serve.callback")  # occurrence 1: disarmed


def test_fault_plan_from_seed_deterministic():
    a = FaultPlan.from_seed(7, n_faults=5)
    b = FaultPlan.from_seed(7, n_faults=5)
    assert a.faults == b.faults
    assert all(f.site in SITES for f in a.faults)
    assert FaultPlan.from_seed(8, n_faults=5).faults != a.faults


def test_fault_plan_on_fire_hook():
    seen = []
    plan = FaultPlan([Fault("engine.chunk.stall", at=(0,))],
                     on_fire=seen.append)
    plan.check("engine.chunk.stall")
    assert [f.site for f in seen] == ["engine.chunk.stall"]


# -- zero-cost-off (the obs-layer structural contract) -------------------------


def test_faults_off_never_consults_the_plan(monkeypatch, tmp_path):
    """With faults=None the FaultPlan class is never touched: booby-trap its
    methods and run the whole stack — engine, checkpoints, serve."""
    def bomb(*a, **k):
        raise AssertionError("faults-off path touched the FaultPlan layer")

    for meth in ("check", "fire", "__init__"):
        monkeypatch.setattr(FaultPlan, meth, bomb)
    sched = Scheduler(checkpoint_dir=str(tmp_path), checkpoint_every_quanta=1)
    h = sched.submit(serve_spec())
    sched.run_until_idle()
    assert h.result(timeout=0).n_sweeps == 16


def test_mega_step_jaxpr_identical_faults_on_and_off():
    temps = np.geomspace(1.5, 3.5, 4)
    cfg = EngineConfig(n_replicas=4, swap_interval=2, chunk_intervals=2)

    def jaxpr(faults):
        eng = Engine(IsingSystem(length=4), cfg, faults=faults)
        st = eng.init(jax.random.key(0), temps)
        return str(jax.make_jaxpr(eng._make_mega(2, st))(
            st.pt, st.stats, st.betas
        ))

    armed = FaultPlan([Fault(s) for s in sorted(SITES)])
    assert jaxpr(None) == jaxpr(armed)


# -- RetryPolicy / Supervisor unit behaviour -----------------------------------


def test_retry_policy_deterministic_and_bounded():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=1.0,
                    jitter=0.25)
    d1 = [p.delay("bucket-a", k) for k in range(1, 6)]
    assert d1 == [p.delay("bucket-a", k) for k in range(1, 6)]  # pure
    assert d1 != [p.delay("bucket-b", k) for k in range(1, 6)]  # decorrelated
    for k, d in enumerate(d1, start=1):
        base = min(1.0, 0.1 * 2 ** (k - 1))
        assert base <= d <= base * 1.25


def test_retry_policy_validates():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)


class _FakeBucket:
    """Host-only bucket stub: fails its quantum ``failures`` times."""

    def __init__(self, failures, error=None, jobs=2):
        self.digest = "fake"
        self.name = "fake-0000"
        self.manager = None
        self.faults = None
        self.finished = False
        self.sweeps_done = 0
        self.restore_fallback_depth = 0
        self._failures = failures
        self._error = error or InjectedFault("boom")
        self._failed = set()
        self.jobs = [_FakeJob(f"f{i}") for i in range(jobs)]
        self.generation = 0

    def live_jobs(self):
        return [j for j in self.jobs if j.id not in self._failed]

    def run_quantum(self, chunks):
        if self._failures > 0:
            self._failures -= 1
            raise self._error
        self.finished = True
        return True

    def recover(self):
        fresh = _FakeBucket(self._failures, self._error)
        fresh.jobs = self.jobs
        fresh._failed = set(self._failed)
        fresh.generation = self.generation + 1
        return fresh

    def abandon(self):
        pass


class _FakeJob:
    def __init__(self, jid):
        self.id = jid
        self.state = JobState.RUNNING
        self.error = None

    def _fail(self, err):
        self.error = err
        self.state = JobState.FAILED


def test_supervisor_retries_then_succeeds():
    sup = Supervisor(policy=RetryPolicy(max_attempts=3, base_delay_s=0.0),
                     sleep=lambda s: None)
    out = sup.run(_FakeBucket(failures=2), 1)
    assert out.finished and not out.quarantined
    assert out.retries == 2
    assert out.bucket.generation == 2  # two recovered generations
    assert len(out.recoveries) == 2
    assert sup.totals["retries"] == 2


def test_supervisor_quarantines_after_max_attempts():
    sup = Supervisor(policy=RetryPolicy(max_attempts=2, base_delay_s=0.0),
                     sleep=lambda s: None)
    bucket = _FakeBucket(failures=99)
    out = sup.run(bucket, 1)
    assert out.quarantined and out.finished
    assert isinstance(out.error, InjectedFault)
    for job in out.bucket.jobs:
        assert job.state is JobState.FAILED
        assert isinstance(job.error, BucketQuarantined)
        assert isinstance(job.error.__cause__, InjectedFault)
    assert sup.totals["quarantined_buckets"] == 1
    assert sup.totals["quarantined_jobs"] == 2


def test_supervisor_wedged_watchdog_quarantines_immediately():
    sup = Supervisor(policy=RetryPolicy(max_attempts=5, base_delay_s=0.0),
                     sleep=lambda s: None)
    err = WatchdogTimeout("stuck", wedged=True)
    out = sup.run(_FakeBucket(failures=99, error=err), 1)
    assert out.quarantined
    assert out.retries == 0  # no retry raced against the stuck thread


def test_supervisor_backoff_uses_injected_sleep():
    slept = []
    sup = Supervisor(policy=RetryPolicy(max_attempts=3, base_delay_s=0.5),
                     sleep=slept.append)
    sup.run(_FakeBucket(failures=1), 1)
    assert len(slept) == 1 and slept[0] >= 0.5


# -- graceful kernel degradation -----------------------------------------------


def _engine_cfg():
    return EngineConfig(n_replicas=4, swap_interval=2, chunk_intervals=2)


def test_compile_failure_degrades_fused_to_per_sweep_bit_equal():
    temps = np.geomspace(1.5, 3.5, 4)
    plan = FaultPlan([Fault("engine.compile", at=(0,))])
    eng = Engine(IsingSystem(length=4, use_fused=True, use_pallas=True),
                 _engine_cfg(), faults=plan)
    with pytest.warns(RuntimeWarning, match="degrading to the per-sweep"):
        st = eng.init(jax.random.key(0), temps)
        st, _ = eng.run(st, 8)
    assert eng._degraded
    assert not eng.system.use_fused and not eng.system.use_pallas

    ref = Engine(IsingSystem(length=4), _engine_cfg())
    st2 = ref.init(jax.random.key(0), temps)
    st2, _ = ref.run(st2, 8)
    assert np.array_equal(np.asarray(st.pt.energy), np.asarray(st2.pt.energy))
    assert np.array_equal(np.asarray(st.pt.states), np.asarray(st2.pt.states))


def test_strict_kernels_makes_compile_failure_fatal():
    plan = FaultPlan([Fault("engine.compile", at=(0,))])
    eng = Engine(IsingSystem(length=4, use_fused=True), _engine_cfg(),
                 faults=plan, strict_kernels=True)
    st = eng.init(jax.random.key(0), np.geomspace(1.5, 3.5, 4))
    with pytest.raises(InjectedFault):
        eng.run(st, 8)


@pytest.mark.parametrize("strict", [False, True])
def test_scheduler_strict_kernels_reaches_its_engines(strict):
    """A Scheduler built with strict_kernels fails a job whose fused
    compile fails, where the default degrades it to the per-sweep path."""
    plan = FaultPlan([Fault("engine.compile", at=(0,))])
    spec = dataclasses.replace(
        serve_spec(sweeps=(8,)),
        system=SystemSpec("ising", {"length": 4, "use_fused": True}),
    )
    sched = Scheduler(max_attempts=1, retry_backoff_s=0.0, faults=plan,
                      strict_kernels=strict)
    job = sched.submit(spec)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        sched.run_until_idle()
    if strict:
        with pytest.raises(JobFailedError):
            sched.result(job, timeout=0)
    else:
        assert sched.result(job, timeout=0).final_energy is not None


def test_plain_system_compile_failure_propagates():
    # nothing to degrade to: the supervisor owns this error class instead
    plan = FaultPlan([Fault("engine.compile", at=(0,))])
    eng = Engine(IsingSystem(length=4), _engine_cfg(), faults=plan)
    st = eng.init(jax.random.key(0), np.geomspace(1.5, 3.5, 4))
    with pytest.raises(InjectedFault):
        eng.run(st, 8)


def test_degraded_kernel_counter_increments():
    from repro.obs import Observability

    obs = Observability.create()
    plan = FaultPlan([Fault("engine.compile", at=(0,))])
    eng = Engine(IsingSystem(length=4, use_fused=True), _engine_cfg(),
                 faults=plan, obs=obs)
    st = eng.init(jax.random.key(0), np.geomspace(1.5, 3.5, 4))
    with pytest.warns(RuntimeWarning):
        eng.run(st, 8)
    snap = obs.metrics.snapshot()
    assert snap["pt_degraded_kernel"]["samples"][0]["value"] == 1.0


# -- supervised serve recovery -------------------------------------------------


def test_transient_faults_recover_bit_equal(tmp_path, baseline):
    plan = FaultPlan([
        Fault("engine.chunk.launch", at=(1, 5)),
        Fault("checkpoint.write.torn", at=(0,)),
    ])
    sched, handles = run_serve(faults=plan, ckdir=str(tmp_path))
    assert plan.fired() >= 2
    assert sched._supervisor.totals["retries"] >= 1
    for h in handles:
        assert_bit_equal(h.result(timeout=0), baseline[h.id])


def test_quarantine_writes_manifest_and_fails_jobs_typed(tmp_path):
    plan = FaultPlan([Fault("engine.chunk.launch", at=tuple(range(64)))])
    sched, handles = run_serve(faults=plan, ckdir=str(tmp_path),
                               max_attempts=2)
    for h in handles:
        with pytest.raises(JobFailedError) as ei:
            h.result(timeout=0)
        assert isinstance(ei.value.__cause__, BucketQuarantined)
    manifests = [
        os.path.join(tmp_path, n, QUARANTINE_NAME)
        for n in os.listdir(tmp_path)
        if os.path.isfile(os.path.join(tmp_path, n, QUARANTINE_NAME))
    ]
    assert len(manifests) == 1
    man = json.load(open(manifests[0]))
    assert man["attempts"] == 2
    assert sorted(man["jobs"]) == ["j0", "j1", "j2"]
    assert man["fired_faults"]  # the schedule that killed it is recorded
    assert sched.stats()["resilience"]["quarantined_jobs"] == 3


def test_nonfinite_energy_fails_only_the_owning_tenant(baseline):
    plan = FaultPlan([Fault("engine.energy.nonfinite", at=(0,), chain=1)])
    _, handles = run_serve(faults=plan)
    by_id = {h.id: h for h in handles}
    assert by_id["j1"].state is JobState.FAILED
    assert isinstance(by_id["j1"].error, FloatingPointError)
    for jid in ("j0", "j2"):
        assert by_id[jid].state is JobState.DONE
        assert_bit_equal(by_id[jid].result(timeout=0), baseline[jid])


def test_callback_fault_is_isolated_per_job(baseline):
    plan = FaultPlan([Fault("serve.callback", at=(1,))])
    _, handles = run_serve(faults=plan)
    failed = [h for h in handles if h.state is JobState.FAILED]
    assert len(failed) == 1
    assert isinstance(failed[0].error, InjectedFault)
    for h in handles:
        if h.state is JobState.DONE:
            assert_bit_equal(h.result(timeout=0), baseline[h.id])


def test_watchdog_recovers_stalled_quantum_bit_equal(tmp_path, baseline):
    plan = FaultPlan([Fault("engine.chunk.stall", at=(1,), duration=10.0)])
    sched, handles = run_serve(faults=plan, ckdir=str(tmp_path),
                               watchdog_s=1.0)
    sched._supervisor.grace_s = 30.0
    assert sched._supervisor.totals["retries"] >= 1
    for h in handles:
        assert_bit_equal(h.result(timeout=0), baseline[h.id])


def test_injected_checkpoint_crash_does_not_kill_the_host_loop(
    tmp_path, baseline
):
    plan = FaultPlan([Fault("checkpoint.write.crash_before_rename", at=(0,)),
                      Fault("checkpoint.write.crash_after_rename", at=(1,))])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        _, handles = run_serve(faults=plan, ckdir=str(tmp_path))
    assert plan.fired() == 2
    for h in handles:
        assert_bit_equal(h.result(timeout=0), baseline[h.id])


def test_resilience_metrics_recorded(tmp_path):
    plan = FaultPlan([Fault("engine.chunk.launch", at=(1,))])
    sched, _ = run_serve(faults=plan, ckdir=str(tmp_path))
    snap = sched.metrics()
    fired = {
        tuple(s["labels"].values()): s["value"]
        for s in snap["pt_fault_injected"]["samples"]
    }
    assert fired[("engine.chunk.launch",)] == 1.0
    assert snap["pt_retries"]["samples"][0]["value"] >= 1.0
    assert snap["pt_quarantined"]["samples"] == [] or (
        snap["pt_quarantined"]["samples"][0]["value"] == 0.0
    )


# -- the chaos suite -----------------------------------------------------------


def _chaos_seeds():
    env = os.environ.get("CHAOS_SEEDS", "")
    if env:
        return [int(s) for s in env.replace(",", " ").split()]
    return [0, 1, 2]


def _assert_checkpoints_intact(root):
    """Corruption on disk is always *detected*: every surviving generation
    either verifies against its digest manifest or raises the typed
    `CheckpointCorrupt` that makes `restore_latest` skip it — nothing can
    silently unflatten into garbage at restore time."""
    from repro.checkpoint.manager import CheckpointCorrupt, CheckpointManager

    for name in os.listdir(root):
        sub = os.path.join(root, name)
        if not os.path.isdir(sub):
            continue
        m = CheckpointManager(sub)
        for step in m.steps():
            try:
                m._verify(step)
            except CheckpointCorrupt:
                pass  # an injected torn/flipped write, caught typed


@pytest.mark.parametrize("seed", _chaos_seeds())
def test_chaos_invariant(seed, tmp_path, baseline):
    """The headline invariant: under a seeded random fault schedule every
    job completes bit-equal to its fault-free run OR fails with a typed
    error, and the checkpoint directory survives restorable."""
    plan = FaultPlan.from_seed(seed, n_faults=4)
    sched, handles = run_serve(faults=plan, ckdir=str(tmp_path),
                               max_attempts=3)
    for h in handles:
        if h.state is JobState.DONE:
            assert_bit_equal(h.result(timeout=0), baseline[h.id])
        else:
            assert h.state is JobState.FAILED
            assert isinstance(
                h.error,
                (InjectedFault, InjectedCrash, BucketQuarantined,
                 FloatingPointError, WatchdogTimeout),
            ), repr(h.error)
    _assert_checkpoints_intact(tmp_path)


def test_chaos_schedule_property_on_supervisor():
    """Hypothesis-driven schedules over the supervisor state machine: any
    mix of transient failures and wedges ends finished-or-quarantined, with
    every failed job's error typed."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=50, deadline=None)
    @given(
        failures=st.integers(0, 6),
        max_attempts=st.integers(1, 4),
        wedged=st.booleans(),
    )
    def check(failures, max_attempts, wedged):
        err = (WatchdogTimeout("stuck", wedged=True) if wedged
               else InjectedFault("boom"))
        sup = Supervisor(
            policy=RetryPolicy(max_attempts=max_attempts, base_delay_s=0.0),
            sleep=lambda s: None,
        )
        out = sup.run(_FakeBucket(failures=failures, error=err), 1)
        assert out.finished or not out.quarantined
        if failures == 0:
            assert not out.quarantined and out.retries == 0
        elif wedged or failures >= max_attempts:
            assert out.quarantined
            for job in out.bucket.jobs:
                assert isinstance(job.error, BucketQuarantined)
        else:
            assert not out.quarantined
            assert out.retries == failures

    check()


# -- lifecycle hygiene ---------------------------------------------------------


def test_shutdown_drains_pending_jobs_typed():
    sched = Scheduler()
    h = sched.submit(serve_spec())
    sched.shutdown()  # loop never ran: the job would block forever pre-fix
    with pytest.raises(JobFailedError) as ei:
        h.result(timeout=0)
    assert isinstance(ei.value.__cause__, SchedulerStopped)


def test_shutdown_drains_staged_jobs_typed():
    sched = Scheduler(pack_window=3600.0)  # stage, never seal
    h = sched.submit(serve_spec())
    sched._intake()
    assert len(sched.queue) == 0 and sched._staged
    sched.shutdown()
    assert h.state is JobState.FAILED
    assert isinstance(h.error, SchedulerStopped)


def test_started_shutdown_no_wait_fails_pending(tmp_path):
    sched = Scheduler(pack_window=3600.0)
    sched.start()
    h = sched.submit(serve_spec())
    sched.shutdown(wait=False)
    assert h.state is JobState.FAILED
    assert isinstance(h.error, SchedulerStopped)


def test_queue_depth_backpressure():
    sched = Scheduler(queue_depth=2)
    sched.submit(serve_spec(seed=0))
    sched.submit(serve_spec(seed=1))
    with pytest.raises(QueueFull):
        sched.submit(serve_spec(seed=2))
    # the rejected submission registered nothing
    assert len(sched.jobs) == 2
    with pytest.raises(QueueFull):
        sched.submit(serve_spec(seed=2), block=True, timeout=0.05)


def test_result_timeout_raises_instead_of_hanging():
    sched = Scheduler()
    h = sched.submit(serve_spec())
    with pytest.raises(TimeoutError, match="still pending"):
        h.result(timeout=0.01)
    sched.run_until_idle()
    assert h.result(timeout=0).n_sweeps == 16


# -- restart with faults threaded through -------------------------------------


def test_from_checkpoint_skips_poisoned_bucket(tmp_path, baseline):
    from repro.serve.bucket import MANIFEST_NAME

    sched = Scheduler(checkpoint_dir=str(tmp_path), checkpoint_every_quanta=1)
    h = sched.submit(serve_spec(), job_id="j0")
    for _ in range(2):
        sched.step()
    assert not h.done()
    bad = os.path.join(tmp_path, "deadbeef-0099")
    os.makedirs(bad)
    with open(os.path.join(bad, MANIFEST_NAME), "w") as f:
        f.write("{ not json")
    with pytest.warns(RuntimeWarning, match="unreadable bucket manifest"):
        sched2 = Scheduler.from_checkpoint(str(tmp_path))
    sched2.run_until_idle()
    assert sched2.jobs["j0"].state is JobState.DONE
    # phases completed before the restore survive via the checkpoint cut;
    # final energies are always bit-equal to the fault-free run
    assert np.array_equal(
        np.asarray(sched2.jobs["j0"].result(timeout=0).final_energy),
        np.asarray(baseline["j0"].final_energy),
    )
