"""Telemetry layer contracts (DESIGN.md §Observability).

The pins, in dependency order:

* **registry semantics** — labeled families cache children, re-declaration
  is idempotent-or-error, counters only go up, histogram buckets cumulate;
* **exposition** — Prometheus text renders HELP/TYPE/labels/histogram
  expansion; the snapshot digest is deterministic and content-sensitive;
* **timeline** — spans/instants/flows land as schema-valid Chrome trace
  events, tracks get stable tids + thread_name metadata, `write` round-trips
  through the `check_trace` validator; `NullTimeline` records nothing and
  its spans reach a running profiler; a span starts at the same instant in
  the timeline and in the profiler;
* **zero-overhead-off** — an engine with ``obs=None`` constructs no
  `_EngineObs`, records no events and touches no metric even when the obs
  classes are booby-trapped to raise; the mega-step jaxpr is byte-identical
  with obs on or off, and an instrumented run is *bit-equal* to a bare one;
* **obs-on** — the engine's spans and counters actually appear
  (repro.engine.compile / .chunk / .dispatch / .adapt / .checkpoint), the
  host loop never waits for the device, `ObsCallback` lands artifacts on
  disk through a full Session, and `Scheduler.metrics()` exposes the
  serve-side series;
* **profiler** — with obs off or on, a profiled engine run holds one
  repro.engine.dispatch and one repro.engine.callbacks span per chunk,
  inside repro.engine.chunk, and a repro.engine.compile span;
* **diagnostics fallback** — legacy traces without a `swap_attempt` channel
  warn when the `prob > 0` inference kicks in; engine-era traces don't.

The <5%-obs-on wall-clock budget is a *benchmark* contract
(`benchmarks/obs_overhead.py`, CI-gated); the slow-marked test here runs
the same measurement end-to-end as a local check.
"""
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import diagnostics, ising, ladder
from repro.engine import Engine, EngineConfig
from repro.engine.driver import _EngineObs
from repro.obs import (
    MetricsRegistry,
    NullTimeline,
    Observability,
    Timeline,
    snapshot_digest,
    to_prometheus,
    write_prometheus,
)
from repro.obs.check_trace import TraceError, validate_trace
from repro.obs.timeline import ProfilerSpan

R, L = 4, 4
TEMPS = np.asarray(ladder.linear_ladder(R, 1.5, 3.5))


def _engine(obs=None, **kw):
    system = ising.IsingSystem(length=L)
    defaults = dict(n_replicas=R, swap_interval=2, chunk_intervals=2)
    defaults.update(kw)
    return Engine(system, EngineConfig(**defaults), obs=obs)


# ---------- metrics registry ----------------------------------------------------


def test_counter_accumulates_and_rejects_negative():
    m = MetricsRegistry()
    c = m.counter("requests_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = MetricsRegistry().gauge("depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6.0


def test_histogram_buckets_cumulative():
    h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    (s,) = h.samples()
    assert s["count"] == 5 and s["sum"] == pytest.approx(56.05)
    # cumulative per upper bound, +Inf == count
    assert s["buckets"] == [[0.1, 1], [1.0, 3], [10.0, 4], ["+Inf", 5]]


def test_labeled_children_cached_and_validated():
    m = MetricsRegistry()
    g = m.gauge("occupancy", labels=("bucket",))
    assert g.labels("a") is g.labels("a")
    assert g.labels(bucket="a") is g.labels("a")
    g.labels("b").set(2)
    samples = {s["labels"]["bucket"]: s["value"] for s in g.samples()}
    assert samples == {"a": 0.0, "b": 2.0}
    with pytest.raises(ValueError, match="label values"):
        g.labels("a", "extra")
    with pytest.raises(ValueError, match="labeled"):
        g.set(1)  # label-less use of a labeled family


def test_redeclare_same_returns_same_family_mismatch_raises():
    m = MetricsRegistry()
    c1 = m.counter("hits_total", "first")
    assert m.counter("hits_total", "second declaration ignored") is c1
    with pytest.raises(ValueError, match="re-declared"):
        m.gauge("hits_total")
    with pytest.raises(ValueError, match="re-declared"):
        m.counter("hits_total", labels=("route",))
    with pytest.raises(ValueError, match="bad metric name"):
        m.counter("1bad")
    with pytest.raises(ValueError, match="bad metric name"):
        m.counter("has space")


def test_snapshot_is_plain_json_data():
    m = MetricsRegistry()
    m.counter("a_total").inc()
    m.histogram("b").observe(0.2)
    snap = m.snapshot()
    json.dumps(snap)  # must be JSON-able as-is
    assert snap["a_total"]["type"] == "counter"
    assert snap["b"]["type"] == "histogram"
    assert snap["a_total"]["samples"][0]["value"] == 1.0


def test_registry_thread_safety_under_contention():
    m = MetricsRegistry()
    c = m.counter("n_total")
    h = m.histogram("h", buckets=(1.0,))

    def work():
        for _ in range(500):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 2000
    assert h.count == 2000


# ---------- exposition ----------------------------------------------------------


def test_prometheus_text_rendering():
    m = MetricsRegistry()
    m.counter("hits_total", "total hits").inc(3)
    m.gauge("depth", labels=("queue",)).labels("main").set(2)
    m.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)).observe(0.05)
    text = to_prometheus(m.snapshot())
    assert "# HELP hits_total total hits" in text
    assert "# TYPE hits_total counter" in text
    assert "hits_total 3" in text
    assert 'depth{queue="main"} 2' in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_sum 0.05" in text
    assert "lat_seconds_count 1" in text


def test_snapshot_digest_deterministic_and_content_sensitive():
    m = MetricsRegistry()
    m.counter("a_total").inc()
    d1 = snapshot_digest(m.snapshot())
    assert d1 == snapshot_digest(m.snapshot())
    assert len(d1) == 12
    m.counter("a_total").inc()
    assert snapshot_digest(m.snapshot()) != d1


def test_write_prometheus_atomic(tmp_path):
    m = MetricsRegistry()
    m.counter("x_total").inc()
    path = write_prometheus(m, str(tmp_path / "sub" / "metrics.prom"))
    assert "x_total 1" in open(path).read()


# ---------- timeline ------------------------------------------------------------


def test_span_records_complete_event_with_args():
    tl = Timeline()
    with tl.span("chunk", cat="engine", index=3) as sp:
        sp.annotate(sweeps=40)
    (meta, ev) = tl.events()
    assert meta["ph"] == "M" and meta["args"]["name"] == threading.current_thread().name
    assert ev["ph"] == "X" and ev["name"] == "chunk"
    assert ev["args"] == {"index": 3, "sweeps": 40}
    assert ev["ts"] >= 0 and ev["dur"] >= 0


def test_span_annotates_exception():
    tl = Timeline()
    with pytest.raises(RuntimeError):
        with tl.span("doomed"):
            raise RuntimeError("boom")
    ev = tl.events()[-1]
    assert ev["args"]["error"] == "RuntimeError"


def test_tracks_get_stable_tids_and_metadata():
    tl = Timeline()
    tl.instant("a", track="alpha")
    tl.instant("b", track="beta")
    tl.instant("c", track="alpha")
    events = tl.events()
    names = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    assert sorted(names.values()) == ["alpha", "beta"]
    a_tid = next(t for t, n in names.items() if n == "alpha")
    assert [e["tid"] for e in events if e["ph"] == "i"] == [
        a_tid, next(t for t, n in names.items() if n == "beta"), a_tid
    ]


def test_flow_events_and_counter():
    tl = Timeline()
    tl.flow_start("job:x", "x", track="intake")
    tl.flow_step("job:x", "x", track="bucket")
    tl.flow_end("job:x", "x", track="bucket", state="done")
    tl.counter("queue", {"depth": 2})
    phs = [e["ph"] for e in tl.events() if e["ph"] not in ("M",)]
    assert phs == ["s", "t", "f", "C"]
    fin = next(e for e in tl.events() if e["ph"] == "f")
    assert fin["bp"] == "e" and fin["id"] == "x"


def test_write_roundtrips_through_validator(tmp_path):
    tl = Timeline()
    with tl.span("compile"):
        pass
    tl.flow_start("j", 1)
    tl.flow_end("j", 1)
    path = tl.write(str(tmp_path / "out.trace.json"))
    with open(path) as f:
        data = json.load(f)
    summary = validate_trace(
        data, require_spans=["compile"], require_balanced_flows=True
    )
    assert summary["n_spans"] == 1
    assert summary["open_flows"] == 0


def test_null_timeline_is_inert_and_allocation_free(tmp_path):
    """The null span keeps nothing of its own: with no profiler it records
    nothing, and a running profiler receives it with its args."""
    nt = NullTimeline()
    assert isinstance(nt.span("a"), ProfilerSpan)
    with nt.span("a") as sp:
        assert sp.annotate(x=1) is sp
    nt.instant("x")
    nt.counter("c", {"v": 1})
    nt.flow_start("f", 1)
    assert len(nt) == 0 and nt.events() == []
    with pytest.raises(RuntimeError, match="records nothing"):
        nt.write("/tmp/never.json")

    jax.profiler.start_trace(str(tmp_path))
    try:
        with nt.span("repro.test.null", k=3) as sp:
            sp.annotate(extra=1)
    finally:
        jax.profiler.stop_trace()
    assert len(nt) == 0
    (ev,) = [e for e in _profiled_host_events(tmp_path)
             if e[0] == "repro.test.null"]
    assert ev[3] == {"k": 3, "extra": 1}


def _profiled_host_events(directory):
    """``(name, start_ns, end_ns, args)`` of every host event in the newest
    profile under ``directory``, start and end on `time.time_ns`'s clock."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(path)
    t0 = next(dict(p.stats)["profile_start_time"] for p in data.planes
              if p.name == "Task Environment")
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        out.append((e.name, t0 + e.start_ns,
                                    t0 + e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def test_span_starts_with_its_profiler_event(tmp_path):
    """One span, recorded by the timeline and by the profiler, starts at
    the same instant on both (within 50 us)."""
    tl = Timeline()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(5):
            with tl.span(f"repro.test.clock{i}"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    prof = {n: (a, b) for n, a, b, _ in _profiled_host_events(tmp_path)}
    spans = [e for e in tl.events() if e["ph"] == "X"]
    assert len(spans) == 5
    for ev in spans:
        start_ns, end_ns = prof[ev["name"]]
        assert abs(ev["ts"] * 1e3 - start_ns) < 50e3, ev["name"]
        assert abs((ev["ts"] + ev["dur"]) * 1e3 - end_ns) < 50e3, ev["name"]


# ---------- trace validator -----------------------------------------------------


def _good_trace():
    return {"traceEvents": [
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
         "args": {"name": "main"}},
        {"name": "chunk", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 5.0},
    ]}


def test_validate_trace_accepts_good_and_summarizes():
    s = validate_trace(_good_trace(), require_spans=["chunk"])
    assert s["n_spans"] == 1 and s["tracks"] == ["main"]


@pytest.mark.parametrize("mutate, match", [
    (lambda d: d.pop("traceEvents"), "traceEvents"),
    (lambda d: d["traceEvents"][1].update(ph="Z"), "unknown or missing ph"),
    (lambda d: d["traceEvents"][1].pop("tid"), "tid"),
    (lambda d: d["traceEvents"][1].update(dur=-1), "dur"),
    (lambda d: d["traceEvents"][1].update(ts=-1), "ts"),
    (lambda d: d["traceEvents"][1].update(name=""), "name"),
])
def test_validate_trace_rejects_structural_violations(mutate, match):
    data = _good_trace()
    mutate(data)
    with pytest.raises(TraceError, match=match):
        validate_trace(data)


def test_validate_trace_required_span_and_flow_balance():
    with pytest.raises(TraceError, match="required span 'adapt'"):
        validate_trace(_good_trace(), require_spans=["adapt"])
    data = _good_trace()
    data["traceEvents"].append(
        {"name": "j", "ph": "s", "pid": 1, "tid": 1, "ts": 1.0, "id": "7"}
    )
    assert validate_trace(data)["open_flows"] == 1
    with pytest.raises(TraceError, match="unfinished flows"):
        validate_trace(data, require_balanced_flows=True)


# ---------- zero-overhead-off (the structural contract) -------------------------


def test_obs_off_engine_never_touches_obs_layer(monkeypatch):
    """With ``obs=None`` the host loop must not construct `_EngineObs`,
    record a single event, or touch a single metric — enforced by making
    every obs entry point raise and running the engine anyway."""
    import repro.engine.driver as driver_mod
    import repro.obs.metrics as metrics_mod
    import repro.obs.timeline as timeline_mod

    def bomb(*a, **k):
        raise AssertionError("obs layer touched on the obs-off path")

    monkeypatch.setattr(driver_mod._EngineObs, "__init__", bomb)
    for cls in (timeline_mod.Timeline,):
        for meth in ("span", "complete", "instant", "counter"):
            monkeypatch.setattr(cls, meth, bomb)
    for name in ("counter", "gauge", "histogram"):
        monkeypatch.setattr(metrics_mod.MetricsRegistry, name, bomb)

    eng = _engine()
    assert eng._eobs is None and eng.obs is None
    st = eng.init(jax.random.key(0), TEMPS)
    st, res = eng.run(st, 16)
    assert res.n_sweeps == 16


def test_mega_step_jaxpr_identical_obs_on_and_off():
    """Instrumentation lives in the host loop only: the compiled computation
    must be byte-identical with obs attached."""
    eng_off = _engine()
    eng_on = _engine(obs=Observability.create(timeline=True))
    st_off = eng_off.init(jax.random.key(0), TEMPS)
    st_on = eng_on.init(jax.random.key(0), TEMPS)
    jx = lambda e, s: str(jax.make_jaxpr(e._make_mega(2, s))(
        s.pt, s.stats, s.betas
    ))
    assert jx(eng_off, st_off) == jx(eng_on, st_on)


def test_obs_on_run_bit_equal_to_obs_off():
    eng_off = _engine()
    eng_on = _engine(obs=Observability.create(timeline=True))
    st_off, _ = eng_off.run(eng_off.init(jax.random.key(3), TEMPS), 24)
    st_on, _ = eng_on.run(eng_on.init(jax.random.key(3), TEMPS), 24)
    np.testing.assert_array_equal(
        np.asarray(st_off.pt.states), np.asarray(st_on.pt.states)
    )
    np.testing.assert_array_equal(
        np.asarray(st_off.pt.energy), np.asarray(st_on.pt.energy)
    )
    np.testing.assert_array_equal(
        np.asarray(st_off.pt.rung), np.asarray(st_on.pt.rung)
    )


def test_cached_mega_step_keeps_its_own_scopes(tmp_path, monkeypatch):
    """The pt.* scopes are debug info, which JAX's persistent cache key
    leaves out by default.  A cache that already holds the same program
    compiled without them must not hand that executable back: the
    engine's executable carries its own scopes."""
    import contextlib

    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

        def compiled():
            eng = _engine(chunk_intervals=1)
            return eng._compiled(eng.init(jax.random.key(0), TEMPS), 1).as_text()

        with monkeypatch.context() as m:  # the same program, unscoped
            m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            assert "pt.sweep" not in compiled()
        assert any(tmp_path.iterdir())
        text = compiled()
        for scope in ("pt.sweep", "pt.rng", "pt.exchange", "pt.stats", "pt.observe"):
            assert scope in text, scope
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_obs_detach_restores_bare_engine():
    eng = _engine(obs=Observability.create(timeline=False))
    assert isinstance(eng._eobs, _EngineObs)
    eng.obs = None
    assert eng._eobs is None and eng.obs is None


def test_failed_jax_profile_start_fails_the_run(monkeypatch, tmp_path):
    """A profile that was asked for and cannot start must stop the run,
    not leave a timeline instant behind and carry on unprofiled."""
    def refuse(_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    obs = Observability.create(timeline=True, jax_profile_dir=str(tmp_path))
    eng = _engine(obs=obs)
    st = eng.init(jax.random.key(1), TEMPS)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        eng.run(st, 4)


# ---------- obs-on engine instrumentation ---------------------------------------


def test_engine_metrics_and_spans_populated():
    obs = Observability.create(timeline=True)
    eng = _engine(obs=obs)
    st = eng.init(jax.random.key(1), TEMPS)
    eng.run(st, 16)  # 8 intervals = 4 chunks of 2

    snap = obs.metrics.snapshot()
    value = lambda n: snap[n]["samples"][0]["value"]
    assert value("engine_compiles_total") == 1
    assert value("engine_chunks_total") == 4
    assert value("engine_sweeps_total") == 16
    # one sample per pair of successive dispatch returns
    assert snap["engine_chunk_seconds"]["samples"][0]["count"] == 3
    assert value("engine_compile_seconds_total") > 0
    for gone in ("engine_device_seconds_total", "engine_host_seconds_total",
                 "engine_sweeps_per_sec"):
        assert gone not in snap
    # live per-rung gauges: R-1 pair children, R rung children
    assert len(snap["pt_swap_acceptance"]["samples"]) == R - 1
    assert len(snap["pt_flow_up_fraction"]["samples"]) == R

    spans = [e for e in obs.timeline.events() if e["ph"] == "X"]
    names = [e["name"] for e in spans]
    assert names.count("repro.engine.compile") == 1
    assert names.count("repro.engine.chunk") == 4
    assert names.count("repro.engine.dispatch") == 4
    assert "device_wait" not in names
    chunk_ev = next(e for e in spans if e["name"] == "repro.engine.chunk")
    assert chunk_ev["args"] == {"chunk": 1, "intervals": 2}


def test_obs_on_host_loop_never_waits_for_the_device(monkeypatch):
    """The instrumented loop dispatches chunk after chunk with no
    `block_until_ready`: the device/host split comes from the trace."""
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    for obs in (None, Observability.create(timeline=True)):
        eng = _engine(obs=obs)
        st = eng.init(jax.random.key(2), TEMPS)
        eng.run(st, 16)
    assert calls == []


@pytest.mark.parametrize("with_obs", [False, True], ids=["obs_off", "obs_on"])
def test_engine_spans_reach_the_profiler(tmp_path, with_obs):
    """Profiled run of a small engine: one dispatch and one callbacks span
    per chunk, each inside its chunk span, and the compile span."""
    obs = Observability.create(timeline=True) if with_obs else None
    eng = _engine(obs=obs)
    st = eng.init(jax.random.key(4), TEMPS)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(st, 16, on_chunk=lambda info: False)  # 4 chunks
    finally:
        jax.profiler.stop_trace()
    events = _profiled_host_events(tmp_path)
    named = lambda n: sorted((a, b) for name, a, b, _ in events if name == n)
    chunks = named("repro.engine.chunk")
    assert len(chunks) == 4 and len(named("repro.engine.compile")) == 1
    for child in ("repro.engine.dispatch", "repro.engine.callbacks"):
        inner = named(child)
        assert len(inner) == 4, child
        for (a, b), (ca, cb) in zip(chunks, inner):
            assert a <= ca <= cb <= b, child
    (ca, cb), (ka, kb) = chunks[0], named("repro.engine.compile")[0]
    assert ca <= ka <= kb <= cb


def test_engine_checkpoint_span_and_counter(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    obs = Observability.create(timeline=True)
    eng = _engine(obs=obs)
    st = eng.init(jax.random.key(1), TEMPS)
    eng.run(st, 16, checkpoint=CheckpointManager(str(tmp_path)),
            checkpoint_every_chunks=2)
    snap = obs.metrics.snapshot()
    assert snap["engine_checkpoints_total"]["samples"][0]["value"] == 2
    names = [e["name"] for e in obs.timeline.events() if e["ph"] == "X"]
    assert names.count("repro.engine.checkpoint") == 2


# ---------- ObsCallback through a full Session ----------------------------------


def _spec(**kw):
    from repro.api import (
        EngineSpec, LadderSpec, PhaseSpec, RunSpec, ScheduleSpec, SystemSpec,
    )

    return RunSpec(
        system=SystemSpec("ising", {"length": L}),
        ladder=LadderSpec(kind="geometric", n_replicas=R, t_min=1.5, t_max=3.5),
        engine=EngineSpec(swap_interval=2, chunk_intervals=2),
        schedule=ScheduleSpec(phases=(
            PhaseSpec("burn", 8), PhaseSpec("measure", 8, reset_stats=True),
        )),
        observables=("mag",),
        seed=0,
        **kw,
    )


def test_obs_callback_writes_artifacts_through_session(tmp_path):
    from repro.api import ObsCallback, Session

    trace_path = str(tmp_path / "run.trace.json")
    prom_path = str(tmp_path / "metrics.prom")
    cb = ObsCallback(timeline_path=trace_path, metrics_path=prom_path)
    Session(_spec(), callbacks=[cb]).run()

    with open(trace_path) as f:
        data = json.load(f)
    validate_trace(data, require_spans=[
        "repro.engine.compile", "repro.engine.chunk", "repro.engine.dispatch",
        "repro.session.phase",
    ])
    phases = [e["args"]["phase"] for e in data["traceEvents"]
              if e.get("name") == "repro.session.phase"]
    assert phases == ["burn", "measure"]
    text = open(prom_path).read()
    # 2 phases x 8 sweeps = 2 phases x 2 chunks of 2 intervals
    assert "engine_chunks_total 4" in text
    assert "engine_sweeps_total 16" in text


def test_obs_callback_session_result_bit_equal_to_bare_session(tmp_path):
    from repro.api import ObsCallback, Session

    bare = Session(_spec()).run()
    cb = ObsCallback(timeline_path=str(tmp_path / "t.json"),
                     metrics_path=str(tmp_path / "m.prom"))
    instrumented = Session(_spec(), callbacks=[cb]).run()
    np.testing.assert_array_equal(
        bare.final_energies(), instrumented.final_energies()
    )


# ---------- serve scheduler telemetry -------------------------------------------


def _serve_spec(seed=0):
    from repro.api import (
        EngineSpec, LadderSpec, PhaseSpec, RunSpec, ScheduleSpec, SystemSpec,
    )

    return RunSpec(
        system=SystemSpec("ising", {"length": 4}),
        ladder=LadderSpec(kind="geometric", n_replicas=4, t_min=1.5, t_max=3.5),
        engine=EngineSpec(swap_interval=2, chunk_intervals=2),
        schedule=ScheduleSpec(phases=(PhaseSpec("burn", 8),)),
        observables=("mag",),
        seed=seed,
    )


def test_scheduler_metrics_exposed():
    from repro.serve import Scheduler

    obs = Observability.create(timeline=True)
    sched = Scheduler(obs=obs)
    jobs = [sched.submit(_serve_spec(seed=s)) for s in range(3)]
    sched.run_until_idle()
    for job in jobs:
        job.result(timeout=30)

    snap = sched.metrics()
    value = lambda n: snap[n]["samples"][0]["value"]
    assert value("serve_queue_depth") == 0
    assert value("serve_quanta_total") >= 1
    # 3 same-shaped jobs amortize exactly one compile
    assert value("serve_jobs_packed_per_compile") == 3.0
    assert snap["serve_quantum_seconds"]["samples"][0]["count"] >= 1
    assert snap["serve_time_in_queue_seconds"]["samples"][0]["count"] == 3
    assert len(snap["serve_job_sweeps"]["samples"]) == 3
    # the job flows opened at submit are all closed by completion
    summary = validate_trace(obs.timeline.to_dict(), require_balanced_flows=True)
    assert summary["open_flows"] == 0


def test_scheduler_metrics_without_obs_still_available():
    from repro.serve import Scheduler

    sched = Scheduler()  # internal registry, NULL timeline
    job = sched.submit(_serve_spec())
    sched.run_until_idle()
    job.result(timeout=30)
    assert "serve_quanta_total" in sched.metrics()


def test_scheduler_condvar_shutdown_is_prompt():
    """shutdown(wait=True) must block on the idle condition (not a poll
    loop) and return promptly once the queue drains."""
    from repro.serve import Scheduler

    sched = Scheduler()
    sched.start()
    job = sched.submit(_serve_spec())
    job.result(timeout=60)
    t0 = time.perf_counter()
    sched.shutdown(wait=True)
    assert time.perf_counter() - t0 < 5.0
    assert sched._thread is None or not sched._thread.is_alive()
    assert sched.metrics()["serve_wakeup_latency_seconds"]["samples"][0]["count"] >= 1


def test_scheduler_periodic_metrics_file(tmp_path):
    from repro.serve import Scheduler

    path = str(tmp_path / "metrics.prom")
    sched = Scheduler(metrics_every=1, metrics_path=path)
    job = sched.submit(_serve_spec())
    sched.run_until_idle()
    job.result(timeout=30)
    assert "serve_quanta_total" in open(path).read()


# ---------- diagnostics fallback warning ----------------------------------------


def test_legacy_trace_fallback_warns():
    t, r = 6, 4
    legacy = {
        "swap_accept": np.ones((t, r)),
        "swap_prob": np.full((t, r), 0.5),
    }
    with pytest.warns(RuntimeWarning, match="swap_attempt"):
        rate = diagnostics.swap_acceptance_rate(legacy)
    assert rate.shape == (r - 1,)


def test_engine_trace_with_attempts_does_not_warn(recwarn):
    t, r = 6, 4
    trace = {
        "swap_accept": np.ones((t, r)),
        "swap_attempt": np.ones((t, r)),
        "swap_prob": np.full((t, r), 0.5),
    }
    rate = diagnostics.swap_acceptance_rate(trace)
    assert not [w for w in recwarn.list if issubclass(w.category, RuntimeWarning)]
    np.testing.assert_allclose(rate, 1.0)


def test_legacy_fallback_rate_matches_nonzero_prob_counting():
    t, r = 4, 3
    prob = np.zeros((t, r))
    prob[:2, :] = 0.7  # only two attempts visible per rung
    acc = np.zeros((t, r))
    acc[0, :] = 1.0
    with pytest.warns(RuntimeWarning):
        rate = diagnostics.swap_acceptance_rate(
            {"swap_accept": acc, "swap_prob": prob}
        )
    np.testing.assert_allclose(rate, 0.5)


# ---------- the <5% wall-clock budget (benchmark-grade, slow) -------------------


@pytest.mark.slow
def test_obs_on_overhead_under_budget():
    obs_overhead = pytest.importorskip("benchmarks.obs_overhead")
    m = obs_overhead.measure(length=32, sweeps=256, repeats=9)
    assert m["ratio"] <= 1.05, f"obs-on overhead {m['ratio']:.3f} > 1.05"
    assert m["n_compiles_off"] == m["n_compiles_on"] == 1
