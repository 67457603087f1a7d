"""The declarative RunSpec API (DESIGN.md §API).

Covers the spec tree's lossless JSON round-trip (every registered system +
hypothesis-generated specs), strict rejection of unknown versions/keys, the
Session-vs-raw-Engine bit-equality contract, the callback pipeline
(checkpoint/early-stop/trace streaming), resume-from-record, and the
``python -m repro`` CLI surface.
"""
import json
import os
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.api import (
    AdaptSpec,
    Callback,
    CheckpointCallback,
    EarlyStopCallback,
    EngineSpec,
    ExchangeSpec,
    LadderSpec,
    PhaseSpec,
    RunSpec,
    ScheduleSpec,
    Session,
    SystemSpec,
    TraceWriterCallback,
    simple_schedule,
)
from repro.api.cli import main as cli_main
from repro.checkpoint.manager import CheckpointManager
from repro.core import systems
from repro.engine import AdaptConfig, Engine, EngineConfig
from repro.validate.conformance import entry_runspec


def tiny_ising_spec(**overrides) -> RunSpec:
    base = dict(
        system=SystemSpec("ising", {"length": 4, "accept_rule": "glauber"}),
        ladder=LadderSpec(kind="custom", n_replicas=4,
                          temps=(1.5, 2.2, 3.1, 4.4)),
        engine=EngineSpec(swap_interval=5, chunk_intervals=4),
        schedule=ScheduleSpec(phases=(PhaseSpec(name="measure", n_sweeps=60),)),
        observables=("absmag",),
        seed=2,
    )
    base.update(overrides)
    return RunSpec(**base)


# ---------- JSON round-trip -----------------------------------------------------


@pytest.mark.parametrize("name", sorted(systems.REGISTRY))
def test_roundtrip_every_registered_system(name):
    """from_json(to_json(s)) == s for the conformance spec of every system."""
    spec = entry_runspec(systems.REGISTRY[name], seed=3)
    assert RunSpec.from_json(spec.to_json()) == spec
    # and the dict form too (what the CLI reads)
    assert RunSpec.from_dict(json.loads(spec.to_json())) == spec


def test_roundtrip_preserves_defaults_and_none_adapt():
    spec = tiny_ising_spec()
    assert spec.adapt is None
    again = RunSpec.from_json(spec.to_json())
    assert again == spec
    assert again.adapt is None
    assert again.engine == EngineSpec(swap_interval=5, chunk_intervals=4)


def test_roundtrip_hypothesis_generated_specs():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @st.composite
    def runspecs(draw):
        r = draw(st.integers(2, 6))
        kind = draw(st.sampled_from(["paper", "linear", "geometric", "custom"]))
        t_min = draw(st.floats(0.5, 2.0, allow_nan=False))
        t_max = t_min + draw(st.floats(0.5, 8.0, allow_nan=False))
        temps = None
        if kind == "custom":
            temps = tuple(float(t) for t in np.linspace(t_min, t_max, r))
        interval = draw(st.integers(1, 20))
        n_phases = draw(st.integers(1, 4))
        phases = tuple(
            PhaseSpec(
                name=f"p{i}",
                n_sweeps=interval * draw(st.integers(1, 40)),
                adapt=draw(st.booleans()),
                reset_stats=draw(st.booleans()),
            )
            for i in range(n_phases)
        )
        name = draw(st.sampled_from(sorted(systems.REGISTRY)))
        return RunSpec(
            system=SystemSpec(name, dict(systems.REGISTRY[name].params)),
            ladder=LadderSpec(kind=kind, n_replicas=r, t_min=t_min,
                              t_max=t_max, temps=temps),
            engine=EngineSpec(
                swap_interval=interval,
                criterion=draw(st.sampled_from(["logistic", "metropolis"])),
                swap_mode=draw(st.sampled_from(["temp", "state"])),
                chunk_intervals=draw(st.integers(1, 64)),
                n_chains=draw(st.integers(1, 4)),
                record_trace=draw(st.booleans()),
            ),
            adapt=AdaptSpec(
                target=draw(st.floats(0.05, 0.9, allow_nan=False)),
                max_rounds=draw(st.one_of(st.none(), st.integers(1, 9))),
            ),
            schedule=ScheduleSpec(phases=phases),
            observables=tuple(systems.REGISTRY[name].observable_names),
            seed=draw(st.integers(0, 2**31 - 1)),
        )

    @hyp.given(runspecs())
    @hyp.settings(max_examples=60, deadline=None)
    def check(spec):
        assert RunSpec.from_json(spec.to_json()) == spec

    check()


def test_unknown_spec_version_rejected():
    data = json.loads(tiny_ising_spec().to_json())
    data["spec_version"] = 99
    with pytest.raises(ValueError, match="spec_version"):
        RunSpec.from_dict(data)
    with pytest.raises(ValueError, match="spec_version"):
        tiny_ising_spec(spec_version=0)


def test_unknown_keys_rejected_everywhere():
    good = json.loads(tiny_ising_spec().to_json())
    for path in (("bogus",), ("system", "bogus"), ("ladder", "bogus"),
                 ("engine", "bogus"), ("exchange", "bogus")):
        data = json.loads(json.dumps(good))
        node = data
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = 1
        with pytest.raises(ValueError, match="unknown key"):
            RunSpec.from_dict(data)


def test_enum_valued_fields_rejected_at_parse_time():
    """Satellite guard: a typo'd enum value must fail in `from_json` with
    the field and its allowed values named — not deep inside the engine."""
    good = json.loads(tiny_ising_spec().to_json())
    cases = [
        (("engine", "criterion"), "boltzman", "criterion.*allowed"),
        (("engine", "swap_mode"), "both", "swap_mode.*allowed"),
        (("ladder", "kind"), "logarithmic", "bad ladder kind"),
        (("exchange", "strategy"), "qpam", "strategy.*allowed"),
    ]
    for path, val, match in cases:
        data = json.loads(json.dumps(good))
        node = data
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = val
        with pytest.raises(ValueError, match=match):
            RunSpec.from_dict(data)
    # adapt.mode rides on a spec that actually has an adapt block
    with_adapt = json.loads(tiny_ising_spec(
        adapt=AdaptSpec(target=0.3)
    ).to_json())
    with_adapt["adapt"]["mode"] = "osmosis"
    with pytest.raises(ValueError, match="adapt mode.*allowed"):
        RunSpec.from_dict(with_adapt)
    # and the constructors reject the same values directly
    with pytest.raises(ValueError, match="allowed"):
        EngineSpec(criterion="boltzman")
    with pytest.raises(ValueError, match="allowed"):
        ExchangeSpec(strategy="qpam")
    with pytest.raises(ValueError, match="allowed"):
        AdaptSpec(mode="osmosis")


def test_exchange_spec_roundtrip_and_default():
    spec = tiny_ising_spec()
    assert spec.exchange == ExchangeSpec()  # deo is the default
    for strat in ("seo", "windowed", "vmpt"):
        s = tiny_ising_spec(exchange=ExchangeSpec(strategy=strat, window=5))
        again = RunSpec.from_json(s.to_json())
        assert again == s
        assert again.exchange.strategy == strat
    # a pre-exchange JSON (no "exchange" key) parses to the default
    data = json.loads(spec.to_json())
    del data["exchange"]
    assert RunSpec.from_dict(data).exchange == ExchangeSpec()


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="custom ladder"):
        LadderSpec(kind="custom", n_replicas=4)
    with pytest.raises(ValueError, match="bad ladder kind"):
        LadderSpec(kind="nope")
    with pytest.raises(ValueError, match="multiple of the engine interval"):
        tiny_ising_spec(schedule=ScheduleSpec(
            phases=(PhaseSpec(name="m", n_sweeps=7),)
        ))
    with pytest.raises(ValueError, match="no AdaptSpec"):
        tiny_ising_spec(schedule=ScheduleSpec(
            phases=(PhaseSpec(name="m", n_sweeps=10, adapt=True),)
        ))
    with pytest.raises(ValueError, match="duplicate phase"):
        ScheduleSpec(phases=(PhaseSpec(name="m", n_sweeps=5),
                             PhaseSpec(name="m", n_sweeps=5)))
    with pytest.raises(KeyError, match="unknown system"):
        SystemSpec("not_a_system").build()
    spec = tiny_ising_spec(observables=("not_an_obs",))
    with pytest.raises(KeyError, match="no observable"):
        Session(spec)


def test_ladder_kinds_build_expected_shapes():
    for kind in ("paper", "linear", "geometric"):
        t = LadderSpec(kind=kind, n_replicas=6, t_min=1.0, t_max=4.0).build()
        assert t.shape == (6,)
        assert np.all(np.diff(t) > 0)
    lin = LadderSpec(kind="linear", n_replicas=5, t_min=1.0, t_max=4.0).build()
    np.testing.assert_allclose(lin, np.linspace(1.0, 4.0, 5), rtol=1e-6)


# ---------- Session execution contract ------------------------------------------


def test_session_bit_equal_to_raw_engine_fixed_ladder():
    """Acceptance criterion: Session.run == hand-driven Engine, bit-for-bit."""
    spec = tiny_ising_spec(
        schedule=ScheduleSpec(phases=(
            PhaseSpec(name="burn", n_sweeps=40),
            PhaseSpec(name="measure", n_sweeps=60, reset_stats=True),
        )),
    )
    result = Session(spec).run()

    system = systems.make_system("ising", {"length": 4, "accept_rule": "glauber"})
    eng = Engine(
        system,
        EngineConfig(n_replicas=4, swap_interval=5, chunk_intervals=4),
        observables=systems.named_observables("ising", system, ["absmag"]),
    )
    st = eng.init(jax.random.key(2), np.asarray(spec.ladder.temps))
    st, _ = eng.run(st, 40)
    st = eng.reset_stats(st)
    st, res = eng.run(st, 60)
    e = np.asarray(st.pt.energy)[np.argsort(np.asarray(st.pt.rung))]
    np.testing.assert_array_equal(e, result.final_energies())
    np.testing.assert_array_equal(
        res.summary["mean_absmag"],
        result.phases["measure"].summary["mean_absmag"],
    )


def test_session_adaptive_matches_raw_engine():
    spec = tiny_ising_spec(
        adapt=AdaptSpec(target=0.3, min_attempts_per_pair=2, max_rounds=2),
        schedule=ScheduleSpec(phases=(
            PhaseSpec(name="burn", n_sweeps=100, adapt=True),
            PhaseSpec(name="measure", n_sweeps=50, reset_stats=True),
        )),
    )
    result = Session(spec).run()
    assert len(result.phases["burn"].ladder_history) == 3  # initial + 2 retunes

    system = spec.system.build()
    eng = Engine(
        system,
        EngineConfig(n_replicas=4, swap_interval=5, chunk_intervals=4),
        observables=spec.system.observables(system, spec.observables),
        adapt=AdaptConfig(target=0.3, min_attempts_per_pair=2, max_rounds=2),
    )
    st = eng.init(jax.random.key(2), np.asarray(spec.ladder.temps))
    st, _ = eng.run(st, 100)
    eng.adapt = None
    st = eng.reset_stats(st)
    st, _ = eng.run(st, 50)
    np.testing.assert_array_equal(np.asarray(st.betas),
                                  np.asarray(result.state.betas))
    e = np.asarray(st.pt.energy)[np.argsort(np.asarray(st.pt.rung))]
    np.testing.assert_array_equal(e, result.final_energies())


def test_callback_order_and_payloads():
    events = []

    class Recorder(Callback):
        def on_phase_start(self, session, phase):
            events.append(("start", phase.name))

        def on_chunk(self, session, info):
            events.append(("chunk", info.index, info.sweeps_done))

        def on_phase_end(self, session, phase, result):
            events.append(("end", phase.name, result.n_sweeps))

    spec = tiny_ising_spec(schedule=ScheduleSpec(phases=(
        PhaseSpec(name="a", n_sweeps=40),  # 8 intervals = 2 chunks
        PhaseSpec(name="b", n_sweeps=20),  # 4 intervals = 1 chunk
    )))
    Session(spec, callbacks=[Recorder()]).run()
    assert events == [
        ("start", "a"), ("chunk", 1, 20), ("chunk", 2, 40), ("end", "a", 40),
        ("start", "b"), ("chunk", 1, 20), ("end", "b", 20),
    ]


def test_early_stop_callback():
    spec = tiny_ising_spec(schedule=ScheduleSpec(phases=(
        PhaseSpec(name="long", n_sweeps=200),
        PhaseSpec(name="never", n_sweeps=20),
    )))
    stop_after = EarlyStopCallback(lambda info: info.sweeps_done >= 40)
    result = Session(spec, callbacks=[stop_after]).run()
    assert result.stopped_early
    assert list(result.phases) == ["long"]
    assert result.phases["long"].stopped_early
    assert result.phases["long"].n_sweeps == 40
    assert int(np.asarray(result.state.pt.t)) == 40


def test_early_stop_on_final_chunk_still_skips_later_phases():
    """A stop request landing exactly on a phase's last chunk must not be
    silently dropped: the remaining phases stay skipped."""
    spec = tiny_ising_spec(schedule=ScheduleSpec(phases=(
        PhaseSpec(name="first", n_sweeps=20),  # exactly one chunk
        PhaseSpec(name="never", n_sweeps=20),
    )))
    result = Session(spec, callbacks=[EarlyStopCallback(lambda i: True)]).run()
    assert result.stopped_early
    assert list(result.phases) == ["first"]
    assert result.phases["first"].n_sweeps == 20  # budget completed...
    assert result.phases["first"].stopped_early  # ...but the stop registered


def test_trace_writer_streams_chunks(tmp_path):
    spec = tiny_ising_spec(
        engine=EngineSpec(swap_interval=5, chunk_intervals=4, record_trace=True),
        schedule=ScheduleSpec(phases=(PhaseSpec(name="m", n_sweeps=60),)),
    )
    reference = Session(spec).run()  # no consumer -> trace in the result
    result = Session(spec, callbacks=[TraceWriterCallback(tmp_path)]).run()
    # the writer consumes the stream, so the engine must NOT also buffer it
    assert result.phases["m"].trace is None
    assert reference.phases["m"].trace is not None
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 3  # 12 intervals = 3 chunks of 4
    streamed = np.concatenate(
        [np.load(tmp_path / f)["energy"] for f in files], axis=0
    )
    np.testing.assert_array_equal(streamed, reference.phases["m"].trace["energy"])


# ---------- resume from (spec, state) -------------------------------------------


@pytest.mark.parametrize("resume_from_step", [20, 40, 60, 80])
def test_checkpoint_resume_bit_equal(tmp_path, resume_from_step):
    """Resume from ANY checkpoint — including mid-adapt-phase ones, where the
    adaptation window baselines must come back from the step meta — and land
    on the exact same final state as the uninterrupted run."""
    spec = tiny_ising_spec(
        adapt=AdaptSpec(target=0.3, min_attempts_per_pair=2, max_rounds=2),
        schedule=ScheduleSpec(phases=(
            PhaseSpec(name="burn", n_sweeps=60, adapt=True),
            PhaseSpec(name="measure", n_sweeps=40, reset_stats=True),
        )),
    )
    ref = Session(spec).run()
    assert len(ref.phases["burn"].ladder_history) > 1  # adaptation did fire

    ckdir = tmp_path / "ck"
    full = Session(
        spec, callbacks=[CheckpointCallback(ckdir, every_chunks=1, keep=0)]
    ).run()
    np.testing.assert_array_equal(ref.final_energies(), full.final_energies())
    np.testing.assert_array_equal(np.asarray(ref.state.betas),
                                  np.asarray(full.state.betas))

    # Roll back to the chosen checkpoint and resume from the directory alone.
    import shutil

    mgr = CheckpointManager(str(ckdir), keep=0)
    steps = mgr.steps()
    assert resume_from_step in steps
    for s in steps:
        if s > resume_from_step:
            shutil.rmtree(mgr._step_dir(s))
    resumed = Session.from_checkpoint(str(ckdir)).run()
    np.testing.assert_array_equal(ref.final_energies(), resumed.final_energies())
    np.testing.assert_array_equal(np.asarray(ref.state.betas),
                                  np.asarray(resumed.state.betas))
    assert int(np.asarray(resumed.state.pt.t)) == 100


def test_checkpoint_meta_carries_exact_f64_ladder(tmp_path):
    """meta['temps'] must be the engine's authoritative f64 ladder, not the
    ulp-lossy 1/f32(betas) inversion — resumed retunes depend on it."""
    spec = tiny_ising_spec(
        adapt=AdaptSpec(target=0.3, min_attempts_per_pair=2, max_rounds=2),
        schedule=ScheduleSpec(phases=(
            PhaseSpec(name="burn", n_sweeps=60, adapt=True),
        )),
    )
    ckdir = tmp_path / "ck"
    session = Session(spec, callbacks=[CheckpointCallback(ckdir, keep=0)])
    session.run()
    mgr = CheckpointManager(str(ckdir), keep=0)
    _, meta = mgr.restore(mgr.steps()[-1], session.state)
    np.testing.assert_array_equal(
        np.asarray(meta["temps"], np.float64), session.engine._temps
    )
    assert "adapt_attempts_base" in meta and meta["adapt_rounds"] >= 1


def test_engine_reinit_resets_adaptation_window():
    """A re-init'd engine must adapt again: fresh states restart the swap
    counters at zero, so stale window baselines would starve the feedback."""
    system = systems.make_system("ising", {"length": 4, "accept_rule": "glauber"})
    eng = Engine(
        system,
        EngineConfig(n_replicas=4, swap_interval=5, chunk_intervals=2),
        adapt=AdaptConfig(target=0.3, min_attempts_per_pair=2),
    )
    temps = np.asarray([1.5, 2.2, 3.1, 4.4])
    st = eng.init(jax.random.key(0), temps)
    _, res1 = eng.run(st, 100)
    assert len(res1.ladder_history) > 1  # adaptation fired
    st2 = eng.init(jax.random.key(1), temps)
    _, res2 = eng.run(st2, 100)
    assert len(res2.ladder_history) > 1  # ...and fires again after re-init


def test_save_spec_load_spec_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.load_spec() is None
    spec = tiny_ising_spec()
    mgr.save_spec(spec.to_json())
    assert RunSpec.from_dict(mgr.load_spec()) == spec
    with pytest.raises(json.JSONDecodeError):
        mgr.save_spec("{not json")


def test_resume_without_spec_or_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="spec.json"):
        Session.from_checkpoint(str(tmp_path / "empty"))
    mgr = CheckpointManager(str(tmp_path / "speconly"))
    mgr.save_spec(tiny_ising_spec().to_json())
    with pytest.raises(FileNotFoundError, match="checkpoint"):
        Session.from_checkpoint(str(tmp_path / "speconly"))


# ---------- CLI -----------------------------------------------------------------


def test_cli_run_writes_manifest_and_reproduces_session(tmp_path, capsys):
    spec = tiny_ising_spec()
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(spec.to_json())
    out = tmp_path / "out"
    rc = cli_main(["run", str(spec_path), "--out", str(out), "--quiet"])
    assert rc == 0
    manifest = json.loads((out / "manifest.json").read_text())
    assert manifest["spec_version"] == 1
    assert RunSpec.from_dict(manifest["spec"]) == spec
    ref = Session(spec).run()
    np.testing.assert_array_equal(
        np.asarray(manifest["final"]["energy"]), ref.final_energies()
    )
    assert (out / "checkpoints" / "spec.json").exists()
    # manifest path printed on stdout (shell-composable)
    assert capsys.readouterr().out.strip().endswith("manifest.json")


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir_is_fixed_or_the_env_one(monkeypatch, tmp_path, env_dir):
    """Unset, the cache goes to <checkout>/.jax_cache; set, only there."""
    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(DEFAULT_DIR)
        assert DEFAULT_DIR == Path(__file__).resolve().parents[1] / ".jax_cache"
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        monkeypatch.setattr(jax.config, "update", pytest.fail)  # sets nothing
    try:
        assert enable_compile_cache() == want
        if env_dir is None:
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        monkeypatch.undo()
        jax.config.update("jax_compilation_cache_dir", before)


def test_cli_list_systems(capsys):
    assert cli_main(["list-systems"]) == 0
    out = capsys.readouterr().out
    for name in systems.CONSTRUCTORS:
        assert name in out


def test_cli_validate_unknown_system(capsys):
    assert cli_main(["validate", "not_a_system"]) == 2
