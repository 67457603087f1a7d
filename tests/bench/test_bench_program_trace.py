"""The program's marks in a profiler trace (`bench.program_trace`) and the
per-layer metrics that read them: the recorded v5e trace, a trace encoded
here with the program's scopes, a profile of a small engine run on the CPU,
and intervals written by hand."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program_trace as pt  # noqa: E402
from bench import trace  # noqa: E402
from bench.run import BENCH, load_module  # noqa: E402

MS = 1e6  # ns
RECORDED = Path(__file__).with_name("data") / "paper_fused_v5e.xplane.pb"
ENGINE, CALLBACKS = "repro.engine.", "repro.engine.callbacks"


def _trace(devices, host):
    return pt.ProgramTrace(devices=devices, host=host)


def test_recorded_chip_trace_decodes_like_profile_data():
    """The decoder reads the same ops, times and harness spans as
    ``jax.profiler.ProfileData`` does, and each op's ``op_name`` path."""
    got = pt.load(str(RECORDED))
    ref = trace.load(str(RECORDED))
    assert sorted(got.devices) == [0] and len(got.devices[0]) == 531
    ours = sorted((n, a, b) for n, _, a, b in got.devices[0])
    theirs = sorted(ref.devices[0])
    assert [n for n, _, _ in ours] == [n for n, _, _ in theirs]
    assert max(abs(x[1] - y[1]) + abs(x[2] - y[2]) for x, y in zip(ours, theirs)) < 5
    bench_spans = sorted((n, a, b) for n, _, a, b in got.host if n.startswith("bench."))
    assert [n for n, _, _ in bench_spans] == [n for n, _, _ in sorted(ref.host)]
    paths = {s for _, s, _, _ in got.devices[0]}
    assert "jit(mega)/while/body/closed_call/jit(ising_sweep_fused)/pallas_call:" in paths
    lo, hi = pt.window(got)
    assert (hi - lo) * 1e-9 == pytest.approx(trace.reduce(ref).window_s, rel=1e-9)


def test_recorded_trace_of_a_program_without_marks_reads_none():
    """The trace was recorded before the program named its scopes and host
    spans: every new metric reads nothing there, and none raises."""
    got = pt.load(str(RECORDED))
    assert all(pt.scopes(s) == () for _, s, _, _ in got.devices[0])
    assert pt.scope_split(got) is None
    assert pt.device_share(got, "pt.exchange") is None
    assert pt.device_share(got, "pt.rng") is None
    assert pt.idle_in_spans(got, ENGINE, CALLBACKS) is None


def test_scopes_of_an_op_name_path():
    path = "jit(mega)/while/body/pt.sweep/pt.rng/jit(_uniform)/threefry2x32:"
    assert pt.scopes(path) == ("pt.sweep", "pt.rng")
    assert pt.scopes("jit(mega)/while/body/pt.exchange/sort:sort") == ("pt.exchange",)
    assert pt.scopes("pt_st.states:") == ()
    assert pt.scopes("") == ()


def _encode_space(path):
    """A small XSpace in the profiler's layout: one TPU plane whose ops carry
    ``tf_op`` paths (one as a string, one by reference, one in a ``while``
    that encloses its body) and a host line with the window and engine
    spans."""
    space = pt._schema()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "pt.sweep/x:"), (3, "flops")):
        dev.stat_metadata.add(key=key).value.name = name
    ops = {  # metadata id -> (hlo text, tf_op string or ref id)
        10: ("%fusion.1 = f32[8] fusion(), calls=%f", "jit(mega)/while/body/pt.sweep/pt.rng/add:"),
        11: ("%sort.2 = s32[8] sort(%a)", 2),
        12: ("%while.3 = (s32[]) while(%t)", "jit(mega)/while:"),
        13: ("%gather.4 = f32[8] gather(%a, %b)", "jit(mega)/while/body/pt.exchange/gather:"),
    }
    for mid, (text, tf_op) in ops.items():
        md = dev.event_metadata.add(key=mid).value
        md.id, md.name = mid, text
        md.stats.add(metadata_id=3, int64_value=7)
        stat = md.stats.add(metadata_id=1)
        if isinstance(tf_op, int):
            stat.ref_value = tf_op
        else:
            stat.str_value = tf_op
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, a, b in ((12, 0, 60), (10, 0, 30), (11, 30, 40), (13, 50, 60)):
        line.events.add(metadata_id=mid, offset_ps=int(a * MS * 1e3),
                        duration_ps=int((b - a) * MS * 1e3))
    host = space.planes.add(name="/host:CPU")
    for mid, name in ((1, "bench.window"), (2, "repro.engine.chunk"),
                      (3, "repro.engine.callbacks"), (4, "other")):
        md = host.event_metadata.add(key=mid).value
        md.id, md.name = mid, name
    hl = host.lines.add(name="python3", timestamp_ns=1000)
    for mid, a, b in ((1, 0, 100), (2, 35, 100), (3, 70, 90), (4, 0, 100)):
        hl.events.add(metadata_id=mid, offset_ps=int(a * MS * 1e3),
                      duration_ps=int((b - a) * MS * 1e3))
    Path(path).write_bytes(space.SerializeToString())


def test_encoded_trace_with_scopes(tmp_path):
    path = tmp_path / "t.xplane.pb"
    _encode_space(path)
    got = pt.load(str(path))
    by_name = {n: s for n, s, _, _ in got.devices[0]}
    assert by_name == {"fusion.1": "jit(mega)/while/body/pt.sweep/pt.rng/add:",
                       "sort.2": "pt.sweep/x:", "while.3": "jit(mega)/while:",
                       "gather.4": "jit(mega)/while/body/pt.exchange/gather:"}
    assert {n for n, *_ in got.host} == {"bench.window", "repro.engine.chunk",
                                         "repro.engine.callbacks"}
    # busy 0-60 ms, the loop's span; the loop is no scope's time (its
    # body's ops are events of their own), so 40-50 ms stays unscoped
    assert pt.device_share(got, "pt.exchange") == pytest.approx(100 / 6)
    assert pt.device_share(got, "pt.rng") == pytest.approx(50.0)
    assert pt.device_share(got, "pt.sweep") == pytest.approx(400 / 6)
    assert pt.scope_split(got) == {"pt.rng": pytest.approx(50.0),
                                   "pt.sweep": pytest.approx(100 / 6),
                                   "pt.exchange": pytest.approx(100 / 6)}
    # device idle 60-100 ms; the host is in the engine 35-70 and 90-100
    assert pt.idle_in_spans(got, ENGINE, CALLBACKS) == pytest.approx(20.0)


def test_a_loop_that_encloses_its_body_counts_once():
    ev = {0: [("while.1", "jit(m)/while:", 0, 100 * MS),
              ("fusion.2", "jit(m)/while/body/pt.exchange/a:", 10 * MS, 30 * MS),
              ("fusion.3", "jit(m)/while/body/pt.exchange/b:", 20 * MS, 40 * MS),
              ("fusion.4", "jit(m)/while/body/pt.sweep/c:", 40 * MS, 100 * MS)]}
    t = _trace(ev, [("bench.window", "main", 0, 100 * MS)])
    assert pt.device_share(t, "pt.exchange") == pytest.approx(30.0)
    split = pt.scope_split(t)
    assert split == {"pt.exchange": pytest.approx(30.0), "pt.sweep": pytest.approx(60.0)}
    assert sum(split.values()) <= 100.0


def test_shares_sum_over_chips_and_idle_is_a_mean_over_chips():
    """Two chips, window 0-100 ms.  Chip 0 busy 0-100 (exchange 0-10), chip
    1 busy 0-50 (exchange 40-50).  The window thread is in a dispatch span
    40-80; another thread's engine span does not count."""
    ev = {0: [("fusion", "pt.sweep/a:", 10 * MS, 100 * MS),
              ("all-gather", "pt.exchange/g:", 0, 10 * MS)],
          1: [("fusion", "pt.sweep/a:", 0, 40 * MS),
              ("all-gather", "pt.exchange/g:", 40 * MS, 50 * MS)]}
    host = [("bench.window", "main", 0, 100 * MS),
            ("repro.engine.dispatch", "main", 40 * MS, 80 * MS),
            ("repro.engine.chunk", "other", 0, 100 * MS)]
    t = _trace(ev, host)
    assert pt.device_share(t, "pt.exchange") == pytest.approx(100.0 * 20 / 150)
    # chip 0 never idles; chip 1 idles 50-80 inside the span: 30 ms / 2 chips
    assert pt.idle_in_spans(t, ENGINE, CALLBACKS) == pytest.approx(15.0)


def test_ops_and_spans_are_clipped_to_the_window():
    ev = {0: [("fusion", "pt.rng/a:", -50 * MS, 20 * MS),
              ("fusion", "pt.sweep/b:", 20 * MS, 200 * MS)]}
    t = _trace(ev, [("bench.window", "main", 0, 100 * MS),
                    ("repro.engine.chunk", "main", -10 * MS, 150 * MS)])
    assert pt.device_share(t, "pt.rng") == pytest.approx(20.0)
    assert pt.idle_in_spans(t, ENGINE, CALLBACKS) == pytest.approx(0.0)


@pytest.mark.parametrize("metric, expect", [
    ("idle_in_engine_share", 20.0),
    ("exchange_device_share", 100 / 6),
    ("rng_device_share", 50.0),
])
def test_metric_readers(tmp_path, monkeypatch, metric, expect):
    """Each reader takes the newest profile under the trace directory and
    reads nothing without a trace or without a profile."""
    mod = load_module(BENCH / "metrics" / f"{metric}.py")
    summary = object()  # a traced run's summary; the readers only test it for None
    monkeypatch.setattr(pt, "TRACE_ROOT", tmp_path)
    assert mod.read(summary, {}, {}) is None
    target = tmp_path / "cell" / "plugins"
    target.mkdir(parents=True)
    _encode_space(target / "host.xplane.pb")
    assert mod.read(summary, {}, {}) == pytest.approx(expect)
    assert mod.read(None, {}, {}) is None


def test_engine_spans_of_a_cpu_profile(tmp_path):
    """A small engine run profiled on the CPU: the decoder finds the
    window and the engine's spans on one host thread, with the same names
    and times as ``ProfileData``."""
    import jax
    import numpy as np

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import ising
    from repro.engine import Engine, EngineConfig

    eng = Engine(ising.IsingSystem(length=4),
                 EngineConfig(n_replicas=4, swap_interval=2, chunk_intervals=2))
    st = eng.init(jax.random.key(0), np.linspace(1.5, 3.5, 4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.run(st, 8, on_chunk=lambda info: False)
    finally:
        jax.profiler.stop_trace()
    got = pt.load(pt.find_newest(tmp_path))
    names = [n for n, *_ in got.host]
    assert names.count("repro.engine.chunk") == 2
    assert names.count("repro.engine.callbacks") == 2
    window_thread = next(t for n, t, _, _ in got.host if n == "bench.window")
    assert {t for n, t, _, _ in got.host if n.startswith(ENGINE)} == {window_thread}
    ref = trace.load(pt.find_newest(tmp_path))
    ours = sorted((a, b) for n, _, a, b in got.host if n == "bench.window")
    assert ours == pytest.approx(sorted((a, b) for _, a, b in ref.host), abs=5)
