"""The reduction from a profiler trace to busy, idle, collective and
breakdown figures, and the metric readers on top of it."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.run import BENCH, load_module  # noqa: E402

MS = 1e6  # ns


def test_reduce_intervals_by_hand():
    # two chips, window 0..100 ms; chip 0 runs a fusion 0-40 and an
    # all-gather 30-50 (10 ms of it exposed); chip 1 a fusion 10-20 and an
    # all-gather 60-70 (all exposed); a loop holds its fusion.  The host is
    # in a step 40-100.
    ev = trace.Events(
        devices={0: [("fusion.1", 0 * MS, 40 * MS), ("all-gather.3", 30 * MS, 50 * MS)],
                 1: [("while.7", 10 * MS, 20 * MS), ("fusion.2", 10 * MS, 20 * MS),
                     ("all-gather.3", 60 * MS, 70 * MS)]},
        host=[("bench.window", 0, 100 * MS), ("bench.scheduler_step", 40 * MS, 100 * MS)])
    s = trace.reduce(ev)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx((0.05 + 0.02) / 2)
    assert s.collective_s == pytest.approx((0.02 + 0.01) / 2)
    assert s.exposed_collective_s == pytest.approx((0.01 + 0.01) / 2)
    assert trace.idle_share_pct(s) == pytest.approx(65.0)
    assert s.device_ops[0][0] == "fusion" and s.device_ops[0][1] == pytest.approx(0.025)
    assert "while" not in [name for name, _ in s.device_ops]
    # longest gap: chip 0 idle 50-100 ms, while the host was in its step
    assert s.idle_gaps[0] == ["bench.scheduler_step", pytest.approx(0.05)]


def test_reduce_clips_to_the_window():
    ev = trace.Events(devices={0: [("fusion", -50 * MS, 10 * MS), ("fusion", 90 * MS, 150 * MS)]},
                      host=[("bench.window", 0, 100 * MS)])
    s = trace.reduce(ev)
    assert s.busy_s == pytest.approx(0.02)


def test_metric_readers_on_a_reduced_window():
    ev = trace.Events(devices={0: [("fusion", 0, 75 * MS)]},
                      host=[("bench.window", 0, 100 * MS)])
    s = trace.reduce(ev)
    record = {"flips": 3e9, "chips": 1}
    rate = load_module(BENCH / "metrics" / "sweep_flips_per_busy_s.py")
    idle = load_module(BENCH / "metrics" / "device_idle_share.sample.py")
    assert rate.read(s, record, {}) == pytest.approx(4e10)
    assert idle.read(s, record, {}) == pytest.approx(25.0)
    assert rate.read(None, record, {}) is None and idle.read(None, record, {}) is None


def test_recorded_chip_trace_reduces_to_known_figures():
    """A 5 s window of ``paper.fused`` traced on one v5e chip: six chunks of
    the interval-fused kernel, one copy of the state per chunk boundary."""
    path = Path(__file__).with_name("data") / "paper_fused_v5e.xplane.pb"
    ev = trace.load(str(path))
    assert sorted(ev.devices) == [0] and len(ev.devices[0]) == 531
    s = trace.reduce(ev)
    assert s.chips == 1
    assert s.window_s == pytest.approx(6.036817179, rel=1e-9)
    assert s.busy_s == pytest.approx(6.02908952, rel=1e-9)
    assert s.collective_s == 0.0 and s.exposed_collective_s == 0.0
    assert trace.idle_share_pct(s) == pytest.approx(0.12800882, rel=1e-6)
    assert s.device_ops[0] == ["ising_sweep_fused", pytest.approx(6.01842436, rel=1e-9)]
    assert s.device_ops[1][0] == "copy"
    assert s.idle_gaps[0] == ["program", pytest.approx(0.007604206, rel=1e-6)]
    assert s.idle_gaps[1][0] == "bench.chunk_boundary"
