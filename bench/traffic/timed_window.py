"""The timed window that every traffic kind driven by `repro.api.Session`
runs.  It is not a traffic kind: a kind builds its `RunSpec` with
`schedule`, hands it to `run_window`, and compares what comes back with its
configuration's plain reference.

The schedule is a warm-up phase of one chunk and a window phase longer than
any run, which a callback ends once ``--seconds`` have passed.  The window
opens when the warm-up chunk has finished on the device and closes when the
chunk that was running at the deadline has finished: it is a whole number
of chunks, ended by ``block_until_ready``.  The host is held at most one
chunk ahead of the device, so the device always has the next chunk queued.

At each chunk boundary the callback copies the `PTState` fields the kind
names (the engine donates its state to the next chunk), every leaf of a
pytree field included, so that the last chunk of the window can be
replayed by the reference from the state it started from.  The initial
state is copied too, before the warm-up.  Kernels are strict: a kernel that
cannot run on the chip fails the run instead of degrading.

Host spans, in a traced run: ``bench.chunk_boundary`` around the callback's
work at each boundary, holding ``bench.chunk_boundary.wait`` (the block on
the previous chunk) and ``bench.chunk_boundary.copy`` (the copies for the
replay); ``bench.window_close`` around the block on the last chunk.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.device import free_device_memory, memory_peak_bytes

WINDOW_SWEEPS = 100 * 10**9  # longer than any run; the callback ends it
WARM, WINDOW = "warm", "window"


@dataclasses.dataclass
class WindowRun:
    """What a window leaves its kind, with the program's state freed.

    ``start``, ``before`` and ``after`` are host copies of the named
    `PTState` fields: before the warm-up, at the start of the window's last
    chunk, and at its end.
    """

    start: dict
    before: dict
    after: dict
    setup_s: float  # process start to window open
    window_s: float
    sweeps: int  # sweeps done in the window, per chain
    memory_peak_bytes: int  # on the fullest chip
    degraded: bool  # the engine fell back from a kernel


def schedule(chunk_sweeps: int):
    """The `ScheduleSpec` that `run_window` drives: one warm-up chunk, then
    the window."""
    from repro.api import PhaseSpec, ScheduleSpec

    return ScheduleSpec(phases=(PhaseSpec(WARM, chunk_sweeps),
                                PhaseSpec(WINDOW, WINDOW_SWEEPS)))


def _copy(pt, keys):
    """Device copies of the fields a replay needs (the engine donates them)."""
    import jax
    import jax.numpy as jnp

    return {k: jax.tree_util.tree_map(jnp.copy, getattr(pt, k)) for k in keys}


def _host(tree):
    import jax

    return jax.tree_util.tree_map(np.asarray, tree)


def run_window(ctx, spec, copy_keys) -> WindowRun:
    """Run ``spec`` (scheduled by `schedule`) through one timed window.

    ``copy_keys`` names the `PTState` fields to copy, ``"t"`` among them:
    the callback blocks on it and counts the window's sweeps with it.
    """
    import jax

    from repro.api import Session
    from repro.api.session import Callback

    keys = tuple(copy_keys)
    tracer = ctx.tracer

    class Window(Callback):
        start = before = pending = None
        t0 = sweep0 = None

        def on_phase_start(self, session, phase):
            if phase.name == WARM:
                self.start = _copy(session.state.pt, keys)
                return
            jax.block_until_ready(session.state)
            self.sweep0 = int(np.asarray(session.state.pt.t).reshape(-1)[0])
            self.pending = _copy(session.state.pt, keys)
            jax.block_until_ready(self.pending)
            tracer.start()
            self.t0 = time.perf_counter()

        def on_chunk(self, session, info):
            if session.current_phase.name != WINDOW:
                return False
            with tracer.span("bench.chunk_boundary"):
                self.before = self.pending  # the state this chunk started from
                with tracer.span("bench.chunk_boundary.wait"):
                    jax.block_until_ready(self.before["t"])  # the previous chunk is done
                if time.perf_counter() - self.t0 >= ctx.seconds:
                    return True
                with tracer.span("bench.chunk_boundary.copy"):
                    self.pending = _copy(info.state.pt, keys)
                return False

    window = Window()
    session = Session(spec, callbacks=[window], strict_kernels=True)
    result = session.run()
    final = result.state
    with tracer.span("bench.window_close"):
        jax.block_until_ready(final)
    t_end = time.perf_counter()
    tracer.stop()
    window_s = t_end - window.t0
    setup_s = window.t0 - ctx.process_t0

    devices = sorted({d for leaf in jax.tree_util.tree_leaves(final.pt.states)
                      for d in leaf.devices()}, key=lambda d: d.id)
    peak = memory_peak_bytes(devices)
    start, before = _host(window.start), _host(window.before)
    after = _host({k: getattr(final.pt, k) for k in keys})
    degraded = bool(session.engine._degraded)
    sweeps = int(after["t"].reshape(-1)[0]) - window.sweep0
    del session, result, final, window
    free_device_memory()
    return WindowRun(start=start, before=before, after=after, setup_s=setup_s,
                     window_s=window_s, sweeps=sweeps, memory_peak_bytes=peak,
                     degraded=degraded)
