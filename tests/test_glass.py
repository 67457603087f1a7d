"""The ±J Edwards-Anderson glass over disorder samples (``ea_glass``):
per-chain quenched bonds through the engine, bit for bit against the plain
reference of the benchmark (``bench/reference/glass3d_pt.py``)."""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (EngineSpec, LadderSpec, PhaseSpec, RunSpec, ScheduleSpec,
                       Session, SystemSpec)
from repro.checkpoint.manager import CheckpointManager
from repro.core import pt
from repro.core.distributed import CHAIN_AXIS, pt_partition_specs
from repro.core.spin_glass import EAGlass
from repro.engine import Engine, EngineConfig
from repro.serve import Scheduler, check_servable
from repro.serve.job import JobFailedError
from repro.validate import exact as ex

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import glass3d_pt as ref  # noqa: E402

SEED = 2**31 + 7
DISORDER = 11
SAMPLES, COPIES, R, INTERVAL = 2, 2, 4, 5
LADDER = {"kind": "linear", "t_min": 0.7, "t_max": 1.6}


def glass_spec(shape, *, n_chains=SAMPLES * COPIES, intervals=3, seed=SEED, **engine):
    return RunSpec(
        system=SystemSpec("ea_glass", {"shape": shape, "disorder_seed": DISORDER,
                                       "copies": COPIES, "accept_rule": "glauber"}),
        ladder=LadderSpec(n_replicas=R, **LADDER),
        engine=EngineSpec(**{"swap_interval": INTERVAL, "chunk_intervals": 1,
                             "criterion": "metropolis", "n_chains": n_chains, **engine}),
        schedule=ScheduleSpec(phases=(PhaseSpec("run", intervals * INTERVAL),)),
        observables=(),
        seed=seed,
    )


def host(st):
    """A `PTState`'s fields but the key, as numpy."""
    return types.SimpleNamespace(**{k: np.asarray(getattr(st, k)) for k in (
        "states", "energy", "rung", "t", "phase", "quenched")})


def run_session(spec):
    """(initial, final) `PTState` fields of a Session run, and its system."""
    session = Session(spec)
    session.state = session.init_state()
    start = host(session.state.pt)
    return start, host(session.run().state.pt), session.system


def plain(system, x):
    return np.asarray(system.plain(jnp.asarray(x)))


# -- the lattice and its layout ----------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 4, 4), (6, 4), (4, 6, 8)])
def test_lane_dense_neighbours_are_the_plain_lattice_rolled(shape):
    system = EAGlass(shape=shape)
    a = jax.random.randint(jax.random.key(0), (3, *system.layout), -50, 50).astype(jnp.int8)
    for d in range(len(shape)):
        for step in (1, -1):
            got = plain(system, system._shift(a, d, step))
            want = np.roll(plain(system, a), -step, axis=d + 1)
            np.testing.assert_array_equal(got, want, err_msg=f"d={d} step={step}")


@pytest.mark.parametrize("shape", [(3, 4), (4, 4, 5), (4,), (2, 2, 2, 2)])
def test_shape_must_be_two_or_three_even_dimensions(shape):
    with pytest.raises(ValueError, match="even"):
        EAGlass(shape=shape)


def test_bonds_are_held_once_per_chain_in_int8():
    eng = Engine(EAGlass(shape=(4, 4, 4), copies=COPIES),
                 EngineConfig(n_replicas=R, swap_interval=INTERVAL, n_chains=4))
    st = eng.init(jax.random.key(0), np.linspace(1.0, 2.0, R))
    assert st.pt.quenched.dtype == jnp.int8 and st.pt.quenched.shape == (4, 3, 4, 16)
    assert st.pt.states.dtype == jnp.int8 and st.pt.states.shape == (4, R, 4, 16)
    specs = pt_partition_specs(st.pt, n_chains=4)
    assert tuple(specs.quenched) == (CHAIN_AXIS,)  # no replica axis to shard


def test_copies_of_a_sample_share_bonds_and_samples_differ():
    system = EAGlass(shape=(4, 4, 4), disorder_seed=DISORDER, copies=COPIES)
    bonds = np.asarray(jax.vmap(system.init_quenched)(jnp.arange(6, dtype=jnp.uint32)))
    for c in range(6):
        np.testing.assert_array_equal(bonds[c], bonds[c - c % COPIES])
    assert not np.array_equal(bonds[0], bonds[2]) and not np.array_equal(bonds[2], bonds[4])
    assert set(np.unique(bonds)) == {-1, 1}
    want = ref.chain_bonds(DISORDER, 6, COPIES, (4, 4, 4))
    np.testing.assert_array_equal(plain(system, bonds), np.asarray(want))


# -- the whole path against the plain reference ------------------------------------

@pytest.mark.parametrize("shape", [(4, 4, 4), (6, 4)])
def test_session_is_bit_equal_to_the_plain_reference(shape):
    start, end, system = run_session(glass_spec(shape))
    c = SAMPLES * COPIES
    bonds = ref.chain_bonds(DISORDER, c, COPIES, shape)
    s0, keys = ref.init_chains(SEED, c, R, shape)
    np.testing.assert_array_equal(plain(system, start.quenched), np.asarray(bonds))
    np.testing.assert_array_equal(plain(system, start.states), np.asarray(s0))
    e0 = np.asarray(ref.energy(s0, bonds))
    np.testing.assert_array_equal(start.energy, e0)
    betas = jnp.asarray(ref.ladder_betas(LADDER, R))
    want = ref.advance(s0, jnp.asarray(e0), jnp.asarray(start.rung), jnp.asarray(start.t),
                       jnp.asarray(start.phase), keys, betas, bonds, n_intervals=3,
                       sweeps_per_interval=INTERVAL, rule="glauber",
                       criterion="metropolis", block=2)
    np.testing.assert_array_equal(plain(system, end.states), np.asarray(want[0]))
    np.testing.assert_array_equal(end.energy, np.asarray(want[1]))
    np.testing.assert_array_equal(end.rung, np.asarray(want[2]))
    np.testing.assert_array_equal(end.t, np.asarray(want[3]))
    np.testing.assert_array_equal(end.energy, np.asarray(ref.energy(want[0], bonds)))
    assert not np.array_equal(end.states, start.states)
    assert not np.array_equal(end.rung, start.rung)  # the exchange ran


def test_chain_is_bit_equal_in_any_ensemble_size():
    _, four, _ = run_session(glass_spec((4, 4, 4), n_chains=4))
    _, eight, _ = run_session(glass_spec((4, 4, 4), n_chains=8))
    for field in ("states", "energy", "rung", "quenched"):
        np.testing.assert_array_equal(getattr(four, field), getattr(eight, field)[:4],
                                      err_msg=field)


def test_monolithic_run_holds_chain_zero_bonds():
    system = EAGlass(shape=(6, 4), disorder_seed=DISORDER, copies=COPIES)
    cfg = pt.PTConfig(n_replicas=R, temps=(0.7, 1.0, 1.3, 1.6), swap_interval=INTERVAL,
                      criterion="metropolis")
    st = pt.init(system, cfg, jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(st.quenched),
                                  np.asarray(system.init_quenched(0)))
    eng = Engine(system, EngineConfig(n_replicas=R, swap_interval=INTERVAL,
                                      criterion="metropolis", chunk_intervals=2,
                                      donate=False))
    est = eng.init(jax.random.key(3), cfg.temps)
    want, _ = eng.run(est, 2 * INTERVAL)
    got, _ = pt.run(system, cfg, st, 2 * INTERVAL)
    np.testing.assert_array_equal(np.asarray(got.states), np.asarray(want.pt.states))
    np.testing.assert_array_equal(np.asarray(got.energy), np.asarray(want.pt.energy))


# -- quenched data through the rest of the engine ----------------------------------

def test_state_mode_swap_leaves_bonds_in_place():
    start, end, system = run_session(glass_spec((4, 4, 4), swap_mode="state",
                                                intervals=6))
    np.testing.assert_array_equal(end.quenched, start.quenched)
    np.testing.assert_array_equal(end.rung, start.rung)  # rungs pinned to slots
    e = np.asarray(jax.vmap(jax.vmap(system.energy, in_axes=(0, None)))(
        jnp.asarray(end.states), jnp.asarray(end.quenched)))
    np.testing.assert_array_equal(end.energy, e)


def test_checkpoint_round_trip_restores_bonds(tmp_path):
    system = EAGlass(shape=(4, 4, 4), disorder_seed=DISORDER, copies=COPIES)
    cfg = EngineConfig(n_replicas=R, swap_interval=INTERVAL, n_chains=4,
                       chunk_intervals=1, donate=False)
    eng = Engine(system, cfg)
    st = eng.init(jax.random.key(5), np.linspace(0.7, 1.6, R))
    st, _ = eng.run(st, 2 * INTERVAL)
    manager = CheckpointManager(str(tmp_path))
    manager.save(2 * INTERVAL, st)
    restored, _ = Engine(system, cfg).restore(manager)
    for got, want in zip(jax.tree_util.tree_leaves(restored.pt),
                         jax.tree_util.tree_leaves(st.pt)):
        if jax.dtypes.issubdtype(want.dtype, jax.dtypes.prng_key):
            got, want = jax.random.key_data(got), jax.random.key_data(want)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(restored.pt.quenched),
                                  np.asarray(st.pt.quenched))
    assert restored.pt.quenched.dtype == jnp.int8


def test_ensemble_split_over_four_devices_is_bit_equal_to_one(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    child = Path(__file__).with_name("_glass_mesh_child.py")
    proc = subprocess.run([sys.executable, str(child), str(tmp_path)], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = np.load(tmp_path / "glass_mesh.npz")
    for mesh in json.loads(proc.stdout.strip().splitlines()[-1])["meshes"]:
        for field in ("states", "energy", "rung", "quenched"):
            np.testing.assert_array_equal(got[f"{mesh}_{field}"], got[f"one_{field}"],
                                          err_msg=f"{mesh} {field}")


def test_serve_refuses_ea_glass():
    spec = glass_spec((4, 4, 4), n_chains=1)
    with pytest.raises(ValueError, match="quenched"):
        check_servable(spec)
    sched = Scheduler()
    job = sched.submit(spec)
    sched.run_until_idle()
    with pytest.raises(JobFailedError, match="quenched"):
        job.result(timeout=5)


# -- exact enumeration ---------------------------------------------------------------

def test_exact_reference_matches_the_systems_energy():
    system = EAGlass(shape=(2, 2, 4), disorder_seed=1, copies=2)
    temps = np.array([0.8, 1.5, 3.0])
    configs = ex._spin_configs(16).reshape(-1, *system.layout)
    e = np.asarray(jax.vmap(system.energy, in_axes=(0, None))(
        jnp.asarray(configs), system.init_quenched(0)))
    absm = np.abs(configs.reshape(-1, 16).mean(axis=1))
    want = ex.boltzmann_means(e, {"absmag": absm}, temps)
    got = ex.ea_glass_exact(system, temps)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-10, err_msg=k)
