"""The Pallas kernels compile for a TPU v5e, at the paper's L=300, and the
3-D glass's mega-step fits one v5e at the Janus size.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Interpret-mode tests cannot see what Mosaic refuses (block
shapes, casts, layouts, VMEM), so each kernel of the main path is compiled
here with ``interpret=False`` and must lower to a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ising_sweep as isk
from repro.kernels import potts_sweep as psk

L = 300  # the paper's lattice
L_2_MOD_4 = 298  # odd colour half-plane height: L/2 = 149
Q = 3
VMEM_MODEL_BUDGET = 16 * 2**20  # the models' v5e budget (tests/test_kernels.py)


def _largest_ladder(model) -> int:
    """Largest R whose whole ladder fits one round-kernel grid step."""
    r = 1
    while model(r + 1) <= VMEM_MODEL_BUDGET:
        r += 1
    return r


R_ROUND_ISING = _largest_ladder(lambda r: isk.vmem_working_set_bytes_fused(r, L))
R_ROUND_POTTS = _largest_ladder(
    lambda r: psk.vmem_working_set_bytes_fused(r, L, L)
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    """Argument shapes placed on one described chip."""
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _kernel_cases(spec, length=L):
    """(function, argument shapes) of each main-path kernel at side ``length``."""
    i8, u32, i32, f32 = jnp.int8, jnp.uint32, jnp.int32, jnp.float32
    fused_args = lambda r: [
        spec((r, length, length), i8), spec((r,), f32), spec((2,), u32),
        spec((1,), u32), spec((1,), u32),
    ]
    round_args = lambda r: [
        spec((r, length, length), i8), spec((2,), u32), spec((1,), u32),
        spec((1,), i32), spec((r,), i32), spec((r,), f32), spec((r,), f32),
    ]
    return {
        "ising_sweep": (
            lambda s, u, b: isk.ising_sweep_pallas(
                s, u, b, r_blk=8, interpret=False),
            [
                spec((16, length, length), i8),
                spec((16, 2, length, length), f32), spec((16,), f32),
            ],
        ),
        "ising_fused": (
            lambda s, b, kw, t0, off: isk.ising_sweep_fused_pallas(
                s, kw, t0, b, n_sweeps=100, replica_offset=off, r_blk=8,
                interpret=False),
            fused_args(16),
        ),
        "ising_round": (
            lambda s, kw, t0, ph, rung, e, b: isk.ising_round_fused_pallas(
                s, kw, t0, ph, rung, e, b, n_sweeps=100, interpret=False),
            round_args(R_ROUND_ISING),
        ),
        "potts_sweep": (
            lambda s, u, b: psk.potts_sweep_pallas(
                s, u, b, q=Q, r_blk=4, interpret=False),
            [
                spec((8, length, length), i8),
                spec((8, 2, 2, length, length), f32), spec((8,), f32),
            ],
        ),
        "potts_fused": (
            lambda s, b, kw, t0, off: psk.potts_sweep_fused_pallas(
                s, kw, t0, b, n_sweeps=100, q=Q, replica_offset=off, r_blk=4,
                interpret=False),
            fused_args(8),
        ),
        "potts_round": (
            lambda s, kw, t0, ph, rung, e, b: psk.potts_round_fused_pallas(
                s, kw, t0, ph, rung, e, b, n_sweeps=100, q=Q, interpret=False),
            round_args(R_ROUND_POTTS),
        ),
    }


KERNELS = [
    "ising_sweep", "ising_fused", "ising_round",
    "potts_sweep", "potts_fused", "potts_round",
]


def test_round_ladders_follow_the_vmem_model():
    assert (R_ROUND_ISING, R_ROUND_POTTS) == (10, 8)


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(spec, name):
    fn, args = _kernel_cases(spec)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("length", [L, L_2_MOD_4])
@pytest.mark.parametrize("name,op", [
    ("ising_fused", "ising_sweep_fused_split"),
    ("ising_round", "ising_round_fused_split"),
])
def test_colour_split_kernel_compiles_for_v5e(spec, name, op, length):
    """The colour-split Ising launches compile at L ≡ 0 and L ≡ 2 (mod 4),
    and a profile names their operation after the layout."""
    fn, args = _kernel_cases(spec, length)[name]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert f"%{op}" in text


def test_ensemble_fused_kernel_compiles_for_v5e(spec):
    """The engine vmaps the kernel over a chain ensemble (and serve packs
    jobs that way): the batched SMEM scalars must still lower."""
    fn, args = _kernel_cases(spec)["ising_fused"]
    batched = [spec((2, *a.shape), a.dtype) for a in args]
    compiled = jax.jit(jax.vmap(fn)).lower(*batched).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_glass_mega_step_fits_one_v5e(one_chip):
    """The ``glass3d_janus`` deployment (bench/configs): 256 chains × 34
    rungs of 32³ spins.  Lane-dense lattices and bonds held once per chain
    keep the compiled chunk's arguments and temporaries far inside 16 GB."""
    from repro.core.spin_glass import EAGlass
    from repro.engine import Engine, EngineConfig

    dep = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                      / "glass3d_janus.json").read_text())
    chains = dep["n_samples"] * dep["copies"]
    eng = Engine(EAGlass(shape=tuple(dep["shape"]), copies=dep["copies"]),
                 EngineConfig(n_replicas=dep["n_replicas"], swap_interval=dep["swap_interval"],
                              criterion=dep["criterion"], chunk_intervals=1, n_chains=chains))
    temps = np.linspace(dep["ladder"]["t_min"], dep["ladder"]["t_max"], dep["n_replicas"])
    shapes = jax.eval_shape(lambda k: eng._fresh_state(k, temps), jax.random.key(0))
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    assert shapes.pt.quenched.shape == (chains, 3, 32, 32 * 32)
    assert shapes.pt.quenched.dtype == jnp.int8
    mem = jax.jit(eng._make_mega(1, shapes), donate_argnums=(0, 1)).lower(
        on_chip(shapes.pt), on_chip(shapes.stats), on_chip(shapes.betas)
    ).compile().memory_analysis()
    sites = chains * dep["n_replicas"] * 32**3
    assert mem.argument_size_in_bytes < 1.2 * sites  # 1 B of spins a site, no padding
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 * 10**9
