"""The Pallas kernels compile for a TPU v5e, at the paper's L=300.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Interpret-mode tests cannot see what Mosaic refuses (block
shapes, casts, layouts, VMEM), so each kernel of the main path is compiled
here with ``interpret=False`` and must lower to a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ising_sweep as isk
from repro.kernels import potts_sweep as psk

L = 300  # the paper's lattice
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


def _kernel_cases(spec):
    """(function, argument shapes) of each main-path kernel at L=300."""
    i8, u32, i32, f32 = jnp.int8, jnp.uint32, jnp.int32, jnp.float32
    fused_args = lambda r: [
        spec((r, L, L), i8), spec((r,), f32), spec((2,), u32),
        spec((1,), u32), spec((1,), u32),
    ]
    round_args = lambda r: [
        spec((r, L, L), i8), spec((2,), u32), spec((1,), u32),
        spec((1,), i32), spec((r,), i32), spec((r,), f32), spec((r,), f32),
    ]
    return {
        "ising_sweep": (
            lambda s, u, b: isk.ising_sweep_pallas(
                s, u, b, r_blk=8, interpret=False),
            [spec((16, L, L), i8), spec((16, 2, L, L), f32), spec((16,), f32)],
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
            [spec((8, L, L), i8), spec((8, 2, 2, L, L), f32), spec((8,), f32)],
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


def test_ensemble_fused_kernel_compiles_for_v5e(spec):
    """The engine vmaps the kernel over a chain ensemble (and serve packs
    jobs that way): the batched SMEM scalars must still lower."""
    fn, args = _kernel_cases(spec)["ising_fused"]
    batched = [spec((2, *a.shape), a.dtype) for a in args]
    compiled = jax.jit(jax.vmap(fn)).lower(*batched).compile()
    assert "tpu_custom_call" in compiled.as_text()
