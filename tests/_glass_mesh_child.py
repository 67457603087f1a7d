"""Child process of ``tests/test_glass.py``: the ``ea_glass`` ensemble on four
virtual CPU devices.

``--xla_force_host_platform_device_count`` must be set before JAX starts, so
this runs in a process of its own.  Runs the same 4-chain spec on one
device and split over a ``MeshSpec`` with the chains on the ensemble axis
(whole chains per device, bonds with them) and with both axes split, then
writes every final ``PTState`` field to ``OUTDIR/glass_mesh.npz`` and prints
the names of the meshes it ran.

    python tests/_glass_mesh_child.py OUTDIR
"""
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.distributed import MeshSpec  # noqa: E402
from repro.core.spin_glass import EAGlass  # noqa: E402
from repro.engine import Engine, EngineConfig  # noqa: E402

MESHES = {"e4": MeshSpec(ensemble=4, replica=1), "e2r2": MeshSpec(ensemble=2, replica=2)}


def run(mesh):
    system = EAGlass(shape=(4, 4, 4), disorder_seed=11, copies=2)
    eng = Engine(system, EngineConfig(n_replicas=4, swap_interval=5, criterion="metropolis",
                                      chunk_intervals=2, n_chains=4, mesh=mesh))
    st = eng.init(jax.random.key(2**31 + 7), np.linspace(0.7, 1.6, 4))
    st, _ = eng.run(st, 20)
    return {k: np.asarray(getattr(st.pt, k)) for k in ("states", "energy", "rung", "quenched")}


def main(outdir: str) -> None:
    assert jax.device_count() == 4, jax.device_count()
    out = {f"one_{k}": v for k, v in run(None).items()}
    for name, mesh in MESHES.items():
        out.update({f"{name}_{k}": v for k, v in run(mesh).items()})
    np.savez(Path(outdir) / "glass_mesh.npz", **out)
    print(json.dumps({"meshes": sorted(MESHES)}))


if __name__ == "__main__":
    main(sys.argv[1])
