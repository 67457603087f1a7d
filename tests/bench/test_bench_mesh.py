"""The sampling comparison with the replicas split over four virtual CPU
devices, as a four-chip cell would run them: a sound run is correct, and
each fault such a cell can have is not, the exchange between chips left
out among them.  Runs in a child process, since the
device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_mesh_cell_sound_and_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(Path(__file__).with_name("_mesh_child.py"))],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "unchanged": False, "half_batch": False,
                   "altered": False, "no_exchange": False}, got
