"""The chips a run uses: what JAX reports of them, their memory peak, and
freeing the program's buffers before the reference runs."""
from __future__ import annotations

import gc


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU ({info}); the benchmark never runs on the CPU")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {info['count']}")
    return info


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def free_device_memory() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
