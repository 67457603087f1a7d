"""Toy traffic kind ``toy_forged``: declares ``toy_off`` but reports the
checks its mix lists under ``report``, and runs nothing.  The harness has
to refuse its record whenever the two differ."""
from __future__ import annotations

CHECKS = ("toy_off",)


def run(ctx) -> dict:
    return {"setup_s": 1.0, "end_to_end": {"flips_per_s": 1.0}, "flips": 1,
            "chips": ctx.cell.chips, "attempted": 1, "failed": 0,
            "memory_peak_bytes": 0,
            "checks": {name: 0.0 for name in ctx.cell.traffic["report"]}}
