"""``rng_device_share``: per cent of busy device time (summed over the
cell's chips) in ops under the program's ``pt.rng`` scope: the per-sweep
path's ``jax.random`` uniforms and key folds.  On the fused path the
random numbers are drawn inside the one kernel, so it is not read there.
None without a trace, or where the program names no scopes."""
from bench import program_trace


def read(trace, record, device):
    if trace is None:
        return None
    return program_trace.read_newest(program_trace.device_share, "pt.rng")
