"""``field_device_share``: per cent of busy device time (summed over the
cell's chips) in ops under the program's ``pt.field`` scope: the spin
glass's coupling-weighted neighbour sum, inside ``pt.sweep``.  None without
a trace, or where the program names no scopes."""
from bench import program_trace


def read(trace, record, device):
    if trace is None:
        return None
    return program_trace.read_newest(program_trace.device_share, "pt.field")
