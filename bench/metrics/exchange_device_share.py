"""``exchange_device_share``: per cent of busy device time (summed over the
cell's chips) in ops under the program's ``pt.exchange`` scope: the swap
decision, its application and, on more than one chip, the all-gathers of
the energy and rung rows.  None without a trace, or where the program names
no scopes."""
from bench import program_trace


def read(trace, record, device):
    if trace is None:
        return None
    return program_trace.read_newest(program_trace.device_share, "pt.exchange")
