"""Per cent of the traced window in which no operation ran on the device,
the mean over the cell's chips (1 - union of op intervals / window)."""
from bench.trace import idle_share_pct


def read(trace, record, device):
    return None if trace is None else idle_share_pct(trace)
