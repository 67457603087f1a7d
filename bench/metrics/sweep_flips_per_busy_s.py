"""``sweep_flips_per_busy_s``: spin-flip attempts per second of device busy
time, per chip: the window's attempts over the chips, divided by the time
in the traced window in which some operation ran on the chip.  The sweep
rate the kernels reach with the host's gaps left out; no roofline, since no
compute peak of the vector unit is published.  None without a trace."""


def read(trace, record, device):
    if trace is None or trace.busy_s <= 0:
        return None
    return record["flips"] / record["chips"] / trace.busy_s
