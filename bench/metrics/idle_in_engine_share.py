"""``idle_in_engine_share``: per cent of the traced window in which the
device idles while the host's window thread is inside the engine's host
loop (a ``repro.engine.*`` span) and not in the caller's chunk callback
(``repro.engine.callbacks``); the mean over the cell's chips.  The host
time that the device waits for: dispatch, trace drain, adapt, checkpoint,
compile, the end-of-run summary.  None without a trace, or where the program opens no
engine spans."""
from bench import program_trace


def read(trace, record, device):
    if trace is None:
        return None
    return program_trace.read_newest(program_trace.idle_in_spans,
                                     "repro.engine.", "repro.engine.callbacks")
