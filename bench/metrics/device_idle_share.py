"""Share of the traced slice in which no op ran on the device (one minus
the union of the device-op intervals over the slice).  Moves
``tokens_per_s``."""
from bench import trace


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace.window_s)
