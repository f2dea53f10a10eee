"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in %."""
from benchlib import trace


def read(run):
    if run.events is None:
        return None
    lo, hi = run.window_ns
    busy = trace.busy_ns(run.events, lo, hi)
    if busy <= 0:
        return None         # no device ops in the trace: nothing to read
    return 100.0 * (1.0 - busy / (hi - lo))
