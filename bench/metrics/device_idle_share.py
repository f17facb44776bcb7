"""Share of the traced window in which no operation ran on the device, in
percent."""

from benchlib import trace as T


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = T.busy_ns(run.trace, lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo)) if busy else None
