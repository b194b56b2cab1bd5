"""device.idle_pct: the share of the window in which no operation ran on
the device, in percent: 1 - (union of the device's op intervals) / window."""
from bench.trace import busy_s


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = busy_s(run.trace, lo, hi)
    return None if busy is None else 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
