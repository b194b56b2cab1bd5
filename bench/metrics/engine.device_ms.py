"""engine.device_ms: device time per run of the served program (module
``jit_seine_score``, one run per request), over the runs that started in
the window, read from the ``XLA Modules`` line of the device trace."""
from bench.trace import MODULES_LINE

MODULE = "jit_seine_score"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    runs = [e - s for lines in run.trace.devices.values()
            for name, s, e in lines.get(MODULES_LINE, [])
            if name.split("(")[0] == MODULE and lo <= s < hi]
    return sum(runs) / len(runs) / 1e6 if runs else None
