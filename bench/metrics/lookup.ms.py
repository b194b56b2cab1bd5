"""lookup.ms: device time per request of the benchmark's probe jit of
``PartitionedIndex.qd_matrix``, run after the window on a sample of the
window's requests at their served shapes, read from the trace by the
probe's module name."""
from bench.harness import PROBE_KEY
from bench.trace import module_time


def read(run):
    if run.trace is None:
        return None
    secs, runs = module_time(run.trace, PROBE_KEY)
    return secs / runs * 1e3 if runs else None
