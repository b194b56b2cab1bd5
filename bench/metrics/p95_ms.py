"""p95_ms: 95th percentile of the same latencies as p50_ms, over every
request due in the window (not a median of chunks)."""
import numpy as np


def read(run):
    return float(np.percentile(run.window.latency_ms, 95))
