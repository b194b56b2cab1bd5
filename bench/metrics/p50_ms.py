"""p50_ms: median latency, from the moment each request was due to the
moment the client held its scores, over every request due in the window."""
import numpy as np


def read(run):
    return float(np.percentile(run.window.latency_ms, 50))
