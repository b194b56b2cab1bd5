"""lookup_roofline: the probe's share of the HBM roofline, in percent:
the bytes ``qd_matrix``'s contract needs (``bench/roofline.py``) over the
probe's device time times the chip's peak HBM bandwidth.  The lookup moves
bytes and does next to no arithmetic, so bandwidth bounds it."""
from bench.harness import PROBE_KEY
from bench.roofline import lookup_bytes
from bench.trace import module_time


def read(run):
    if run.trace is None or not run.probe_requests:
        return None
    secs, runs = module_time(run.trace, PROBE_KEY)
    if not runs or runs != len(run.probe_requests) or secs <= 0:
        return None
    cfg = run.config
    need = lookup_bytes(run.probe_requests, cfg["n_segments"],
                        len(cfg["functions"]), cfg["dtype"])
    return 100.0 * need / (secs * run.peaks["hbm_bytes_per_s"])
