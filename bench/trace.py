"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Everything is read through ``jax.profiler.ProfileData``.  Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run, their ``XLA Modules`` line one per program run.  Host events are not
read: the host tracer drops them under load, so the host clock is tied to
the trace's by a program the benchmark runs on the device (its clock
mark), and what the host was doing comes from the run's own record.  All
times are on the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_NS = 100_000        # device gaps at least this long are labelled

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Event]]]   # plane -> line -> events


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def from_profile(pd) -> Trace:
    return Trace({plane.name: {
        ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in ln.events] for ln in plane.lines}
        for plane in pd.planes if DEVICE_PLANE.match(plane.name)})


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def clock_offset(tr: Trace, key: str, host_ns: Sequence[int]) -> float:
    """Trace clock minus host clock (``perf_counter``), in ns.  ``host_ns``
    holds the host clock's reading after each run of the clock-mark
    program (module name containing ``key``) had finished; the median
    over the runs of device end minus reading.  Raises where the trace
    does not hold one device run per reading."""
    ends = sorted(e for lines in tr.devices.values()
                  for name, _, e in lines.get(MODULES_LINE, [])
                  if key in name)
    if not host_ns or len(ends) != len(host_ns):
        raise ValueError(f"{len(ends)} runs of {key!r} in the trace for "
                         f"{len(host_ns)} host readings")
    offs = sorted(e - h for e, h in zip(ends, host_ns))
    return offs[len(offs) // 2]


def window(start_s: float, seconds: float,
           offset_ns: float) -> Tuple[float, float]:
    """(start_ns, end_ns) on the trace clock of the host-clock window
    ``[start_s, start_s + seconds)``."""
    lo = start_s * 1e9 + offset_ns
    return lo, lo + seconds * 1e9


def _clip(evs: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def union(evs: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals of a set of events."""
    out: List[List[float]] = []
    for _, s, e in sorted(evs, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_ops(tr: Trace, plane: str) -> List[Event]:
    lines = tr.devices[plane]
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def busy_s(tr: Trace, lo: float, hi: float) -> Optional[float]:
    """Seconds in [lo, hi) in which some operation ran, averaged over the
    device planes; None when the trace holds no device."""
    if not tr.devices:
        return None
    tot = [sum(e - s for s, e in union(_clip(device_ops(tr, p), lo, hi)))
           for p in tr.devices]
    return sum(tot) / len(tot) / 1e9


def module_time(tr: Trace, key: str) -> Tuple[float, int]:
    """(seconds, runs) of the programs whose module name contains ``key``,
    summed over devices (the one device of a one-chip cell)."""
    t, n = 0.0, 0
    for lines in tr.devices.values():
        for name, s, e in lines.get(MODULES_LINE, []):
            if key in name:
                t += (e - s) / 1e9
                n += 1
    return t, n


def op_name(event_name: str) -> str:
    """An op event's name is its HLO text (``%fusion.4 = f32[92160]{...}
    fusion(...), ...``): keep the op's name, result shape and opcode."""
    if " = " not in event_name:
        return event_name[:120]
    name, rest = event_name.split(" = ", 1)
    shape = "tuple" if rest.startswith("(") else rest.split("{")[0].split()[0]
    op = re.search(r"[}\]) ]([a-z][\w-]*)\(", rest)
    return " ".join([name.lstrip("%"), shape] + ([op.group(1)] if op else []))


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` operations that took most device time in [lo, hi)."""
    acc: Dict[str, float] = defaultdict(float)
    for p in tr.devices:
        for name, s, e in _clip(device_ops(tr, p), lo, hi):
            acc[op_name(name)] += (e - s) / 1e9
    return sorted(([k, v] for k, v in acc.items()),
                  key=lambda kv: -kv[1])[:n]


def idle_gaps(tr: Trace, lo: float, hi: float,
              in_flight: Sequence[Tuple[float, float]] = (),
              min_ns: float = GAP_NS) -> list:
    """Seconds of device idle time in [lo, hi) by what the host was doing.

    ``in_flight`` holds, on the trace clock, each request's interval from
    its submission to its answer (the run's own host-clock record, moved
    by the clock mark).  Each gap of at least ``min_ns`` between device
    operations goes under ``request_in_flight`` (the host path: queueing,
    batch formation, dispatch, answering) where a request was in flight
    at its midpoint, else under ``no_request_in_flight`` (the generator
    waiting for the next due time).  Shorter gaps are summed under
    ``gaps_under_<min_ns/1000>us``.  At most 10 entries, largest first."""
    if not tr.devices:
        return []
    flight = union([("", s, e) for s, e in in_flight])
    starts = [s for s, _ in flight]
    acc: Dict[str, float] = defaultdict(float)
    short = f"gaps_under_{min_ns / 1e3:g}us"
    for p in tr.devices:
        edges = [lo] + [x for s, e in union(_clip(device_ops(tr, p), lo, hi))
                        for x in (s, e)] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            if b - a < min_ns:
                acc[short] += (b - a) / 1e9
                continue
            i = bisect.bisect_right(starts, (a + b) / 2) - 1
            busy = i >= 0 and flight[i][1] > (a + b) / 2
            acc["request_in_flight" if busy else
                "no_request_in_flight"] += (b - a) / 1e9
    k = len(tr.devices)
    return sorted(([name, v / k] for name, v in acc.items()),
                  key=lambda kv: -kv[1])[:10]
