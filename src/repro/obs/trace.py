"""Lightweight tracing spans over the index lifecycle.

``with span("build.stage2.interact"): ...`` times a region with
``time.perf_counter_ns()``, nests (a thread-local stack tracks the active
span path), and aggregates into per-name stats (count / total / min /
max / last).  Aggregates export through :mod:`repro.obs.export` as
``seine_span_seconds_total{span=...}`` / ``seine_span_count_total`` /
``seine_span_last_seconds`` so build-stage timings ride the same
Prometheus/JSON snapshot as the counters and gauges.

Event record, off by default: :func:`record_events` keeps the last
``capacity`` span exits, each a :class:`SpanEvent` ``(name, t0_ns, t1_ns,
thread, attrs)``, and :func:`events` reads them.  The times are absolute
``time.perf_counter_ns()`` readings, so a reader that ties that clock to
another one (a device trace's, by a program run on the device at a known
host time) places every span on it.  With the record off a span costs
its aggregate alone.

A span measures *host wall-clock between enter and exit*: jax dispatch is
asynchronous, so wrap the ``block_until_ready``/``int(...)`` boundary if
you want device time included (the build pipeline's per-stage spans do).
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterator, List, NamedTuple, Optional

from . import metrics as _metrics


class SpanStat:
    __slots__ = ("count", "total_s", "min_s", "max_s", "last_s")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.min_s = float("inf")
        self.max_s = 0.0
        self.last_s = 0.0

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)
        self.last_s = dt

    def snapshot(self) -> dict:
        return {"count": self.count, "total_s": self.total_s,
                "min_s": self.min_s if self.count else 0.0,
                "max_s": self.max_s, "last_s": self.last_s}


class SpanEvent(NamedTuple):
    """One span exit: host ``perf_counter_ns`` at enter and exit, the
    thread it ran on and the attributes it was given."""
    name: str
    t0_ns: int
    t1_ns: int
    thread: str
    attrs: dict


_STATS: Dict[str, SpanStat] = {}
_TLS = threading.local()
_EVENTS: Optional[Deque[SpanEvent]] = None


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[dict]:
    """Time a region and aggregate under ``name``.

    Yields ``attrs``, which the region may fill in as it learns them (a
    batch's size once it closes); they ride into the span's event when
    the record is on, and nowhere else — per-name aggregates stay
    unlabelled so the hot path never builds a label set.
    """
    if not _metrics.enabled():
        yield attrs
        return
    stack = _stack()
    stack.append(name)
    t0 = time.perf_counter_ns()
    try:
        yield attrs
    finally:
        t1 = time.perf_counter_ns()
        stack.pop()
        stat = _STATS.get(name)
        if stat is None:
            stat = _STATS[name] = SpanStat()
        stat.add((t1 - t0) / 1e9)
        rec = _EVENTS
        if rec is not None:
            rec.append(SpanEvent(name, t0, t1,
                                 threading.current_thread().name, attrs))


def current_span() -> Optional[str]:
    """Innermost active span name on this thread (None outside any)."""
    stack = _stack()
    return stack[-1] if stack else None


def span_stats() -> Dict[str, SpanStat]:
    return dict(_STATS)


def snapshot() -> dict:
    return {name: _STATS[name].snapshot() for name in sorted(_STATS)}


def reset_spans() -> None:
    """Clear the per-name aggregates and any recorded events (the record
    stays on or off as it was)."""
    _STATS.clear()
    if _EVENTS is not None:
        _EVENTS.clear()


def record_events(capacity: Optional[int]) -> None:
    """Record span events into a fresh ring of the last ``capacity``
    exits; ``None`` stops recording and drops the record."""
    global _EVENTS
    if capacity is None:
        _EVENTS = None
        return
    if int(capacity) <= 0:
        raise ValueError(f"capacity must be positive, got {capacity}")
    _EVENTS = deque(maxlen=int(capacity))


def events() -> List[SpanEvent]:
    """The recorded span events, oldest first ([] when not recording)."""
    rec = _EVENTS
    return [] if rec is None else list(rec)
