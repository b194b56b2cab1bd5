"""Async serving front end: admission queue + SLO-aware continuous batching.

:func:`serve_batches` walks pre-formed batches synchronously — the right
loop for min-latency benchmarks, the wrong one for multi-user traffic
where requests arrive on their own timeline.  :class:`ServingFrontend`
puts an admission queue in front of the engine and forms batches
continuously: a batch closes when either the size target (``max_batch``)
or the time budget since its first request (``batch_timeout_ms``) is
hit, so a lone request never waits longer than the budget and a burst
fills batches immediately.  The existing ``batch_pad`` bucketing bounds
candidate-shape compile counts exactly as in :func:`serve_batches`, and
``pair_pad`` does the same for the coalesced distinct-pair count.

Deadlines: with ``slo_ms`` set, a request that has already aged past the
SLO when its batch forms is rejected unserved — its future raises
:class:`DeadlineExceeded` and ``seine_serve_slo_misses_total`` counts it.
Serving a request that can no longer meet its deadline only steals
capacity from the ones that still can (load shedding keeps goodput from
collapsing under overload).

Batch-level SEINE optimizations (both exact — scores stay bitwise-equal
to per-request ``engine.score``):

* ``coalesce=True`` routes the formed batch through
  :class:`~repro.serving.coalesce.CoalescingScorer`: (term, doc) pairs
  shared across the batch's queries resolve ONCE.
* ``cache_tiles > 0`` adds a
  :class:`~repro.serving.tile_cache.PostingTileCache` under the
  coalescer, so pairs landing in recently-touched posting tiles skip
  the routed fetch entirely.

Latency accounting: per-request latency is arrival→completion (queue
wait included — the number a client sees), recorded into a thread-safe
:class:`~repro.serving.engine.ServeStats` together with the
time-in-queue split and the queue-depth high-water mark.  The worker's
spans split a batch's host time: ``frontend.form`` (batch formation,
the ``batch_timeout_ms`` wait included), ``engine.score`` per request
inside ``frontend.batch``, then ``frontend.wait`` and
``frontend.answer`` per request (see :mod:`repro.obs`).

:func:`run_open_loop` drives a frontend under open-loop Poisson load
(exponential inter-arrival at ``target_qps``, submission never gated on
completion), times each request from the instant it was due, and
reports goodput — the fraction of submitted requests answered within
the SLO — and the generator's lateness, which closed-loop min-latency
benchmarks cannot see.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import obs
from .coalesce import CoalescingScorer
from .engine import ServeStats, pairs_counter
from .tile_cache import PostingTileCache


class DeadlineExceeded(Exception):
    """The request aged past the SLO in the queue and was rejected."""


@dataclass
class ServeRequest:
    """One queued request: candidates to score against one query."""
    query_terms: np.ndarray
    doc_ids: np.ndarray
    arrival_s: float
    future: Future = field(default_factory=Future)


_SHUTDOWN = object()


class ServingFrontend:
    """Continuous-batching async front end over a mesh-less engine.

    ``submit`` enqueues and returns a :class:`concurrent.futures.Future`
    resolving to the (B,) scores (host array); a dedicated worker thread
    forms and serves batches.  ``close`` drains every admitted request
    before joining the worker, so no future is left forever pending.

    Batch formation: a batch closes at ``max_batch`` requests or
    ``batch_timeout_ms`` after its first dequeue, whichever comes first;
    ``slo_ms`` rejects requests already past their deadline at dequeue
    (:class:`DeadlineExceeded`) instead of serving them late.
    ``coalesce`` dedupes (term, doc) pairs across the formed batch and
    ``cache_tiles`` keeps hot posting tiles device-resident — both
    exact (scores stay bitwise-equal to ``engine.score``).

    Live serving: :meth:`swap_engine` stages a replacement engine (e.g.
    over a freshly compacted :class:`~repro.dist.live.LiveIndex`
    generation) that the worker installs atomically between batches —
    the in-process half of an epoch swap, counted by
    ``seine_frontend_epoch_swaps_total``.
    """

    def __init__(self, engine, *, max_batch: int = 8,
                 batch_timeout_ms: float = 2.0, batch_pad: int = 0,
                 slo_ms: Optional[float] = None, coalesce: bool = True,
                 cache_tiles: int = 0, pair_pad: int = 256):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if batch_timeout_ms < 0:
            raise ValueError("batch_timeout_ms must be >= 0, "
                             f"got {batch_timeout_ms}")
        if batch_pad < 0:
            raise ValueError(f"batch_pad must be >= 0, got {batch_pad}")
        if slo_ms is not None and slo_ms <= 0:
            raise ValueError(f"slo_ms must be > 0, got {slo_ms}")
        if cache_tiles < 0:
            raise ValueError(f"cache_tiles must be >= 0, got {cache_tiles}")
        if cache_tiles > 0 and not coalesce:
            raise ValueError("cache_tiles > 0 requires coalesce=True: the "
                             "tile cache serves the coalesced distinct-"
                             "pair lookup")
        self.engine = engine
        self.max_batch = int(max_batch)
        self.batch_timeout_s = batch_timeout_ms / 1e3
        self.batch_pad = int(batch_pad)
        self.slo_ms = slo_ms
        self.pair_pad = int(pair_pad)
        self._coalesce = bool(coalesce)
        # a LiveIndex's tile cache binds the immutable BASE generation
        # (the delta/tombstone tail is applied per batch by the
        # coalescer); compaction bumps index.generation and the worker
        # rebinds between batches — see _apply_swaps
        live = bool(getattr(engine.index, "is_live", False))
        self.cache = (PostingTileCache(
            engine.index.base if live else engine.index, cache_tiles)
            if cache_tiles > 0 else None)
        self.scorer = (CoalescingScorer(engine, cache=self.cache,
                                        pair_pad=pair_pad)
                       if coalesce else None)
        self.stats = ServeStats()
        # epoch-swap plumbing: a staged engine is installed by the
        # WORKER between batches, never mid-batch — in-flight requests
        # always finish against the engine that started them
        self._staged_engine = None
        self._live_gen = getattr(engine.index, "generation", None)
        self._swap_counter = obs.counter(
            "seine_frontend_epoch_swaps_total",
            "engine/generation swaps applied between batches")
        self._req_counter = obs.counter("seine_frontend_requests_total",
                                        "requests admitted to the queue")
        self._batch_counter = obs.counter("seine_frontend_batches_total",
                                          "batches formed and served")
        self._pairs = pairs_counter()
        self._slo_counter = obs.counter(
            "seine_serve_slo_misses_total",
            "requests rejected unserved (aged past the SLO in queue)")
        self._queue: "queue.Queue" = queue.Queue()
        self._closed = False
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="seine-frontend")
        self._worker.start()

    # -- admission -----------------------------------------------------

    def submit(self, query_terms, doc_ids) -> Future:
        if self._closed:
            raise RuntimeError("frontend is closed")
        req = ServeRequest(np.asarray(query_terms), np.asarray(doc_ids),
                           time.perf_counter())
        self._req_counter.inc()
        self._queue.put(req)
        return req.future

    def swap_engine(self, engine) -> None:
        """Stage a new engine for an atomic epoch swap.

        The worker installs it BETWEEN batches: the batch being served
        keeps its engine/scorer/cache to completion, the next batch sees
        only the new ones — no request ever scores against a torn
        mixture of generations.  The tile cache rebinds (invalidating
        every cached tile) and the coalescing scorer is rebuilt, so no
        jit-captured arrays of the old index survive the swap.
        """
        if self._closed:
            raise RuntimeError("frontend is closed")
        self._staged_engine = engine

    def close(self) -> None:
        """Drain every admitted request, then stop the worker."""
        if self._closed:
            return
        self._closed = True
        # submissions stop before the sentinel enters, so everything
        # real sits ahead of it in FIFO order — the worker drains all
        # of it before it can see the sentinel
        self._queue.put(_SHUTDOWN)
        self._worker.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- batch formation ----------------------------------------------

    def _form_batch(self) -> Optional[List[ServeRequest]]:
        """Block for a first request, then gather until the size target
        or the time budget (measured from the first dequeue) is hit.
        Returns None when the shutdown sentinel surfaces with the queue
        already drained."""
        first = self._queue.get()
        if first is _SHUTDOWN:
            return None
        batch = [first]
        with obs.span("frontend.form", size=1) as attrs:
            t_close = time.perf_counter() + self.batch_timeout_s
            while len(batch) < self.max_batch:
                left = t_close - time.perf_counter()
                if left <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=left)
                except queue.Empty:
                    break
                if nxt is _SHUTDOWN:
                    # keep draining: the current batch (and any queued
                    # remainder) still gets served; re-post so the outer
                    # loop terminates once the queue is truly empty
                    self._queue.put(_SHUTDOWN)
                    break
                batch.append(nxt)
            attrs["size"] = len(batch)
        self.stats.note_queue_depth(self._queue.qsize())
        return batch

    def _apply_swaps(self) -> None:
        """Install any staged engine and track the live index's
        generation — both BETWEEN batches only (worker thread).  A
        compaction inside a LiveIndex publishes its new base atomically
        (readers are snapshot-safe already); the frontend's only job is
        to rebind the tile cache, whose cached tiles belong to the old
        generation's layout."""
        staged, self._staged_engine = self._staged_engine, None
        if staged is not None:
            self.engine = staged
            idx = staged.index
            if self.cache is not None:
                self.cache.swap_index(
                    idx.base if getattr(idx, "is_live", False) else idx)
            if self._coalesce:
                self.scorer = CoalescingScorer(staged, cache=self.cache,
                                               pair_pad=self.pair_pad)
            self._live_gen = getattr(idx, "generation", None)
            self._swap_counter.inc()
            return
        gen = getattr(self.engine.index, "generation", None)
        if gen is not None and gen != self._live_gen:
            if self.cache is not None:
                self.cache.swap_index(self.engine.index.base)
            self._live_gen = gen
            self._swap_counter.inc()

    def _run(self) -> None:
        while True:
            batch = self._form_batch()
            if batch is None:
                return
            self._apply_swaps()
            try:
                self._serve(batch)
            except BaseException as e:  # worker must survive; futures carry
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(e)

    # -- serving -------------------------------------------------------

    def _serve(self, batch: List[ServeRequest]) -> None:
        self._batch_counter.inc()
        t_dequeue = time.perf_counter()
        live, waits = [], []
        for r in batch:
            wait_ms = (t_dequeue - r.arrival_s) * 1e3
            if self.slo_ms is not None and wait_ms > self.slo_ms:
                self._slo_counter.inc()
                r.future.set_exception(DeadlineExceeded(
                    f"queued {wait_ms:.1f} ms > SLO {self.slo_ms:.1f} ms"))
                continue
            if r.doc_ids.shape[0] == 0:
                # degenerate request, as in serve_batches: nothing to
                # score, and the pad id (docs[0]) does not exist.
                # record BEFORE resolving — a caller blocked on
                # result() may read stats immediately after
                self.stats.record(wait_ms, queue_ms=wait_ms)
                r.future.set_result(np.zeros((0,), np.float32))
                continue
            live.append(r)
            waits.append(wait_ms)
        if not live:
            return
        pad = self.batch_pad

        def padded(docs):
            n = docs.shape[0]
            if pad > 0 and n % pad:
                m = -(-n // pad) * pad
                docs = np.concatenate(
                    [docs, np.full(m - n, docs[0], docs.dtype)])
            return docs

        for r in live:
            self._pairs.inc(int((r.query_terms >= 0).sum())
                            * r.doc_ids.shape[0])
        with obs.span("frontend.batch"):
            if self.scorer is not None:
                scores = self.scorer.score_batch(
                    [(r.query_terms, padded(r.doc_ids)) for r in live])
            else:
                scores = [self.engine.score(r.query_terms,
                                            padded(r.doc_ids))
                          for r in live]
            for r, w, s in zip(live, waits, scores):
                with obs.span("frontend.wait"):
                    s = jax.block_until_ready(s)
                with obs.span("frontend.answer"):
                    done_ms = (time.perf_counter() - r.arrival_s) * 1e3
                    self.stats.record(done_ms, queue_ms=w)
                    r.future.set_result(
                        np.asarray(s)[:r.doc_ids.shape[0]])


@dataclass
class OpenLoopResult:
    """Outcome of one open-loop run.

    ``latency_ms`` holds each request's time from the instant it was due
    to its answer (NaN where it was rejected), the latency a client sees
    whether or not the generator kept up; ``late_ms`` how late the
    generator submitted each request after it was due (a starved
    generator would otherwise read as a fast server).  ``goodput`` is the
    fraction of SUBMITTED requests answered within the SLO by that clock
    (rejected requests and late answers both count against it); with no
    SLO it degenerates to the answered fraction.  ``stats`` is the
    frontend's own record, timed from each ``submit``."""
    n_submitted: int
    n_served: int
    n_rejected: int
    goodput: float
    stats: ServeStats
    latency_ms: np.ndarray
    late_ms: np.ndarray

    def percentile_ms(self, q: float) -> float:
        lat = self.latency_ms[np.isfinite(self.latency_ms)]
        return float(np.percentile(lat, q)) if lat.size else 0.0

    @property
    def p50_ms(self) -> float:
        return self.percentile_ms(50.0)

    @property
    def p95_ms(self) -> float:
        return self.percentile_ms(95.0)


def run_open_loop(frontend: ServingFrontend,
                  requests: Sequence[Tuple[np.ndarray, np.ndarray]],
                  *, target_qps: float, seed: int = 0) -> OpenLoopResult:
    """Submit ``requests`` on a Poisson timeline at ``target_qps``.

    Open loop: inter-arrival gaps are exponential draws (seeded, so
    compared paths replay the SAME arrival schedule) and submission
    never waits on completions — queueing delay under overload shows up
    in the latency tail instead of silently throttling the offered
    load, which is exactly the failure mode closed-loop benchmarks hide.
    Each request is timed from the instant it was due, not from its
    submission, so a late generator cannot hide latency either.
    Blocks until every future resolves (the frontend stays open).
    """
    if target_qps <= 0:
        raise ValueError(f"target_qps must be > 0, got {target_qps}")
    rng = np.random.RandomState(seed)
    n = len(requests)
    gaps = rng.exponential(1.0 / target_qps, size=n)
    due = np.empty(n)
    submitted = np.empty(n)
    done = np.full(n, np.nan)
    # a future wakes its waiters before it runs its callbacks: count
    # the callbacks in, so every answer time is written before it is read
    answered = threading.Semaphore(0)

    def on_done(i):
        def cb(_):
            done[i] = time.perf_counter()
            answered.release()
        return cb

    futures = []
    t_next = time.perf_counter()
    for i, ((q, d), gap) in enumerate(zip(requests, gaps)):
        t_next += gap
        due[i] = t_next
        now = time.perf_counter()
        if t_next > now:
            time.sleep(t_next - now)
        submitted[i] = time.perf_counter()
        f = frontend.submit(q, d)
        f.add_done_callback(on_done(i))
        futures.append(f)
    for _ in futures:
        answered.acquire()
    ok = np.zeros(n, bool)
    rejected = 0
    for i, f in enumerate(futures):
        try:
            f.result()
            ok[i] = True
        except DeadlineExceeded:
            rejected += 1
    latency = np.where(ok, (done - due) * 1e3, np.nan)
    if frontend.slo_ms is None:
        good = int(ok.sum())
    else:
        good = int((latency[ok] <= frontend.slo_ms).sum())
    return OpenLoopResult(n, int(ok.sum()), rejected, good / max(n, 1),
                          frontend.stats, latency, (submitted - due) * 1e3)
