"""Query-time retrieval engines (the paper's retrieval phase, Fig. 1).

``SeineEngine``  — looks M_{q,d} up from the segment inverted index (fast path).
``NoIndexEngine`` — recomputes interactions on the fly (the paper's baseline).

Both expose the same `score(query, doc_ids)` so Table-1-style efficiency
comparisons are one engine swap. A tiny batched request loop provides the
serving driver used by launch/serve.py.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.builder import IndexBuilder
from ..core.index import PairLookupIndex, SegmentInvertedIndex
from ..retrievers import QMeta, get_retriever


def make_qmeta(index: PairLookupIndex, query_terms: jnp.ndarray,
               doc_ids: jnp.ndarray) -> QMeta:
    """Per-(query, candidate) scoring metadata: query mask/idf plus the
    candidates' doc/segment lengths and the corpus ``avg_dl`` — the
    side inputs every retriever's ``spec.score`` consumes next to M.
    Pad query slots (term id < 0) get zero mask/idf; ``doc_ids`` must
    already be clipped to ``[0, n_docs)`` by the caller."""
    return QMeta(
        q_mask=(query_terms >= 0).astype(jnp.float32),
        q_idf=index.idf.at[query_terms.clip(0)].get(mode="clip")
        * (query_terms >= 0),
        doc_len=index.doc_len.at[doc_ids].get(mode="clip"),
        seg_len=index.seg_len.at[doc_ids].get(mode="clip"),
        avg_dl=index.avg_doc_len,
    )


class SeineEngine:
    """Indexed scorer over any :class:`~repro.core.index.PairLookupIndex`.

    With ``mesh`` the index is placed for SPMD serving and candidate
    batches shard over the data axes, so one score() call runs across
    every device.  Two placements:

    * default — dist.sharding.shard_index: posting-list values on the
      model axis, CSR skeleton replicated (capped at ~2^31 nnz/pod);
    * ``partition="term"`` — dist.sharding.partition_index: the index is
      split into ``n_shards`` nnz-balanced term-range shards (defaults to
      the mesh's model-axis size) with no replicated CSR skeleton; query
      terms route to their owning shard and partial M rows merge exactly.
      Works without a mesh too (K stacked shards on one device — the
      configuration the oracle-parity tests sweep).  ``n_shards`` is
      clamped (with a warning) to the number of populated term ranges so
      tiny vocabularies never ship zero-nnz shards.

    A pre-built :class:`~repro.dist.partition.PartitionedIndex` (from the
    shard-native ``IndexBuilder.build_partitioned``) is served as-is —
    only mesh placement is applied.

    Lookup dispatch: without a mesh the engine scores over the FUSED
    lookup path (``kernels.csr_lookup`` — one routed two-level bisect per
    (term, doc) pair, no K partial matrices; on TPU only the winning
    posting tile is DMA'd into VMEM, so shard size is not VMEM-bound);
    with a mesh it keeps the partial-sum jnp expression the XLA
    partitioner turns into an all-reduce.  Both are held bitwise-equal
    to the single-CSR oracle.  ``lookup_tile`` overrides the kernel's
    posting-tile width (default ``core.index.POSTING_TILE``) — a serving
    knob for tuning VMEM footprint vs DMA count per cell; every width is
    bitwise-exact.

    ``codec`` (with ``partition="term"``) serves tile-compressed postings
    (``core.codec``): ``"packed"`` FOR/bit-packs doc ids per posting tile
    (lossless — lookup and retrieval results stay bitwise-equal to the
    uncompressed index), ``"packed-q8"`` additionally int8-quantises the
    interaction values with per-term scales (~4x smaller, effectiveness-
    gated in CI).  A pre-built PartitionedIndex carries its own codec and
    is served as-is; packed layouts are mesh-less only and pin the
    lookup tile to their build-time ``codec_tile``.
    """

    def __init__(self, index: PairLookupIndex, retriever: str,
                 params: Any, *, mesh: Optional[Any] = None,
                 partition: Optional[str] = None,
                 n_shards: Optional[int] = None,
                 lookup_tile: Optional[int] = None,
                 codec: str = "none",
                 codec_tile: Optional[int] = None):
        from ..core.codec import validate_codec
        from ..dist.partition import PartitionedIndex
        codec = validate_codec(codec)
        if partition not in (None, "term"):
            raise ValueError(f"unknown partition scheme {partition!r}; "
                             "supported: 'term'")
        if (codec != "none" and partition != "term"
                and not isinstance(index, PartitionedIndex)):
            raise ValueError(
                f"codec {codec!r} requires partition='term': the packed "
                "posting layout is the stacked-shard PartitionedIndex")
        # reject, don't coerce: n_shards=0 used to fall through the falsy
        # `or` chain below and silently serve the mesh default — a surprise
        # configuration is worse than an error
        if n_shards is not None and int(n_shards) <= 0:
            raise ValueError(f"n_shards must be positive, got {n_shards}; "
                             "pass None to default to the mesh's "
                             "model-axis size")
        if lookup_tile is not None and int(lookup_tile) <= 0:
            raise ValueError(
                f"lookup_tile must be positive, got {lookup_tile}; "
                "pass None for the default POSTING_TILE")
        self.mesh = mesh
        # mesh-less default: _place is never called, but must not crash if
        # it ever is (latent AttributeError — _data_axes was only assigned
        # under `mesh is not None`)
        self._data_axes = ()
        self._live = bool(getattr(index, "is_live", False))
        if self._live:
            # a LiveIndex mutates underneath the engine, so its serve
            # snapshot rides through jit as an ARGUMENT (see _score below)
            # — placement/partitioning of a moving target is out of scope
            if mesh is not None:
                raise ValueError(
                    "a LiveIndex cannot serve under a mesh: compaction "
                    "swaps the base generation underneath the placement")
            if partition is not None:
                raise ValueError(
                    "a LiveIndex is already partitioned (its base); "
                    "pass partition=None")
            if codec != "none" and codec != index.codec:
                raise ValueError(
                    f"engine codec {codec!r} conflicts with the live "
                    f"index's base codec {index.codec!r}")
        elif isinstance(index, PartitionedIndex):
            # born-sharded (builder.build_partitioned): use it as-is; it
            # carries its own codec — a conflicting request is a config
            # error, not something to re-encode silently
            if codec != "none" and codec != index.codec:
                raise ValueError(
                    f"engine codec {codec!r} conflicts with the pre-built "
                    f"index's codec {index.codec!r}; pack at build time "
                    "(build_partitioned(codec=...)) or pass codec='none'")
            if mesh is not None:
                from ..dist.sharding import shard_partitioned_index
                index = shard_partitioned_index(index, mesh)
        elif partition == "term":
            from ..dist.sharding import partition_index
            if n_shards is not None:
                k = int(n_shards)
            else:
                k = int((mesh and dict(
                    zip(mesh.axis_names,
                        mesh.devices.shape)).get("model")) or 1)
            # K beyond the populated term ranges is clamped (with a
            # warning) by the merger itself — partitioned_from_runs, the
            # single guard every build path shares — so tiny vocabularies
            # never ship zero-nnz shards
            index = partition_index(index, k, mesh=mesh, codec=codec,
                                    codec_tile=codec_tile)
        elif mesh is not None:
            from ..dist.sharding import shard_index
            index = shard_index(index, mesh)
        served_codec = getattr(index, "codec", "none")
        if served_codec != "none":
            # satellite guards, at construction not first lookup: a mesh
            # forces the jnp partial-sum impl (no packed lowering), and a
            # lookup_tile cannot re-tile a baked packed layout
            if mesh is not None:
                raise ValueError(
                    "packed codecs cannot serve under a mesh: the SPMD "
                    "partial-sum lookup has no packed lowering (serve "
                    "mesh-less, or build with codec='none')")
            if (lookup_tile is not None
                    and int(lookup_tile) != int(index.codec_tile)):
                raise ValueError(
                    f"lookup_tile {lookup_tile} does not match the packed "
                    f"index's codec tile {index.codec_tile}; packed "
                    "layouts serve only at their build-time tile")
        if mesh is not None:
            from ..dist.sharding import data_axes
            self._data_axes = data_axes(mesh) or tuple(
                a for a in mesh.axis_names if a != "model")
        self.index = index
        self.spec = get_retriever(retriever)
        self.params = params
        # lookup dispatch: mesh-less serving takes the fused hot path
        # (kernels.csr_lookup); under a mesh the index arrays carry
        # NamedShardings, so keep the XLA-partitionable jnp expression
        # (partial-sum merge -> all-reduce over the model axis)
        self._lookup_impl = "jnp" if mesh is not None else "fused"
        self._lookup_tile = lookup_tile
        # the jitted programs take the index as a pytree ARGUMENT, never
        # as a closure: jit embeds closed-over arrays in the program as
        # literal constants — a copy of a multi-GB index inside the HLO —
        # and a LiveIndex mutates underneath the engine, so its programs
        # must serve whatever snapshot (``_served()``) the call reads.
        # Compiled code is keyed on shapes, never on array values.  The
        # served program's module reads ``jit_seine_score`` in a device
        # trace; its named scopes (``seine.lookup.*`` in the lookup,
        # ``seine.rank`` here) attribute its operations.
        def seine_score(params, index, query_terms, doc_ids):
            m = index.qd_matrix(query_terms, doc_ids,
                                impl=self._lookup_impl,
                                tile=self._lookup_tile)
            with jax.named_scope("seine.rank"):
                meta = make_qmeta(index, query_terms, doc_ids)
                return self.spec.score(params, m, meta, index.functions)

        self._score = jax.jit(seine_score)
        # first-stage retrieval: one jit per static k (jax caches per
        # (k, doc_block) pair); retrieve() trims k > n_docs before jitting
        # so a sweep of oversized ks shares one compiled program
        self._retrieve = jax.jit(self._retrieve_impl,
                                 static_argnames=("k", "doc_block"))
        self._retrieves_counter = obs.counter(
            "seine_engine_retrieves_total", "engine.retrieve calls")
        # per-call registry lookups hoisted to construction: score() is
        # the serving hot path and the family objects are stable
        self._scores_counter = obs.counter("seine_engine_scores_total",
                                           "engine.score calls")
        if obs.enabled():
            obs.gauge("seine_index_nnz", "nnz of the served index").set(
                self.index.nnz)
            obs.gauge("seine_index_nbytes", "bytes of the served index"
                      ).set(self.index.nbytes)

    def _served(self):
        """What the jitted programs read: the index, or a LiveIndex's
        current snapshot (a LiveView pytree)."""
        return self.index.view if self._live else self.index

    def _retrieve_impl(self, params, index, query_terms, k, doc_block):
        """First-stage retrieval over ``index``; for a LiveView the base
        drives the block scan, the delta joins through the scan's
        ``extra_m_fn`` hook and tombstones mask to ``-inf``."""
        n_docs = index.n_docs

        def score_block(m, docs):
            # blocks overrun the corpus tail; clip the gather targets
            # (the driver masks those scores to -inf afterwards)
            d = docs.clip(0, n_docs - 1)
            meta = make_qmeta(index, query_terms, d)
            return self.spec.score(params, m, meta, index.functions)

        return index.retrieve_topk(query_terms, k, score_block,
                                   doc_block=doc_block,
                                   impl=self._lookup_impl,
                                   tile=self._lookup_tile)

    def retrieve(self, query_terms: jnp.ndarray, k: int, *,
                 doc_block: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """First-stage retrieval: no candidate set — walk the index from
        the query's posting lists and return the corpus-wide top-k as
        ``(scores, doc_ids)``, each ``(min(k, n_docs),)``, scores
        descending, ties toward the lower doc id.

        An all-OOV (or all-padding) query is still well-defined: every M
        row is zero, so ranking falls back to the retriever's
        doc-dependent background score (doc_len/seg_len terms) — same as
        scoring those docs through :meth:`score`.  ``doc_block`` sets
        the scan's doc-block width (default: whole corpus up to 1024);
        each distinct (k, doc_block) compiles once.  Mesh-less engines
        only — the scan's segment scatter has no SPMD lowering yet.
        """
        if self.mesh is not None:
            raise NotImplementedError(
                "retrieve() is mesh-less only for now; serve a mesh-less "
                "engine for first-stage retrieval")
        if int(k) <= 0:
            raise ValueError(f"k must be positive, got {k}")
        query_terms = jnp.asarray(query_terms)
        kk = min(int(k), int(self.index.n_docs))
        if obs.enabled():
            self._retrieves_counter.inc()
            obs.counter("seine_retrieve_docs_scanned_total",
                        "docs covered by retrieve scans").inc(
                self.index.n_docs)
            obs.gauge("seine_retrieve_last_k",
                      "k of the most recent retrieve").set(kk)
        return self._retrieve(self.params, self._served(), query_terms,
                              k=kk, doc_block=doc_block)

    def _place(self, query_terms, doc_ids):
        """Shard candidates over the data axes (fit_spec shrinks/drops axes
        that don't divide the batch — the repo's one divisibility policy)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ..dist.sharding import fit_spec
        spec = fit_spec(self.mesh, P(self._data_axes), doc_ids.shape) \
            if self._data_axes else P()
        return (jax.device_put(query_terms, NamedSharding(self.mesh, P())),
                jax.device_put(doc_ids, NamedSharding(self.mesh, spec)))

    def score(self, query_terms: jnp.ndarray, doc_ids: jnp.ndarray
              ) -> jnp.ndarray:
        """Scores of ``doc_ids`` (B,) for ``query_terms`` (Q,), as a
        device array the caller waits on.  The ``engine.score`` span
        times the host's part: argument conversion and the dispatch of
        the served program, up to the call's return."""
        with obs.span("engine.score"):
            query_terms = jnp.asarray(query_terms)
            doc_ids = jnp.asarray(doc_ids)
            if self.mesh is not None:
                query_terms, doc_ids = self._place(query_terms, doc_ids)
            self._scores_counter.inc()
            return self._score(self.params, self._served(), query_terms,
                               doc_ids)


class NoIndexEngine:
    """Recomputes the q-d interaction matrix at query time (No Index row)."""

    def __init__(self, builder: IndexBuilder, index: SegmentInvertedIndex,
                 tokens: np.ndarray, segs: np.ndarray, retriever: str,
                 params: Any):
        # `index` is used ONLY for doc stats/idf (identical qmeta), never
        # for interaction values.
        self.builder = builder
        self.index = index
        self.tokens = jnp.asarray(tokens)
        self.segs = jnp.asarray(segs)
        self.spec = get_retriever(retriever)
        self.params = params
        qd_fn = builder.make_qd_fn()

        def impl(params, query_terms, doc_ids):
            m = qd_fn(query_terms, self.tokens[doc_ids], self.segs[doc_ids])
            meta = make_qmeta(self.index, query_terms, doc_ids)
            return self.spec.score(params, m, meta, self.index.functions)

        self._score = jax.jit(impl)

    def score(self, query_terms: jnp.ndarray, doc_ids: jnp.ndarray
              ) -> jnp.ndarray:
        return self._score(self.params, query_terms, doc_ids)


def pairs_counter() -> obs.Counter:
    """``seine_lookup_pairs_total``: the (term, candidate) pairs served,
    valid query terms x real candidates per request.  The serve loops
    count it from the host arrays they hold, so it costs no device
    program and no wait."""
    return obs.counter("seine_lookup_pairs_total",
                       "(term, candidate) pairs served: valid query "
                       "terms x real candidates")


@dataclass
class ServeStats:
    """Per-request latency record.  The mean alone hides tail latency under
    data-parallel serving (one straggler device stretches every request it
    shares a batch with), so p50/p95 quantiles are reported alongside it.
    count/total are O(1) running scalars, and ``latencies_ms`` is a deque
    keeping only the most recent ``window`` samples, so a long-lived
    serving loop gets recent-window quantiles at bounded memory and O(1)
    per-request cost (a full-history ServeStats would grow forever at
    production rates).

    Thread safety: the async front end records from its worker thread
    while the submitting thread reads quantiles, so ``record`` /
    ``note_queue_depth`` and the sorted-snapshot cache take an internal
    lock — without it a read mid-record could sort a deque whose running
    count it then caches against, pinning a stale snapshot forever.

    Queue instrumentation (continuous batching): ``record`` takes an
    optional ``queue_ms`` (admission-to-dequeue wait, also exported as
    the ``seine_serve_queue_wait_ms`` histogram) and the front end calls
    ``note_queue_depth`` per batch so ``max_queue_depth`` tracks the
    high-water mark."""
    latencies_ms: Sequence[float] = field(default_factory=list)
    window: int = 1 << 16
    queue_depth: int = 0
    max_queue_depth: int = 0
    _n: int = 0
    _total_ms: float = 0.0
    _queue_n: int = 0
    _queue_total_ms: float = 0.0
    _snap: Optional[np.ndarray] = field(default=None, repr=False)
    _snap_n: int = -1

    def __post_init__(self):
        self.latencies_ms = deque(self.latencies_ms, maxlen=self.window)
        self._lock = threading.Lock()
        # family objects cached once: obs.reset() clears samples but keeps
        # registered families, so the handles stay valid for the stats
        # object's whole life
        self._hist = obs.histogram("seine_serve_latency_ms",
                                   "per-request serve latency (ms)")
        self._qhist = obs.histogram(
            "seine_serve_queue_wait_ms",
            "admission-to-dequeue wait in the serving queue (ms)")
        self._depth_gauge = obs.gauge(
            "seine_serve_queue_depth",
            "admission queue depth at batch formation")

    def record(self, ms: float, queue_ms: Optional[float] = None) -> None:
        # the obs writes stay inside the lock: metric samples are plain
        # dict read-modify-writes, unsafe under concurrent recorders
        with self._lock:
            self._n += 1
            self._total_ms += ms
            self.latencies_ms.append(ms)
            if queue_ms is not None:
                self._queue_n += 1
                self._queue_total_ms += queue_ms
            # dual-write: the obs histogram is the exported surface
            # (Prometheus buckets, JSON snapshot); the deque keeps exact
            # recent-window quantiles for in-process reporting
            self._hist.observe(ms)
            if queue_ms is not None:
                self._qhist.observe(queue_ms)

    def note_queue_depth(self, depth: int) -> None:
        with self._lock:
            self.queue_depth = int(depth)
            if depth > self.max_queue_depth:
                self.max_queue_depth = int(depth)
            self._depth_gauge.set(depth)

    @property
    def n_requests(self) -> int:
        return self._n

    @property
    def total_ms(self) -> float:
        return self._total_ms

    @property
    def ms_per_request(self) -> float:
        return self._total_ms / max(self._n, 1)

    @property
    def queue_ms_per_request(self) -> float:
        with self._lock:
            return self._queue_total_ms / max(self._queue_n, 1)

    def _sorted_ms(self) -> np.ndarray:
        """Sorted snapshot of the recent-window samples, cached per
        record() count: a p50+p95 report used to materialise and sort
        the (up to 64k-sample) deque twice per read — now any number of
        quantile reads between two records share one O(n log n) sort.
        Snapshot + count are read under the lock so a concurrent record
        can't interleave between the deque copy and the count cache."""
        with self._lock:
            if self._snap is None or self._snap_n != self._n:
                self._snap = np.sort(np.asarray(self.latencies_ms,
                                                dtype=np.float64))
                self._snap_n = self._n
            return self._snap

    def percentile_ms(self, q: float) -> float:
        if not self.latencies_ms:
            return 0.0
        # np.percentile on the pre-sorted snapshot: identical result to
        # sorting internally (interpolation only indexes ordered values)
        return float(np.percentile(self._sorted_ms(), q))

    @property
    def p50_ms(self) -> float:
        return self.percentile_ms(50.0)

    @property
    def p95_ms(self) -> float:
        return self.percentile_ms(95.0)


def serve_batches(engine, requests: Sequence[Tuple[np.ndarray, np.ndarray]],
                  batch_pad: int = 0) -> Tuple[List[np.ndarray], ServeStats]:
    """requests: list of (query_terms (Q,), candidate_doc_ids (B,)).

    ``batch_pad > 0`` pads every candidate set up to the next multiple of
    ``batch_pad`` (bucketing) before scoring and slices the pad scores
    off the result.  The engine's score fn is jit'd per candidate-set
    SHAPE, so without bucketing a production stream recompiles once per
    distinct candidate count — e.g. 32 requests with candidate counts
    drawn from [50, 200) hit ~32 distinct shapes = ~32 compiles, where
    ``batch_pad=64`` buckets them into {64, 128, 192} = 3 compiles (and a
    fixed candidate workload stays at exactly 1, as
    tests/test_build_pipeline.py asserts via ``_score._cache_size()``).
    Pad ids re-use candidate 0 — any valid doc id scores safely; the
    padded rows are dropped before returning, so results are identical to
    the unpadded call.  Under a data-parallel mesh pick ``batch_pad`` as
    a multiple of the device count, otherwise the padded batch stops
    tiling the data axes and the engine's divisibility guard silently
    replicates it (launch/serve.py rounds ``--batch-pad`` up for you).
    """
    if batch_pad < 0:
        raise ValueError(f"batch_pad must be >= 0, got {batch_pad}")
    stats = ServeStats()
    out = []
    real_slots = pad_slots = 0
    req_counter = obs.counter("seine_serve_requests_total",
                              "serve_batches requests")
    pairs = pairs_counter()
    for q, docs in requests:
        q, docs = np.asarray(q), np.asarray(docs)
        n = docs.shape[0]
        req_counter.inc()
        if n == 0:
            # degenerate request: no candidates to score.
            # Short-circuit to an empty result instead of padding
            # (the pad id comes from docs[0], which does not exist)
            # or paying a device round-trip for a (0,) batch.
            obs.counter("seine_serve_degenerate_requests_total",
                        "empty-candidate requests").inc()
            out.append(np.zeros((0,), np.float32))
            continue
        pairs.inc(int((q >= 0).sum()) * n)
        if batch_pad > 0 and n % batch_pad:
            m = -(-n // batch_pad) * batch_pad
            docs = np.concatenate(
                [docs, np.full(m - n, docs[0], docs.dtype)])
        real_slots += n
        pad_slots += docs.shape[0] - n
        t0 = time.perf_counter()
        # block on the DEVICE array: np.asarray first would force a
        # blocking host transfer inside the timed region and
        # double-count conversion
        with obs.span("serve.request"):
            s = jax.block_until_ready(engine.score(jnp.asarray(q),
                                                   jnp.asarray(docs)))
        stats.record((time.perf_counter() - t0) * 1e3)
        out.append(np.asarray(s)[:n])
    if obs.enabled() and (real_slots or pad_slots):
        obs.counter("seine_serve_slots_total",
                    "real candidate slots scored").inc(real_slots)
        if pad_slots:
            obs.counter("seine_serve_pad_slots_total",
                        "padded candidate slots scored").inc(pad_slots)
        obs.gauge("seine_serve_pad_waste_ratio",
                  "pad / (pad + real) slots, most recent call").set(
            pad_slots / (real_slots + pad_slots))
    return out, stats


def serve_retrieval(engine, queries: Sequence[np.ndarray], k: int
                    ) -> Tuple[List[Tuple[np.ndarray, np.ndarray]],
                               ServeStats]:
    """First-stage serving loop: one corpus-wide top-k retrieval per
    query (no candidate sets — :meth:`SeineEngine.retrieve` walks the
    index).  Returns ``([(scores, doc_ids), ...], ServeStats)``; latency
    accounting mirrors :func:`serve_batches` — block on the device
    result inside the ``serve.retrieve`` span, convert to host arrays
    after the timer stops.
    """
    stats = ServeStats()
    out = []
    req_counter = obs.counter("seine_retrieve_requests_total",
                              "serve_retrieval requests")
    for q in queries:
        req_counter.inc()
        t0 = time.perf_counter()
        with obs.span("serve.retrieve"):
            s, d = engine.retrieve(jnp.asarray(q), k)
            jax.block_until_ready((s, d))
        stats.record((time.perf_counter() - t0) * 1e3)
        out.append((np.asarray(s), np.asarray(d)))
    return out, stats
