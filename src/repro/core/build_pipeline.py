"""Staged streaming index build — Algorithm 1 as a device-side pipeline.

The legacy :meth:`~repro.core.builder.IndexBuilder.build_legacy` path is
host-bound: a Python loop walks documents, filters rows with per-doc
``np.flatnonzero``, accumulates every posting in host lists and only then
materialises the global CSR — so index capacity is capped by one host's
RAM even though serving (PR 2) is not.  This module splits the build into
four explicit stages, each independently testable and each keeping the
heavy work on device:

  stage 1  unique-term extraction   ``make_unique_terms_fn`` — vectorised
           (sort + first-occurrence compaction) replacement for the
           ``unique_terms_host`` Python loop; jit'd, vmap'd over docs.
  stage 2  fused interaction pass   the existing
           ``make_batch_interaction_fn`` v-d pass, with the Algorithm-1
           ``tf > sigma`` filter and row compaction moved ON DEVICE
           (``make_compact_rows_fn``: mask + fixed-capacity stable-sort
           compaction instead of host ``flatnonzero`` per doc).  Each
           batch leaves the device as one term-sorted posting run.
  stage 3  spill layer              :class:`RunSpiller` flushes the
           per-batch term-sorted runs — in host memory by default, to an
           on-disk ``spill_dir`` when given one — so resident host bytes
           are bounded by a single run, not by total nnz.
  stage 4  k-way run merge          :func:`~repro.core.index.
           build_shard_from_runs` assembles per-shard local CSRs directly
           from ``plan_term_ranges`` cuts; ``dist.partition.
           partitioned_from_runs`` stacks them into a PartitionedIndex
           that is *born sharded* — no host ever holds the global
           doc_ids/values skeleton (each shard needs only the runs and
           its own term range, which is exactly what one pod would hold).

Exactness: the run rows are sliced from the same jit'd interaction pass
the legacy path uses (same batch padding, same per-doc vmap), the tf
filter compares integer-valued float32 sums (exact in any order), and the
merge lexsorts by (term, doc) exactly like ``build_from_rows`` — so the
streamed-and-merged index is bitwise-identical to the legacy host-CSR
build (tests/test_build_pipeline.py holds K ∈ {1,2,4} x four retrievers
to ``rtol=0, atol=0``).
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.base import SeineConfig
from .index import SegmentInvertedIndex, build_shard_from_runs
from .interactions import init_interaction_params
from .providers import EmbeddingProvider
from .vocab import Vocabulary

_log = obs.get_logger("repro.core.build")

FETCH_BUCKET = 2048   # run rows fetched per bucket (see stream_runs)


# ---------------------------------------------------------------------------
# stage 1: device-side unique-term extraction
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_unique_terms_fn(max_uniq: int):
    """jit'd (tokens (B, Lp) int32) -> (B, max_uniq) int32, -1 padded.

    Per doc: sort tokens ascending (pads sort first), keep first
    occurrences of non-negative values, scatter-compact into a fixed
    ``max_uniq`` capacity.  Matches ``np.unique(tok[tok >= 0])[:max_uniq]``
    exactly (ascending order, smallest ``max_uniq`` slots on overflow).
    Cached per ``max_uniq`` so repeated builds reuse the compiled fn.
    """
    def one_doc(tok):
        x = jnp.sort(tok)
        first = (x >= 0) & jnp.concatenate(
            [jnp.ones((1,), bool), x[1:] != x[:-1]])
        pos = jnp.cumsum(first) - 1
        out = jnp.full((max_uniq,), -1, jnp.int32)
        return out.at[jnp.where(first, pos, max_uniq)].set(x, mode="drop")

    return jax.jit(jax.vmap(one_doc))


# ---------------------------------------------------------------------------
# stage 2: device-side filter + row compaction (one term-sorted run / batch)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_compact_rows_fn(vocab_size: int, sigma: float,
                         tf_index: Optional[int]):
    """jit'd (vals (B,U,n_b,n_f), uniq (B,U), doc_start ()) ->
    (term_ids (B*U,), doc_ids (B*U,), values (B*U,n_b,n_f), n_valid ()).
    Cached per (vocab_size, sigma, tf_index) — repeated builds reuse the
    compiled fn instead of re-tracing per IndexBuilder instance.

    Replaces the host per-doc ``np.flatnonzero`` loop: the Algorithm-1
    line-8 filter (``tf > sigma``; exact — tf sums are integer-valued
    float32) and the survivor compaction run on device.  Surviving rows
    are stable-sorted by term id (invalid rows keyed ``vocab_size``, so
    they sink to the tail); because the (B, U) flattening is doc-major,
    doc ids stay ascending within each term — the run is term-sorted and
    host-side work is one ``[:n_valid]`` slice.
    """
    def compact(vals, uniq, doc_start):
        B, U = uniq.shape
        mask = uniq >= 0
        if tf_index is not None:      # Algorithm 1 line 8: filter(tf > sigma)
            mask &= vals[..., tf_index].sum(-1) > sigma
        docs = doc_start + jnp.broadcast_to(
            jnp.arange(B, dtype=jnp.int32)[:, None], (B, U))
        flat_mask = mask.reshape(-1)
        key = jnp.where(flat_mask, uniq.reshape(-1), vocab_size)
        order = jnp.argsort(key, stable=True)
        return (uniq.reshape(-1)[order], docs.reshape(-1)[order],
                vals.reshape((B * U,) + vals.shape[2:])[order],
                flat_mask.sum())

    return jax.jit(compact)


# ---------------------------------------------------------------------------
# stage 3: spill layer — term-sorted posting runs
# ---------------------------------------------------------------------------

@dataclass
class PostingRun:
    """One term-sorted run of posting triples (doc ascending within term).

    Either resident (arrays held) or spilled (``path`` set, arrays None).
    """
    n_rows: int
    nbytes: int
    term_ids: Optional[np.ndarray] = None   # (n,) int32, ascending
    doc_ids: Optional[np.ndarray] = None    # (n,) int32, asc within term
    values: Optional[np.ndarray] = None     # (n, n_b, n_f) float32
    path: Optional[str] = None

    @classmethod
    def from_arrays(cls, term_ids: np.ndarray, doc_ids: np.ndarray,
                    values: np.ndarray) -> "PostingRun":
        nbytes = term_ids.nbytes + doc_ids.nbytes + values.nbytes
        return cls(n_rows=int(term_ids.shape[0]), nbytes=nbytes,
                   term_ids=term_ids, doc_ids=doc_ids, values=values)

    def load(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if self.term_ids is not None:
            return self.term_ids, self.doc_ids, self.values
        with np.load(self.path) as z:
            return z["term_ids"], z["doc_ids"], z["values"]

    def ids(self) -> Tuple[np.ndarray, np.ndarray]:
        """(term_ids, doc_ids) WITHOUT the values payload.

        The hot-term sub-shard planner needs the doc ids of a few split
        terms before the stage-4 assembly pass; lazy npz member access
        keeps the values bulk (~n_b*n_f*4 bytes/row vs 8) on disk for
        spilled runs, so the extra planning pass stays O(id bytes).
        """
        if self.term_ids is not None:
            return self.term_ids, self.doc_ids
        with np.load(self.path) as z:
            return z["term_ids"], z["doc_ids"]

    def term_counts(self, vocab_size: int) -> np.ndarray:
        """(|v|,) int64 postings per term in this run.

        Reads ONLY the term_ids member of a spilled npz (member access is
        lazy) — the values payload (~n_b*n_f*4 bytes/row vs 4) stays on
        disk during stage-4 range planning.
        """
        if self.term_ids is not None:
            t = self.term_ids
        else:
            with np.load(self.path) as z:
                t = z["term_ids"]
        # bincount takes int32 directly; an astype here would transiently
        # double the id bytes over the whole run for nothing
        return np.bincount(t, minlength=vocab_size)


class RunSpiller:
    """Accumulates per-batch posting runs, optionally spilling to disk.

    With ``spill_dir`` each run is written to ``run_<i>.npz`` and its host
    arrays dropped, so resident host bytes stay bounded by the largest
    single run (the per-batch working set) instead of total nnz — the
    memory telemetry the build benchmark asserts on.
    """

    def __init__(self, spill_dir: Optional[str] = None):
        self.spill_dir = spill_dir
        if spill_dir is not None:
            os.makedirs(spill_dir, exist_ok=True)
        self.runs: List[PostingRun] = []
        self.run_bytes: List[int] = []      # per-batch run size (telemetry)
        self.resident_bytes = 0
        self.peak_host_bytes = 0
        self.spilled_bytes = 0

    def add(self, term_ids: np.ndarray, doc_ids: np.ndarray,
            values: np.ndarray) -> PostingRun:
        run = PostingRun.from_arrays(term_ids, doc_ids, values)
        self.run_bytes.append(run.nbytes)
        # the freshly produced run is resident while we decide its fate
        self.peak_host_bytes = max(self.peak_host_bytes,
                                   self.resident_bytes + run.nbytes)
        if self.spill_dir is not None:
            run.path = os.path.join(self.spill_dir,
                                    f"run_{len(self.runs):05d}.npz")
            np.savez(run.path, term_ids=term_ids, doc_ids=doc_ids,
                     values=values)
            run.term_ids = run.doc_ids = run.values = None
            self.spilled_bytes += run.nbytes
            obs.counter("seine_build_runs_spilled_total",
                        "posting runs written to spill_dir").inc()
            obs.counter("seine_build_spill_bytes_total",
                        "bytes spilled to disk").inc(run.nbytes)
        else:
            self.resident_bytes += run.nbytes
        self.runs.append(run)
        obs.counter("seine_build_runs_total",
                    "posting runs produced (resident or spilled)").inc()
        obs.gauge("seine_build_last_run_bytes",
                  "size of the newest per-batch run").set(run.nbytes)
        obs.gauge("seine_build_resident_bytes",
                  "run bytes currently resident on host").set(
            self.resident_bytes)
        obs.gauge("seine_build_peak_host_bytes",
                  "peak resident run bytes this build").set(
            self.peak_host_bytes)
        return run

    @property
    def total_nnz(self) -> int:
        return sum(r.n_rows for r in self.runs)

    @property
    def total_nnz_bytes(self) -> int:
        return sum(self.run_bytes)


# ---------------------------------------------------------------------------
# the staged pipeline
# ---------------------------------------------------------------------------

@dataclass
class BuildStats:
    """Telemetry from one streaming build (BENCH_build.json feeds on it).

    ``peak_host_bytes`` is scoped to the STREAMING phase (stages 1-3):
    with a spill dir it equals the largest single per-batch run instead
    of total nnz.  The stage-4 merge is O(shard nnz) per shard — and the
    returned in-process index object necessarily holds every shard it
    stacks; the run/spill bound is the per-pod story, where each host
    streams its doc range and merges only its own term-range shard.
    """
    n_docs: int = 0
    n_batches: int = 0
    build_s: float = 0.0
    run_bytes: List[int] = field(default_factory=list)  # per batch
    peak_host_bytes: int = 0       # max resident run bytes during streaming
    spilled_bytes: int = 0
    total_nnz: int = 0
    total_nnz_bytes: int = 0

    @property
    def docs_per_s(self) -> float:
        return self.n_docs / max(self.build_s, 1e-9)

    def summary(self) -> str:
        return (f"{self.n_docs} docs in {self.build_s:.2f}s "
                f"({self.docs_per_s:.0f} docs/s), {self.n_batches} runs, "
                f"peak host {self.peak_host_bytes/1e6:.1f} MB "
                f"(total postings {self.total_nnz_bytes/1e6:.1f} MB"
                f"{', spilled' if self.spilled_bytes else ''})")


def compute_doc_seg_lengths(tokens: np.ndarray, seg_ids: np.ndarray,
                            n_b: int) -> Tuple[np.ndarray, np.ndarray]:
    """(doc_len (n_docs,), seg_len (n_docs, n_b)) in one bincount pass.

    Replaces the per-segment Python loop over ``n_b``: valid tokens are
    counted into the flattened (doc, segment) grid with a single bincount
    (the one-hot-einsum contraction, done as integer counting so the
    float32 result is exact).
    """
    n_docs = tokens.shape[0]
    valid = tokens >= 0
    flat = (np.arange(n_docs, dtype=np.int64)[:, None] * n_b
            + np.clip(seg_ids, 0, n_b - 1))
    seg_len = np.bincount(flat[valid].ravel(),
                          minlength=n_docs * n_b).reshape(n_docs, n_b)
    return valid.sum(1).astype(np.float32), seg_len.astype(np.float32)


class BuildPipeline:
    """Stages 1-4 wired together over an embedding provider + vocabulary.

    Mirrors the :class:`~repro.core.builder.IndexBuilder` constructor; the
    builder's ``build`` is now a thin wrapper over :meth:`build_index`.
    """

    def __init__(self, cfg: SeineConfig, vocab: Vocabulary,
                 provider: EmbeddingProvider,
                 ip: Optional[Dict[str, Any]] = None,
                 functions: Optional[Sequence[str]] = None):
        self.cfg = cfg
        self.vocab = vocab
        self.provider = provider
        self.functions = tuple(functions or cfg.functions)
        self.ip = ip if ip is not None else init_interaction_params(
            jax.random.key(17), provider.embed_dim)
        self._idf = jnp.asarray(vocab.idf)

    # -- stages 1-3: tokens -> spilled term-sorted runs ---------------------

    def stream_runs(self, tokens: np.ndarray, seg_ids: np.ndarray, *,
                    batch_size: int = 32, max_uniq: Optional[int] = None,
                    spill_dir: Optional[str] = None, verbose: bool = False,
                    doc_start: int = 0
                    ) -> Tuple[RunSpiller, BuildStats]:
        """Run the device pipeline over all docs, emitting one term-sorted
        posting run per batch into a :class:`RunSpiller`.

        ``doc_start`` offsets every emitted doc id: row ``i`` of ``tokens``
        lands as doc ``doc_start + i``.  The live-index delta builds
        (:class:`~repro.dist.live.LiveIndex`) use it to place freshly
        ingested documents after the base corpus in the shared doc-id
        space; the offset rides the same ``jnp.int32`` batch-offset input
        the compaction kernel already takes, so an offset build is
        bitwise-identical to the same docs built at position zero in a
        larger corpus."""
        from .builder import make_batch_interaction_fn

        n_docs, Lp = tokens.shape
        n_b = self.cfg.n_segments
        max_uniq = max_uniq or min(Lp, 512)
        uniq_fn = make_unique_terms_fn(max_uniq)
        interact_fn = make_batch_interaction_fn(
            self.provider, self._idf, self.ip, n_b, self.functions)
        tf_i = (self.functions.index("tf")
                if "tf" in self.functions else None)
        compact_fn = make_compact_rows_fn(
            self.vocab.size, float(self.cfg.sigma_index), tf_i)

        spiller = RunSpiller(spill_dir)
        t0 = time.perf_counter()
        # span semantics: stage-1/2 spans time the async DISPATCH, the
        # stage-2b span absorbs the device sync (int(n_valid) blocks), and
        # stage 3 the host copies + spill I/O — together they partition
        # the wall clock without adding any synchronisation of their own
        with obs.span("build.stream_runs"):
            for s in range(0, n_docs, batch_size):
                e = min(s + batch_size, n_docs)
                pad = batch_size - (e - s)
                tb = np.pad(tokens[s:e], ((0, pad), (0, 0)),
                            constant_values=-1)
                sb = np.pad(seg_ids[s:e], ((0, pad), (0, 0)),
                            constant_values=n_b - 1)
                tb_d = jnp.asarray(tb)
                with obs.span("build.stage1.uniq"):
                    ub = uniq_fn(tb_d)                           # stage 1
                with obs.span("build.stage2.interact"):
                    vals = interact_fn(tb_d, jnp.asarray(sb), ub)  # stage 2
                with obs.span("build.stage2b.compact"):
                    terms, docs, rows, n_valid = compact_fn(
                        vals, ub, jnp.int32(doc_start + s))      # stage 2b
                    n = int(n_valid)
                # padded docs (rows >= e): only -1 uniq slots -> masked out
                with obs.span("build.stage3.spill"):
                    # fetch a prefix rounded up to whole buckets: a device
                    # slice of each distinct length is a program of its
                    # own, compiled anew on nearly every batch
                    m = min(-(-n // FETCH_BUCKET) * FETCH_BUCKET,
                            terms.shape[0])
                    th, dh, vh = jax.device_get(
                        (terms[:m], docs[:m], rows[:m]))
                    spiller.add(th[:n].copy(), dh[:n].copy(),
                                np.array(vh[:n], np.float32))  # stage 3
                obs.counter("seine_build_docs_total",
                            "docs through build stages 1-3").inc(e - s)
                obs.counter("seine_build_batches_total",
                            "device batches streamed").inc()
                if verbose and (s // batch_size) % 16 == 0:
                    _log.info("streamed", docs=f"{e}/{n_docs}",
                              s=f"{time.perf_counter() - t0:.1f}",
                              resident_mb=(
                                  f"{spiller.resident_bytes / 1e6:.1f}"))
        stats = BuildStats(
            n_docs=n_docs, n_batches=len(spiller.runs),
            build_s=time.perf_counter() - t0,
            run_bytes=list(spiller.run_bytes),
            peak_host_bytes=spiller.peak_host_bytes,
            spilled_bytes=spiller.spilled_bytes,
            total_nnz=spiller.total_nnz,
            total_nnz_bytes=spiller.total_nnz_bytes)
        obs.gauge("seine_build_docs_per_s",
                  "stage 1-3 streaming throughput").set(stats.docs_per_s)
        obs.gauge("seine_build_total_nnz",
                  "postings streamed in the last build").set(stats.total_nnz)
        return spiller, stats

    # -- stage 4 entries ----------------------------------------------------

    def build_index(self, tokens: np.ndarray, seg_ids: np.ndarray, *,
                    batch_size: int = 32, max_uniq: Optional[int] = None,
                    spill_dir: Optional[str] = None, verbose: bool = False
                    ) -> Tuple[SegmentInvertedIndex, BuildStats]:
        """Full-vocabulary merge (K=1): the legacy return type, streamed."""
        spiller, stats = self.stream_runs(
            tokens, seg_ids, batch_size=batch_size, max_uniq=max_uniq,
            spill_dir=spill_dir, verbose=verbose)
        doc_len, seg_len = compute_doc_seg_lengths(
            tokens, seg_ids, self.cfg.n_segments)
        with obs.span("build.stage4.merge"):
            obs.gauge("seine_merge_fan_in",
                      "runs k-way-merged in stage 4").set(len(spiller.runs))
            index = build_shard_from_runs(
                spiller.runs, 0, self.vocab.size, idf=self.vocab.idf,
                doc_len=doc_len, seg_len=seg_len, n_docs=tokens.shape[0],
                vocab_size=self.vocab.size, n_b=self.cfg.n_segments,
                functions=self.functions)
        return index, stats

    def build_partitioned(self, tokens: np.ndarray, seg_ids: np.ndarray,
                          k: int, *, batch_size: int = 32,
                          max_uniq: Optional[int] = None,
                          spill_dir: Optional[str] = None,
                          verbose: bool = False, mesh=None,
                          codec: str = "none",
                          codec_tile: Optional[int] = None):
        """Shard-native build: runs -> K term-range shards, directly.

        Returns ``(PartitionedIndex, BuildStats)``; the global
        doc_ids/values CSR is never materialised on the host — each shard
        is assembled independently from the runs and its term range (the
        per-pod unit of work at production scale).  ``codec`` packs the
        posting payload at merge time (``core.codec``): the raw stacked
        doc_ids exist only transiently inside stage 4.
        """
        from ..dist.partition import partitioned_from_runs

        spiller, stats = self.stream_runs(
            tokens, seg_ids, batch_size=batch_size, max_uniq=max_uniq,
            spill_dir=spill_dir, verbose=verbose)
        doc_len, seg_len = compute_doc_seg_lengths(
            tokens, seg_ids, self.cfg.n_segments)
        with obs.span("build.stage4.merge"):
            obs.gauge("seine_merge_fan_in",
                      "runs k-way-merged in stage 4").set(len(spiller.runs))
            pidx = partitioned_from_runs(
                spiller.runs, k, idf=self.vocab.idf, doc_len=doc_len,
                seg_len=seg_len, n_docs=tokens.shape[0],
                vocab_size=self.vocab.size, n_b=self.cfg.n_segments,
                functions=self.functions, mesh=mesh, codec=codec,
                codec_tile=codec_tile)
        return pidx, stats
