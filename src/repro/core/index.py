"""The segment-level inverted index (§2.3–2.4).

True posting-list layout (CSR over terms), all int32 / static-shape:

  term_offsets (|v|+1,)            posting-list boundaries
  doc_ids      (nnz,)              docs per term, sorted within each list
  values       (nnz, n_b, n_f)     atomic interaction rows  M(w, d)

Only pairs with tf(w,d) > sigma_index are stored; lookup of an absent pair
returns zeros (exactly the sigma=0 semantics). Random access is a fixed
32-step branchless binary search inside the term's posting range — static
shapes, vmap-able over (query-term x candidate-doc) batches, shardable, and
int32-safe at Gov2 scale (4e10 logical pairs; nnz per shard < 2^31).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

# Default posting-tile width for the two-level serving bisect: the fused
# kernel holds one fence row (every POSTING_TILE-th doc id, built here at
# index-build time) plus ONE posting tile in VMEM — O(Nmax/T + T) instead
# of the whole O(Nmax) doc-id row — so shard capacity is no longer VMEM-
# bound (~1-4M postings before; tens of millions now).  sqrt(Nmax) is the
# VMEM-optimal T; 256 covers the 64K-16M postings/shard band and keeps
# the tile DMA above the ~512 B efficiency floor.
POSTING_TILE = 256


def fence_count(n: int, tile: int = POSTING_TILE) -> int:
    """Number of fence entries covering ``n`` postings at ``tile`` spacing
    (at least one, so degenerate empty shards keep static shapes)."""
    return -(-max(int(n), 1) // int(tile))


def build_fences(doc_ids, tile: int = POSTING_TILE):
    """Every ``tile``-th doc id along the last axis: ``(..., N)`` ->
    ``(..., ceil(N/tile))``.

    The fence array is the first level of the serving bisect: restricted
    to one term's posting range [lo, hi) — always sorted, because a range
    never crosses a posting-list boundary — the fences bracket the single
    tile that can contain the lookup target.  The tail is padded with
    int32 max so fence values stay monotone past the data; padding fences
    are never *consulted* (the fence bisect is clamped to the tiles
    intersecting [lo, hi)), so the pad value cannot affect results.
    Works on numpy and jax arrays (jit-traceable: shapes are static).
    """
    xp = jnp if isinstance(doc_ids, jnp.ndarray) else np
    n = doc_ids.shape[-1]
    f = fence_count(n, tile)
    pad = f * tile - n
    if pad:
        width = [(0, 0)] * (doc_ids.ndim - 1) + [(0, pad)]
        doc_ids = xp.pad(doc_ids, width,
                         constant_values=np.iinfo(np.int32).max)
    return doc_ids[..., ::tile]


def _bisect(doc_ids: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
            target: jnp.ndarray, n_iter: int = 32) -> jnp.ndarray:
    """First position p in [lo, hi) with doc_ids[p] >= target (branchless)."""
    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        v = doc_ids.at[mid].get(mode="clip")
        go_right = (v < target) & (lo < hi)
        return jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid)
    lo, hi = jax.lax.fori_loop(0, n_iter, body, (lo, hi))
    return lo


def csr_lookup_positions(term_offsets: jnp.ndarray, doc_ids: jnp.ndarray,
                         term_ids: jnp.ndarray, doc_targets: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Random access into one CSR skeleton: ``(term, doc) -> (pos, in_list)``.

    ``term_ids`` must already be valid row indices for ``term_offsets``
    (clipped / localised by the caller — the global index clips raw query
    ids, a term-range shard passes shard-local ids).  ``in_list`` is True
    only where the posting list for the term actually stores ``doc_targets``;
    callers AND in their own validity masks (padding, ownership).
    """
    lo = term_offsets.at[term_ids].get(mode="clip")
    hi = term_offsets.at[term_ids + 1].get(mode="clip")
    pos = _bisect(doc_ids, lo, hi, doc_targets)
    in_list = (pos < hi) & (doc_ids.at[pos].get(mode="clip") == doc_targets)
    return pos, in_list


@runtime_checkable
class PairLookupIndex(Protocol):
    """What the serving engine dispatches on (the Eq. 4 lookup contract).

    Any index — the single-CSR :class:`SegmentInvertedIndex` here, the
    term-range :class:`~repro.dist.partition.PartitionedIndex` — that can
    materialise M_{q,d} rows (zeros for absent pairs, the sigma=0
    semantics) plus the per-doc/per-term stats QMeta needs is servable;
    retrievers never learn which one produced M.  ``cols``, a static
    tuple of function indices, narrows M's last axis to those functions,
    bit for bit the full M's ``[..., cols]``; the engine passes the
    functions its ranker reads.
    """
    idf: jnp.ndarray           # (|v|,)
    doc_len: jnp.ndarray       # (n_docs,)
    seg_len: jnp.ndarray       # (n_docs, n_b)
    n_docs: int
    vocab_size: int
    n_b: int
    functions: Tuple[str, ...]

    @property
    def nbytes(self) -> int: ...

    @property
    def avg_doc_len(self) -> jnp.ndarray: ...

    def fn_index(self, name: str) -> int: ...

    def lookup_pairs(self, term_ids: jnp.ndarray, doc_ids: jnp.ndarray,
                     *, cols: Optional[Tuple[int, ...]] = None
                     ) -> jnp.ndarray: ...

    def qd_matrix(self, query_terms: jnp.ndarray, doc_ids: jnp.ndarray,
                  *, impl: str = None, tile: Optional[int] = None,
                  cols: Optional[Tuple[int, ...]] = None
                  ) -> jnp.ndarray: ...

    def retrieve_topk(self, query_terms: jnp.ndarray, k: int,
                      score_block_fn, *, doc_block: Optional[int] = None,
                      impl: str = None, tile: Optional[int] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]: ...


@jax.tree_util.register_dataclass
@dataclass
class SegmentInvertedIndex:
    term_offsets: jnp.ndarray  # (|v|+1,) int32
    doc_ids: jnp.ndarray       # (nnz,) int32
    values: jnp.ndarray        # (nnz, n_b, n_f) float32
    idf: jnp.ndarray           # (|v|,)
    doc_len: jnp.ndarray       # (n_docs,) float32
    seg_len: jnp.ndarray       # (n_docs, n_b) float32 tokens per segment
    n_docs: int = dataclasses.field(metadata=dict(static=True), default=0)
    vocab_size: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_b: int = dataclasses.field(metadata=dict(static=True), default=1)
    functions: Tuple[str, ...] = dataclasses.field(
        metadata=dict(static=True), default=())
    # (ceil(nnz/POSTING_TILE),) int32 — every POSTING_TILE-th doc id, the
    # level-1 array of the tiled serving bisect.  Built by the CSR build
    # paths; None (legacy instances / old checkpoints) makes the lookup op
    # derive it on the fly from doc_ids.
    fences: Optional[jnp.ndarray] = None

    @property
    def nnz(self) -> int:
        return int(self.doc_ids.shape[0])

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in (self.term_offsets, self.doc_ids, self.values,
                             self.idf, self.doc_len, self.seg_len,
                             self.fences)
                   if a is not None)

    @property
    def avg_doc_len(self) -> jnp.ndarray:
        return jnp.mean(self.doc_len)

    def fn_index(self, name: str) -> int:
        return self.functions.index(name)

    # -- lookups (Eq. 4) ----------------------------------------------------

    def lookup_positions(self, term_ids: jnp.ndarray, doc_ids: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """term_ids (..., Q), doc_ids broadcastable (...,) ->
        (positions (..., Q), found (..., Q))."""
        w = term_ids.clip(0)
        d = jnp.broadcast_to(doc_ids[..., None], term_ids.shape)
        pos, in_list = csr_lookup_positions(self.term_offsets, self.doc_ids,
                                            w, d)
        return pos, in_list & (term_ids >= 0)

    def lookup_pairs(self, term_ids: jnp.ndarray, doc_ids: jnp.ndarray,
                     *, cols: Optional[Tuple[int, ...]] = None
                     ) -> jnp.ndarray:
        """(..., Q) term ids x (...,) doc ids -> (..., Q, n_b, n_f), or
        (..., Q, n_b, len(cols)) with ``cols``.  Missing pairs -> zeros."""
        pos, found = self.lookup_positions(term_ids, doc_ids)
        if cols is None:
            vals = self.values.at[pos].get(mode="clip")
        else:
            n_b = self.values.shape[1]
            vals = self.values.at[
                pos[..., None, None], jnp.arange(n_b)[:, None],
                jnp.asarray(cols, jnp.int32)[None, :]].get(mode="clip")
        return vals * found[..., None, None]

    def qd_matrix(self, query_terms: jnp.ndarray, doc_ids: jnp.ndarray,
                  *, impl: str = None, tile: Optional[int] = None,
                  cols: Optional[Tuple[int, ...]] = None
                  ) -> jnp.ndarray:
        """Stack rows for the query terms (Eq. 4).

        query_terms (Q,), doc_ids (B,) -> M_{q,d} (B, Q, n_b, n_f).

        ``impl`` picks the lookup expression:

        * ``None`` / ``"fused"`` — the fused serving path
          (``kernels.csr_lookup``: Pallas kernel on TPU, its routed-jnp
          lowering on CPU; per-term routing amortised over candidates);
        * ``"jnp"`` — the legacy broadcast + :meth:`lookup_pairs`
          composition, the XLA-partitionable expression mesh-placed
          engines keep (values sharded over 'model' by
          ``dist.sharding.shard_index``);
        * ``"interpret"`` — force the Pallas interpreter (parity tests).

        ``tile`` overrides the kernel's posting-tile width (default
        ``POSTING_TILE``); the jnp path ignores it (no tiling there).
        Every impl x tile is held bitwise-equal to
        ``csr_lookup_positions`` by tests/test_kernels.py::TestCsrLookup.
        ``cols`` narrows M to those function columns on every impl.
        """
        if impl not in (None, "fused", "jnp", "interpret"):
            raise ValueError(f"unknown lookup impl {impl!r}; supported: "
                             "'fused', 'jnp', 'interpret'")
        if impl == "jnp":
            q = jnp.broadcast_to(query_terms[None],
                                 (doc_ids.shape[0],) + query_terms.shape)
            return self.lookup_pairs(q, doc_ids, cols=cols)
        from ..kernels.csr_lookup import csr_lookup
        return csr_lookup(
            self.term_offsets[None], self.doc_ids[None], self.values[None],
            None, None, query_terms, doc_ids,
            fences=None if self.fences is None else self.fences[None],
            tile=tile, interpret=True if impl == "interpret" else None,
            cols=cols)

    def retrieve_topk(self, query_terms: jnp.ndarray, k: int,
                      score_block_fn, *, doc_block: Optional[int] = None,
                      impl: str = None, tile: Optional[int] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """First-stage top-k over the WHOLE corpus — no candidate set.

        Walks the query terms' posting lists block-of-docs at a time
        (``kernels.csr_lookup.csr_retrieve_topk``), scores each block
        with ``score_block_fn(M_block (block, Q, n_b, n_f), doc_ids
        (block,)) -> (block,)``, and streams a device-side
        ``jax.lax.top_k``.  Returns ``(scores (k,), doc_ids (k,))``,
        ties broken toward the lower doc id; slots past the corpus size
        carry ``-inf`` / ``-1``.  Exact vs brute-force score-all-docs:
        the M blocks are bitwise-equal to the lookup path (rtol=0/atol=0
        in tests/test_retrieval.py) and the single-block default is
        score-bitwise too; see ``csr_retrieve_topk`` for the multi-block
        ulp caveat.  ``impl`` as in :meth:`qd_matrix` (``"jnp"`` forces
        the jnp scan, ``"interpret"`` the Pallas interpreter).  Not
        jit'd — callers jit around the closure.
        """
        from ..kernels.csr_lookup import csr_retrieve_topk
        return csr_retrieve_topk(
            self.term_offsets[None], self.doc_ids[None], self.values[None],
            None, None, None, query_terms, n_docs=self.n_docs, k=k,
            score_block_fn=score_block_fn, doc_block=doc_block, tile=tile,
            impl=impl)


def merge_run_parts(parts: list, t_lo: int, t_hi: int, *, n_b: int,
                    n_f: int, out: Optional[np.ndarray] = None
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Merge ``[(term_ids, doc_ids, values), ...]`` slices — each already
    (term, doc)-sorted and restricted to ``[t_lo, t_hi)`` — into one local
    CSR: ``(term_offsets (span+1,) int32, doc_ids (n,) int32, values
    (n, n_b, n_f) float32)`` with offsets localised to the range.

    Rows lexsort by (term, doc), the same order :func:`build_from_rows`
    produces, which is what keeps the streamed build bitwise-equal to the
    legacy one; a single part skips the sort outright (it is already
    ordered — the partition_index compatibility path, one run per index,
    hits this for every shard).  Only the ids are concatenated and
    sorted: each part's value rows are scattered once, straight to their
    merged positions in ``out`` (at least ``n`` rows; allocated when not
    given), so the values bulk is never copied twice — at MQ2007 scale
    it is ~10 GB.
    """
    span = t_hi - t_lo
    if parts:
        t = np.concatenate([p[0] for p in parts]).astype(np.int64) - t_lo
        d = np.concatenate([p[1] for p in parts])
    else:
        t = np.zeros(0, np.int64)
        d = np.zeros(0, np.int32)
    n = t.shape[0]
    if out is None:
        out = np.empty((n, n_b, n_f), np.float32)
    if len(parts) > 1:
        order = np.lexsort((d, t))
        t, d = t[order], d[order]
        dest = np.empty(n, np.int64)
        dest[order] = np.arange(n)
        s = 0
        for p in parts:
            e = s + p[2].shape[0]
            out[dest[s:e]] = p[2]
            s = e
    elif parts:
        out[:n] = parts[0][2]
    counts = np.bincount(t, minlength=max(span, 1))[:max(span, 1)]
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return offsets, np.asarray(d, np.int32), out[:n]


def shard_csr_from_runs(runs, t_lo: int, t_hi: int, *, n_b: int, n_f: int
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One term range's local CSR from term-sorted runs (one disk pass).

    Each run contributes a contiguous searchsorted slice — copied for
    spilled runs, so host memory is O(range nnz) plus one loaded run,
    never the global posting space.  Assembling MANY ranges at once
    should instead slice every range per run load
    (``dist.partition.partitioned_from_runs`` does) so spilled runs are
    read once, not once per shard.
    """
    parts = []
    for run in runs:
        spilled = getattr(run, "term_ids", None) is None
        t, d, v = run.load()
        lo = int(np.searchsorted(t, t_lo, side="left"))
        hi = int(np.searchsorted(t, t_hi, side="left"))
        if hi > lo:
            sl = (t[lo:hi], d[lo:hi], v[lo:hi])
            parts.append(tuple(a.copy() for a in sl) if spilled else sl)
    return merge_run_parts(parts, t_lo, t_hi, n_b=n_b, n_f=n_f)


def build_shard_from_runs(runs, t_lo: int, t_hi: int, *, idf: np.ndarray,
                          doc_len: np.ndarray, seg_len: np.ndarray,
                          n_docs: int, vocab_size: int, n_b: int,
                          functions: Tuple[str, ...]
                          ) -> SegmentInvertedIndex:
    """Assemble ONE term-range shard's local CSR from term-sorted runs.

    ``runs``: objects with ``load() -> (term_ids, doc_ids, values)`` where
    ``term_ids`` is ascending (build_pipeline.PostingRun).  Only the rows
    with ``t_lo <= term < t_hi`` are touched — each run contributes a
    contiguous slice found by searchsorted, so assembling shard ``k``
    needs the runs plus O(shard nnz) host memory, never the global CSR
    (this is the per-pod unit of work of the shard-native build).

    The result is a self-contained index over the *local* term range:
    ``term_offsets`` has ``t_hi - t_lo + 1`` rows, ``idf`` is sliced, and
    ``vocab_size`` is the span.  With ``(0, |v|)`` this is exactly the
    global index — the compatibility path ``IndexBuilder.build`` uses —
    and rows sort by (term, doc) exactly like :func:`build_from_rows`
    (stable lexsort; one row per (term, doc) pair, so the order — and the
    bits — match the legacy host build).
    """
    offsets, d, v = shard_csr_from_runs(runs, t_lo, t_hi, n_b=n_b,
                                        n_f=len(functions))
    span = t_hi - t_lo
    return SegmentInvertedIndex(
        term_offsets=jnp.asarray(offsets),
        doc_ids=jnp.asarray(d.astype(np.int32)),
        values=jnp.asarray(v.astype(np.float32)),
        fences=jnp.asarray(build_fences(d.astype(np.int32))),
        idf=jnp.asarray(np.asarray(idf)[t_lo:t_hi].astype(np.float32)),
        doc_len=jnp.asarray(np.asarray(doc_len).astype(np.float32)),
        seg_len=jnp.asarray(np.asarray(seg_len).astype(np.float32)),
        n_docs=int(n_docs), vocab_size=int(span), n_b=int(n_b),
        functions=tuple(functions),
    )


def build_from_rows(doc_ids: np.ndarray, term_ids: np.ndarray,
                    values: np.ndarray, *, idf: np.ndarray,
                    doc_len: np.ndarray, seg_len: np.ndarray,
                    n_docs: int, vocab_size: int,
                    functions: Tuple[str, ...]) -> SegmentInvertedIndex:
    """Assemble the index from flat (doc, term, value-row) triples (host)."""
    order = np.lexsort((doc_ids, term_ids))
    t = term_ids[order].astype(np.int64)
    counts = np.bincount(t, minlength=vocab_size)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    n_b = values.shape[1]
    sorted_docs = doc_ids[order].astype(np.int32)
    return SegmentInvertedIndex(
        term_offsets=jnp.asarray(offsets),
        doc_ids=jnp.asarray(sorted_docs),
        values=jnp.asarray(values[order].astype(np.float32)),
        fences=jnp.asarray(build_fences(sorted_docs)),
        idf=jnp.asarray(idf.astype(np.float32)),
        doc_len=jnp.asarray(doc_len.astype(np.float32)),
        seg_len=jnp.asarray(seg_len.astype(np.float32)),
        n_docs=int(n_docs), vocab_size=int(vocab_size), n_b=int(n_b),
        functions=tuple(functions),
    )
