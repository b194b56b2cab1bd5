"""Term-range partitioned SEINE index (cross-pod index sharding).

``dist.sharding.shard_index`` scales the *values* of a
:class:`~repro.core.index.SegmentInvertedIndex` across devices but
replicates the CSR skeleton (``term_offsets`` |v|+1, ``doc_ids`` nnz) on
every one of them — fine up to ~2^31 nnz per pod, a hard wall past it.
:class:`PartitionedIndex` removes that last replicated O(|v|+nnz)
structure: posting lists split into K *contiguous term ranges* balanced by
nnz (``dist.sharding.plan_term_ranges``), each shard carrying its own
local ``term_offsets`` / ``doc_ids`` / ``values``, so index capacity
scales linearly with pod count.  Only two small structures replicate:

  term_to_shard (|v|,)   routing table: global term -> owning shard
  range_lo      (K,)     term-range starts: global term -> shard-local row

Query time is the classic term-partitioned plan, SPMD-shaped: every shard
receives the full query, masks the terms it owns, resolves them against
its local CSR (the same 32-step branchless bisect as the global index, via
``core.index.csr_lookup_positions``), and emits a *partial* M_{q,d} with
exact zeros for terms it does not own.  Partial rows merge by summation —
a psum over the shard axis once the leading K dim is placed on a mesh axis
(``dist.sharding.shard_partitioned_index``).  Because every (q, d) entry
is owned by exactly one shard and absent pairs are zeros by construction,
``x + 0 + ... + 0`` reproduces the single-CSR lookup bit-for-bit: the
sigma=0 semantics survive partitioning exactly (the oracle-parity harness
in tests/test_partitioned_index.py holds every lookup path to that).

Shards are padded to common (Vmax+1,) / (Nmax,) widths and *stacked* on a
leading K axis, so one jitted program serves any K and the XLA partitioner
turns the merge into an all-reduce when K tiles the mesh's model axis.
Padding rows are empty posting lists (offsets pinned at the shard's nnz)
and can never be "found": lookups stay exact whatever the padding holds.

That partial-sum plan is the SPMD *expression* — on a single host it pays
K full-width bisects and K dense partial M matrices for one useful row,
which PR 3's BENCH_partitioned.json showed losing 2-3x to the replicated
path.  Serving therefore defaults to the fused routed lookup
(``kernels.csr_lookup``: Pallas kernel on TPU, routed-jnp lowering on
CPU) that resolves each (term, doc) pair against its owning shard only;
the ``impl="jnp"`` partial-sum path remains the mesh-placed expression
and the SPMD oracle.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.index import csr_lookup_positions, merge_run_parts
from ..kernels.csr_lookup.ref import gather_rows


@jax.tree_util.register_dataclass
@dataclass
class PartitionedIndex:
    """K term-range shards of a SegmentInvertedIndex, stacked on axis 0.

    With ``codec="none"`` the posting payload is the raw layout below.
    With a packed codec (``core.codec``) the raw ``doc_ids`` row is
    replaced by the tile-compressed quadruple ``packed_words`` /
    ``tile_bits`` / ``tile_base`` / ``tile_word_off`` (``doc_ids`` is
    None — nbytes and the per-device projections therefore account for
    the packed buffers by construction, never a reconstructed unpacked
    view), and under ``"packed-q8"`` the f32 ``values`` additionally
    give way to int8 ``values_q`` + per-(shard, local term) ``value_scale``.
    Ids decode losslessly so every lookup/retrieve path stays
    bitwise-equal to the uncompressed index; only q8 values are
    approximate (gated on effectiveness, benchmarks/bench_compressed.py).
    """
    term_offsets: jnp.ndarray   # (K, Vmax+1) int32, shard-local CSR offsets
    doc_ids: Optional[jnp.ndarray]  # (K, Nmax) int32 padded with n_docs;
    #                             None under a packed codec
    values: Optional[jnp.ndarray]   # (K, Nmax, n_b, n_f) f32 zero-padded;
    #                             None under codec "packed-q8"
    term_to_shard: jnp.ndarray  # (|v|,) int32 routing table (replicated)
    range_lo: jnp.ndarray       # (K,) int32 first global term of each shard
    idf: jnp.ndarray            # (|v|,)
    doc_len: jnp.ndarray        # (n_docs,) float32
    seg_len: jnp.ndarray        # (n_docs, n_b) float32
    n_docs: int = dataclasses.field(metadata=dict(static=True), default=0)
    vocab_size: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_b: int = dataclasses.field(metadata=dict(static=True), default=1)
    n_shards: int = dataclasses.field(metadata=dict(static=True), default=1)
    functions: Tuple[str, ...] = dataclasses.field(
        metadata=dict(static=True), default=())
    # (K, ceil(Nmax/POSTING_TILE)) int32 — per-shard fence rows for the
    # kernel's two-level bisect (built at merge time; None on legacy
    # checkpoints -> derived on the fly by the lookup op).  Packed codecs
    # keep fences RAW — they are the tile anchors the decode resolves
    # against — and always carry them.
    fences: Optional[jnp.ndarray] = None
    # (K,) int32 — last global term (inclusive) with postings in shard k.
    # Without doc-range sub-shards this is just the next range_lo minus
    # one; with them, boundary terms appear in BOTH neighbours' ranges.
    # None (legacy checkpoints) falls back to table-based ownership.
    range_hi: Optional[jnp.ndarray] = None
    # (K,) int32 doc-range sub-shard tables: split_term[k] is the global
    # term whose posting list CONTINUES into shard k from shard k-1 (-1
    # when shard k starts on a fresh term), split_doc[k] the first doc id
    # shard k owns of it.  None when no hot term was split — then routing
    # is per term and the kernel keeps its (Q,)-stream fast path.
    split_term: Optional[jnp.ndarray] = None
    split_doc: Optional[jnp.ndarray] = None
    # -- codec axis (core.codec tile-compressed postings) -------------------
    codec: str = dataclasses.field(metadata=dict(static=True),
                                   default="none")
    codec_tile: int = dataclasses.field(metadata=dict(static=True),
                                        default=0)
    max_tile_words: int = dataclasses.field(metadata=dict(static=True),
                                            default=0)
    # pack-time loop-bound hint for the CPU two-level bisect: (max tiles
    # any term's routed range spans, max posting-list length).  (0, 0) =
    # unknown (legacy checkpoints) -> worst-case iteration counts.
    codec_spans: Tuple[int, int] = dataclasses.field(
        metadata=dict(static=True), default=(0, 0))
    packed_words: Optional[jnp.ndarray] = None   # (K, W) int32
    tile_bits: Optional[jnp.ndarray] = None      # (K, F) int32 in {0,4,8,16,32}
    tile_base: Optional[jnp.ndarray] = None      # (K, F) int32 FOR bases
    tile_word_off: Optional[jnp.ndarray] = None  # (K, F+1) int32 prefix sums
    values_q: Optional[jnp.ndarray] = None       # (K, Nmax, n_b, n_f) int8
    value_scale: Optional[jnp.ndarray] = None    # (K, Vmax) f32 per-term

    @property
    def nnz(self) -> int:
        """True stored pairs (padding excluded)."""
        return int(np.asarray(self.term_offsets[:, -1]).sum())

    @property
    def nmax(self) -> int:
        """Padded postings per shard row (the stacked layout's width)."""
        a = self.values if self.values is not None else self.values_q
        return int(a.shape[1])

    def _packed(self):
        """The codec quadruple in the order the kernels take it."""
        return (self.packed_words, self.tile_bits, self.tile_base,
                self.tile_word_off)

    @property
    def _serve_values(self):
        """The values array lookups read: f32, or int8 under q8 (the
        kernels dequantise against ``value_scale`` on the fly)."""
        return self.values_q if self.codec == "packed-q8" else self.values

    def _check_lookup_impl(self, impl):
        if self.codec != "none" and impl == "jnp":
            raise ValueError(
                f"impl='jnp' (the mesh partial-sum expression) does not "
                f"support codec {self.codec!r}: packed postings have no "
                "XLA-partitionable per-shard bisect; serve packed indexes "
                "with the fused lookup, or build with codec='none' for "
                "mesh placement")

    def _sharded_arrays(self):
        """Arrays stacked on the leading K axis (split over devices)."""
        return tuple(a for a in (self.term_offsets, self.doc_ids,
                                 self.values, self.fences,
                                 self.packed_words, self.tile_bits,
                                 self.tile_base, self.tile_word_off,
                                 self.values_q, self.value_scale)
                     if a is not None)

    @property
    def posting_nbytes(self) -> int:
        """Bytes of the per-posting payload only — ids (raw or packed,
        codec sidecars included) + values (+ scales) — the denominator
        ``codec_shrink`` is defined on; fences and replicated stats are
        common to both codecs and excluded."""
        arrs = (self.doc_ids, self.values, self.packed_words,
                self.tile_bits, self.tile_base, self.tile_word_off,
                self.values_q, self.value_scale)
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in arrs if a is not None)

    def _replicated_arrays(self):
        """O(|v|) / O(n_docs) / O(K) leftovers every device holds."""
        return tuple(a for a in (self.term_to_shard, self.range_lo,
                                 self.range_hi, self.split_term,
                                 self.split_doc, self.idf, self.doc_len,
                                 self.seg_len) if a is not None)

    @property
    def nbytes(self) -> int:
        """Total bytes across all shards (padding included)."""
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self._sharded_arrays() +
                   self._replicated_arrays())

    @property
    def per_device_nbytes(self) -> int:
        """Capacity projection: bytes one device holds with the K shards
        spread over K devices — its 1/K slice of the stacked shard arrays
        plus every replicated structure (routing table + per-doc stats).
        For what the *current* placement actually costs per device, use
        :attr:`placed_per_device_nbytes`."""
        sharded = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in self._sharded_arrays())
        replicated = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                         for a in self._replicated_arrays())
        return sharded // self.n_shards + replicated

    @property
    def placed_per_device_nbytes(self) -> int:
        """Bytes per device under the arrays' *actual* shardings (falls
        back to full size for unplaced / single-device arrays — e.g. when
        the mesh's model axis does not tile K and the divisibility guard
        replicated the stacked shards)."""
        total = 0
        for a in self._sharded_arrays() + self._replicated_arrays():
            shape = (a.sharding.shard_shape(a.shape)
                     if hasattr(a, "sharding") else a.shape)
            total += int(np.prod(shape)) * a.dtype.itemsize
        return total

    @property
    def avg_doc_len(self) -> jnp.ndarray:
        return jnp.mean(self.doc_len)

    def fn_index(self, name: str) -> int:
        return self.functions.index(name)

    # -- lookups (Eq. 4, term-partitioned) ----------------------------------

    def lookup_pairs(self, term_ids: jnp.ndarray, doc_ids: jnp.ndarray,
                     *, impl: str = None, alive=None,
                     cols=None) -> jnp.ndarray:
        """(..., Q) term ids x (...,) doc ids -> (..., Q, n_b, n_f).

        Route each term to its owning shard, resolve shard-locally (zeros
        for absent pairs / non-owned terms).  ``impl`` picks the
        expression:

        * ``None`` / ``"fused"`` — the routed single-pass lookup
          (``kernels.csr_lookup.lookup_pairs_ref``): ONE bisect per
          (term, doc) pair against the owning shard, no K-axis anywhere.
          Because ownership is exclusive, the cross-shard merge
          degenerates to exclusive writes — the fast path on one host.
        * ``"jnp"`` — the SPMD expression: every shard bisects the full
          query and emits a partial M_{q,d} with exact zeros for
          non-owned terms; partials merge by summation, which XLA lowers
          to an all-reduce when the leading K axis is mesh-placed
          (``shard_partitioned_index``).  K-fold more work on one
          device — keep it only under a live mesh.

        ``alive`` (n_docs,) bool tombstones deleted docs: their pairs
        resolve to exact zeros, identical to an index rebuilt without
        them (:class:`~repro.dist.live.LiveIndex` passes it).

        ``cols`` (static tuple of function indices) gathers only those
        columns -> (..., Q, n_b, len(cols)), bit for bit the full rows'
        ``[..., cols]``, on every impl.
        """
        if impl not in (None, "fused", "jnp"):
            raise ValueError(f"unknown lookup impl {impl!r}; supported: "
                             "'fused', 'jnp'")
        self._check_lookup_impl(impl)
        if impl != "jnp":
            if self.codec != "none":
                from ..kernels.csr_lookup.ref import lookup_pairs_packed_ref
                return lookup_pairs_packed_ref(
                    self.term_offsets, self._packed(), self.fences,
                    self._serve_values, self.value_scale,
                    self.term_to_shard, self.range_lo, term_ids, doc_ids,
                    self.split_term, self.split_doc, tile=self.codec_tile,
                    spans=self.codec_spans, alive=alive, cols=cols)
            from ..kernels.csr_lookup import lookup_pairs_ref
            return lookup_pairs_ref(
                self.term_offsets, self.doc_ids, self.values,
                self.term_to_shard, self.range_lo, term_ids, doc_ids,
                self.split_term, self.split_doc, alive=alive, cols=cols)
        w = term_ids.clip(0)
        d = jnp.broadcast_to(doc_ids[..., None], term_ids.shape)
        shard_of = self.term_to_shard.at[w].get(mode="clip")
        valid = term_ids >= 0
        # ownership: term-range based when range_hi is known (a doc-range
        # sub-sharded term is "owned" by every sub-shard — each stores a
        # disjoint doc slice, so at most one partial is nonzero per pair
        # and the summation merge stays exact); legacy table equality
        # otherwise (pre-sub-shard checkpoints, where both are the same)
        range_hi = self.range_hi

        def partial(offsets_k, docs_k, lo_k, hi_k, k):
            owned = ((shard_of == k) if range_hi is None
                     else (w >= lo_k) & (w <= hi_k)) & valid
            local = (w - lo_k).clip(0)
            pos, in_list = csr_lookup_positions(offsets_k, docs_k, local, d)
            found = in_list & owned
            if alive is not None:
                found = found & alive.at[d].get(mode="clip")
            return pos, found

        hi = (self.range_lo if range_hi is None else range_hi)
        ks = jnp.arange(self.n_shards, dtype=jnp.int32)
        pos, found = jax.vmap(partial)(
            self.term_offsets, self.doc_ids, self.range_lo, hi, ks)
        vals = gather_rows(self.values,
                           ks.reshape((-1,) + (1,) * (pos.ndim - 1)), pos,
                           cols)
        return (vals * found[..., None, None]).sum(axis=0)

    def qd_matrix(self, query_terms: jnp.ndarray, doc_ids: jnp.ndarray,
                  *, impl: str = None, tile: Optional[int] = None,
                  alive=None, cols=None) -> jnp.ndarray:
        """query_terms (Q,), doc_ids (B,) -> M_{q,d} (B, Q, n_b, n_f).

        The serving hot path.  ``impl=None``/``"fused"`` dispatches to
        ``kernels.csr_lookup`` (fused Pallas kernel on TPU, its routed
        jnp lowering on CPU); ``"jnp"`` keeps the SPMD partial-sum
        composition for mesh-placed serving; ``"interpret"`` forces the
        Pallas interpreter (the oracle-parity sweep).  ``tile`` overrides
        the kernel's posting-tile width (jnp path ignores it).  ``cols``
        narrows M to those function columns, as in :meth:`lookup_pairs`.
        """
        if impl not in (None, "fused", "jnp", "interpret"):
            raise ValueError(f"unknown lookup impl {impl!r}; supported: "
                             "'fused', 'jnp', 'interpret'")
        self._check_lookup_impl(impl)
        if impl == "jnp":
            q = jnp.broadcast_to(query_terms[None],
                                 (doc_ids.shape[0],) + query_terms.shape)
            return self.lookup_pairs(q, doc_ids, impl="jnp", alive=alive,
                                     cols=cols)
        self._check_codec_tile(tile)
        from ..kernels.csr_lookup import csr_lookup
        return csr_lookup(
            self.term_offsets, self.doc_ids, self._serve_values,
            self.term_to_shard, self.range_lo, query_terms, doc_ids,
            fences=self.fences, split_term=self.split_term,
            split_doc=self.split_doc,
            tile=self.codec_tile if self.codec != "none" else tile,
            interpret=True if impl == "interpret" else None,
            codec=self.codec,
            packed=self._packed() if self.codec != "none" else None,
            value_scale=self.value_scale,
            max_tile_words=self.max_tile_words,
            codec_spans=self.codec_spans, alive=alive, cols=cols)

    def retrieve_topk(self, query_terms: jnp.ndarray, k: int,
                      score_block_fn, *, doc_block: Optional[int] = None,
                      impl: str = None, tile: Optional[int] = None,
                      alive=None, n_docs: Optional[int] = None,
                      extra_m_fn=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """First-stage top-k over the whole corpus — no candidate set.

        Same contract as
        :meth:`~repro.core.index.SegmentInvertedIndex.retrieve_topk`,
        over the K-stacked shard layout.  The (query, shard) lane grid
        walks each shard's posting slice for each query term; ownership
        is range-based when ``range_hi`` is known, so a doc-range
        sub-sharded hot term contributes each doc exactly once (the
        sub-shards hold disjoint doc slices) and the cross-shard merge
        stays an exclusive segment scatter — no per-pair ``route_pairs``
        needed on the scan path.

        ``alive``/``n_docs``/``extra_m_fn`` are the live-index hooks:
        tombstone mask, a doc-space total larger than this index's own
        (delta docs live past the base corpus — base lanes just find
        empty windows there), and the per-block delta M to add before
        scoring (exclusive ownership keeps the sum exact; see
        :func:`~repro.kernels.csr_lookup.csr_retrieve_topk`).
        """
        self._check_codec_tile(tile)
        from ..kernels.csr_lookup import csr_retrieve_topk
        return csr_retrieve_topk(
            self.term_offsets, self.doc_ids, self._serve_values,
            self.term_to_shard, self.range_lo, self.range_hi, query_terms,
            n_docs=self.n_docs if n_docs is None else int(n_docs),
            k=k, score_block_fn=score_block_fn,
            doc_block=doc_block,
            tile=self.codec_tile if self.codec != "none" else tile,
            impl=impl, codec=self.codec,
            packed=self._packed() if self.codec != "none" else None,
            value_scale=self.value_scale,
            max_tile_words=self.max_tile_words,
            codec_spans=self.codec_spans, fences=self.fences,
            alive=alive, extra_m_fn=extra_m_fn)

    def _check_codec_tile(self, tile):
        """Satellite guard: a packed layout bakes its tile width into the
        word offsets and fence spacing — an overriding ``tile`` cannot be
        honoured, so reject it up front instead of DMA'ing wrong offsets
        deep in the kernel."""
        if (self.codec != "none" and tile is not None
                and int(tile) != self.codec_tile):
            raise ValueError(
                f"lookup tile {tile} does not match this index's packed "
                f"codec tile {self.codec_tile}; packed indexes serve only "
                "at their build-time tile (rebuild with codec='none' to "
                "sweep tile widths)")


# Bytes per host<->device transfer of values.  A transfer changes the
# layout (the device keeps the posting axis minor, the host row-major) and
# is staged on the device: building a 10.4 GB index in 256 MiB chunks
# peaked 4.6 GB above it on a v5e, about a chunk in (24, 128)-padded rows.
VALUES_CHUNK_BYTES = 1 << 25


def device_values(values: np.ndarray,
                  chunk_bytes: int = VALUES_CHUNK_BYTES):
    """Host ``(K, N, n_b, n_f)`` posting values -> one device array.

    Arrays past ``chunk_bytes`` move ``chunk_bytes`` of postings at a time
    into a donated device buffer, so the transfer's staging stays the
    size of a chunk, never of the index (~10 GB at MQ2007 scale).  The
    last chunk overlaps the one before it, so every transfer has the
    same shape and compiles once.
    """
    n, rows = _chunk_rows(values, chunk_bytes)
    if rows >= n:
        return jnp.asarray(values)
    put = jax.jit(lambda out, part, i: jax.lax.dynamic_update_slice_in_dim(
        out, part, i, axis=1), donate_argnums=0)
    out = jnp.zeros(values.shape, values.dtype)
    for s in range(0, n, rows):
        s = min(s, n - rows)
        out = put(out, values[:, s:s + rows], jnp.int32(s))
    return out


def host_values(values,
                chunk_bytes: int = VALUES_CHUNK_BYTES) -> np.ndarray:
    """The way back: device ``(K, N, n_b, n_f)`` values -> host numpy,
    ``chunk_bytes`` of postings per transfer, so the layout change never
    stages an index-sized copy on the device either."""
    n, rows = _chunk_rows(values, chunk_bytes)
    if rows >= n:
        return np.asarray(values)
    take = jax.jit(lambda v, i: jax.lax.dynamic_slice_in_dim(
        v, i, rows, axis=1))
    out = np.empty(values.shape, values.dtype)
    for s in range(0, n, rows):
        s = min(s, n - rows)
        out[:, s:s + rows] = np.asarray(take(values, jnp.int32(s)))
    return out


def _chunk_rows(values, chunk_bytes: int):
    """``(N, postings per chunk_bytes)`` of a (K, N, ...) values array."""
    n = values.shape[1]
    row_bytes = values.dtype.itemsize * (values.size // max(n, 1))
    return n, max(chunk_bytes // max(row_bytes, 1), 1)


# ---------------------------------------------------------------------------
# codec application (core.codec tile-compressed postings)
# ---------------------------------------------------------------------------

def _codec_arrays(codec: str, tile: int, doc_ids: np.ndarray,
                  values, term_offsets):
    """Pack host-side posting arrays for ``codec`` and emit the codec
    telemetry (per-tile bit-width histogram + bytes-saved gauges).
    Returns the dict of constructor overrides."""
    from ..core import codec as codec_mod

    p = codec_mod.pack_doc_ids(np.asarray(doc_ids, np.int32), tile)
    offs = np.asarray(term_offsets, np.int64)
    lo, hi = offs[:, :-1], offs[:, 1:]
    live = hi > lo
    # loop-bound hint: the widest routed range, in tiles and in postings
    # (extra bisect iterations are no-ops, so ceilings are all it needs)
    span = int(np.where(live, (hi - 1) // tile - lo // tile + 1, 1)
               .max(initial=1))
    max_len = int((hi - lo).max(initial=1))
    out = dict(
        codec=codec, codec_tile=int(tile),
        max_tile_words=int(p.max_tile_words),
        codec_spans=(span, max_len),
        doc_ids=None,
        packed_words=jnp.asarray(p.packed_words),
        tile_bits=jnp.asarray(p.tile_bits),
        tile_base=jnp.asarray(p.tile_base),
        tile_word_off=jnp.asarray(p.tile_word_off))
    raw_bytes = int(np.prod(doc_ids.shape)) * 4
    packed_bytes = p.nbytes
    if codec == "packed-q8":
        q, scale = codec_mod.quantize_values(np.asarray(values, np.float32),
                                             np.asarray(term_offsets))
        out.update(values=None, values_q=device_values(q),
                   value_scale=jnp.asarray(scale))
        raw_bytes += int(np.prod(values.shape)) * 4
        packed_bytes += q.nbytes + scale.nbytes
    bits_hist = obs.gauge("seine_codec_tile_bits_total",
                          "posting tiles per packed bit width")
    bits_hist.clear()
    widths, counts = np.unique(p.tile_bits, return_counts=True)
    for w, c in zip(widths, counts):
        bits_hist.set(int(c), bits=str(int(w)))
    obs.gauge("seine_codec_bytes_saved",
              "posting bytes removed by the codec").set(
        max(raw_bytes - packed_bytes, 0))
    obs.gauge("seine_codec_shrink",
              "raw / packed posting payload bytes").set(
        raw_bytes / max(packed_bytes, 1))
    return out


def pack_index(pidx: PartitionedIndex, codec: str,
               tile: Optional[int] = None) -> PartitionedIndex:
    """Re-encode an uncompressed PartitionedIndex under ``codec``.

    The tile defaults to the build-time ``POSTING_TILE`` (the spacing of
    the stored fence rows); a different ``tile`` also rebuilds the
    fences so anchors and packed tiles stay aligned.  Ids round-trip
    bitwise; q8 values quantise per (shard, local term).
    """
    from ..core.codec import validate_codec
    from ..core.index import POSTING_TILE, build_fences

    codec = validate_codec(codec)
    if pidx.codec != "none":
        raise ValueError(f"index is already packed ({pidx.codec!r}); "
                         "unpack_index first to re-encode")
    if codec == "none":
        return pidx
    t = int(tile or POSTING_TILE)
    doc_ids = np.asarray(pidx.doc_ids)
    values = host_values(pidx.values)
    over = _codec_arrays(codec, t, doc_ids, values,
                         np.asarray(pidx.term_offsets))
    over["fences"] = jnp.asarray(build_fences(doc_ids, t))
    return dataclasses.replace(pidx, **over)


def unpack_index(pidx: PartitionedIndex) -> PartitionedIndex:
    """Materialise the raw layout back from a packed index: ids decode
    bitwise; q8 values dequantise (approximate by design — the scales
    are kept, the pre-quantisation floats are gone)."""
    from ..core import codec as codec_mod

    if pidx.codec == "none":
        return pidx
    p = codec_mod.PackedIds(
        np.asarray(pidx.packed_words), np.asarray(pidx.tile_bits),
        np.asarray(pidx.tile_base), np.asarray(pidx.tile_word_off),
        pidx.max_tile_words, pidx.codec_tile, pidx.nmax)
    doc_ids = codec_mod.unpack_doc_ids(p)
    values = pidx.values
    if pidx.codec == "packed-q8":
        offs = np.asarray(pidx.term_offsets, np.int64)
        nmax = pidx.nmax
        scale = np.asarray(pidx.value_scale)
        pos_scale = np.ones((pidx.n_shards, nmax), np.float32)
        for i in range(pidx.n_shards):
            counts = np.diff(np.clip(offs[i], 0, nmax))
            term_of = np.repeat(np.arange(offs.shape[1] - 1), counts)
            pos_scale[i, :term_of.shape[0]] = scale[i][term_of]
        values = jnp.asarray(np.asarray(pidx.values_q, np.float32)
                             * pos_scale[..., None, None])
    return dataclasses.replace(
        pidx, codec="none", codec_tile=0, max_tile_words=0,
        codec_spans=(0, 0),
        doc_ids=jnp.asarray(doc_ids), values=values, packed_words=None,
        tile_bits=None, tile_base=None, tile_word_off=None,
        values_q=None, value_scale=None)


# ---------------------------------------------------------------------------
# shard-native assembly from term-sorted posting runs (the streaming build)
# ---------------------------------------------------------------------------

def merged_term_counts(runs: Sequence, vocab_size: int) -> np.ndarray:
    """Global postings per term, (|v|,) int64, accumulated run-by-run.

    This is the only full-vocabulary structure the shard-native build ever
    materialises on a host — O(|v|), the same order as the replicated
    ``term_to_shard`` routing table, never O(nnz).
    """
    counts = np.zeros(vocab_size, np.int64)
    for run in runs:
        counts += run.term_counts(vocab_size)
    return counts


def partitioned_from_runs(runs: Sequence, k: int, *, idf: np.ndarray,
                          doc_len: np.ndarray, seg_len: np.ndarray,
                          n_docs: int, vocab_size: int, n_b: int,
                          functions: Tuple[str, ...],
                          mesh=None, split_hot: bool = True,
                          codec: str = "none",
                          codec_tile: Optional[int] = None
                          ) -> "PartitionedIndex":
    """Assemble a K-shard PartitionedIndex directly from term-sorted runs.

    The stage-4 merger of the streaming build (core.build_pipeline): per-
    term counts accumulate run-by-run into the global CSR *boundary* array
    (O(|v|) — the skeleton's doc_ids/values, the O(nnz) bulk, are never
    concatenated globally), ``plan_posting_ranges`` cuts it into K nnz-
    balanced ranges — sub-sharding hot Zipfian terms by doc range when a
    single list exceeds the even split (``split_hot=False`` restores the
    old term-aligned-only plan and its skew warning) — and each shard's
    local CSR is merged independently from the runs via
    :func:`~repro.core.index.shard_csr_from_runs` — the per-pod unit of
    work at production scale.  Padding/stacking semantics are identical to
    the legacy ``partition_index`` (offsets pinned at the shard's nnz,
    doc_ids padded with ``n_docs``, zero values), and ``partition_index``
    itself is now a compatibility wrapper over this merger, so both paths
    produce bitwise-identical shards.
    """
    from ..core.codec import validate_codec
    from ..core.index import POSTING_TILE, build_fences
    from .sharding import (plan_posting_ranges, plan_term_ranges,
                           shard_partitioned_index)

    codec = validate_codec(codec)
    if codec != "none" and mesh is not None:
        raise ValueError(
            "codec != 'none' cannot be combined with a mesh: packed "
            "posting buffers have no partial-sum mesh lowering (pack "
            "after gathering, or serve the mesh index uncompressed)")
    counts = merged_term_counts(runs, vocab_size)
    # guard (shared by every build path, incl. shard-native): K beyond the
    # populated term ranges would mint zero-nnz shards whose padding still
    # K-multiplies the stacked arrays — clamp with a warning instead
    n_pop = int(np.count_nonzero(counts))
    if k > max(n_pop, 1):
        warnings.warn(
            f"partitioned_from_runs: k={k} exceeds the {n_pop} populated "
            f"term range(s); clamping to {max(n_pop, 1)} to avoid "
            f"zero-nnz shards", stacklevel=2)
        k = max(n_pop, 1)
    offs = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    ranks = np.zeros(k + 1, np.int64)
    if split_hot:
        bounds, ranks = plan_posting_ranges(offs, k)
    else:
        bounds = plan_term_ranges(offs, k)
    if not ranks.any():
        # pure term-aligned plan: repair degenerate quantile cuts.  With
        # k <= populated terms, every range can (and must) own at least
        # one populated term — a skewed distribution (one hot list
        # swallowing several quantile targets) otherwise yields zero-nnz
        # shards whose padding still K-multiplies the stacked arrays.
        # Left clamp gives range i-1 its first populated term; right
        # clamp leaves k-i populated terms for the ranges after the cut.
        # Both clamps are no-ops for plans that are already valid, so
        # balanced quantile cuts pass through untouched.  (Sub-shard
        # plans fix degeneracy on posting positions inside
        # plan_posting_ranges instead.)
        pop = np.flatnonzero(counts)
        if k > 1 and pop.size >= k:
            for i in range(1, k):
                nxt = int(np.searchsorted(pop, bounds[i - 1]))
                lo_min = int(pop[nxt]) + 1
                hi_max = int(pop[pop.size - (k - i)])
                bounds[i] = min(max(int(bounds[i]), lo_min), hi_max)

    # shard i's term range is [t_first[i], t_last[i]] INCLUSIVE: cut i
    # with ranks[i] > 0 puts term bounds[i] in both shard i-1 and shard i
    t_first = bounds[:-1].copy()
    t_last = np.empty(k, np.int64)
    for i in range(k):
        t_last[i] = bounds[i + 1] - 1 if ranks[i + 1] == 0 \
            else bounds[i + 1]
    t_last = np.maximum(t_last, t_first)          # empty-range guard
    spans = t_last - t_first + 1
    pos_bounds = offs[bounds] + ranks             # global posting cuts
    local_nnz = np.diff(pos_bounds)
    vmax = max(int(spans.max()), 1)
    nmax = max(int(local_nnz.max()), 1)
    ideal = -(-int(offs[-1]) // k)          # ceil(nnz / k)
    # shard-balance telemetry: the quantities the padded-storage and
    # per-device-byte claims ride on (scripts/bench_gate.py prints these
    # next to any serve regression so skew context comes with the alert)
    shard_nnz = obs.gauge("seine_shard_nnz", "postings per shard")
    shard_nnz.clear()               # drop stale shards from a previous plan
    for i in range(k):
        shard_nnz.set(int(local_nnz[i]), shard=str(i))
    obs.gauge("seine_shard_count", "shards in the last partition plan"
              ).set(k)
    obs.gauge("seine_shard_skew_max_ratio",
              "widest shard vs even split").set(nmax / max(ideal, 1))
    obs.gauge("seine_shard_skew_mean_ratio",
              "mean shard vs even split").set(
        float(local_nnz.mean()) / max(ideal, 1))
    obs.gauge("seine_shard_hot_splits",
              "doc-range sub-shard cuts in the plan").set(
        int((ranks[1:k] > 0).sum()) if k > 1 else 0)
    if k > 1 and nmax > 2 * ideal:
        warnings.warn(
            f"partitioned_from_runs: skewed posting lists — widest shard "
            f"holds {nmax} postings vs an even split of {ideal}; padded "
            f"storage is ~{k * nmax / max(int(offs[-1]), 1):.1f}x nnz and "
            f"per-device bytes will not shrink ~1/K (hot term dominates; "
            f"doc-range sub-sharding is disabled or was defeated)",
            stacklevel=2)

    # split tables: the doc id where each mid-list cut lands.  A cut
    # ``ranks[i]`` postings into term w needs w's globally doc-sorted
    # posting list, merged across runs — an ids-only prepass (the values
    # payload stays on disk for spilled runs; only the few hot terms'
    # doc ids are ever concatenated).
    split_term = np.full(k, -1, np.int32)
    split_doc = np.zeros(k, np.int32)
    hot = sorted({int(bounds[i]) for i in range(1, k) if ranks[i] > 0})
    if hot:
        hot_docs = {w: [] for w in hot}
        for run in runs:
            t, d = run.ids()
            for w in hot:
                sl = int(np.searchsorted(t, w, side="left"))
                sr = int(np.searchsorted(t, w, side="right"))
                if sr > sl:
                    hot_docs[w].append(np.asarray(d[sl:sr]).copy())
        merged = {w: np.sort(np.concatenate(ps))
                  for w, ps in hot_docs.items()}
        for i in range(1, k):
            if ranks[i] > 0:
                w = int(bounds[i])
                split_term[i] = w
                split_doc[i] = int(merged[w][int(ranks[i])])

    n_f = len(functions)
    # ONE pass over the runs: slice every shard's range per loaded run (a
    # spilled run's values payload is read once, not once per shard).
    # Spilled runs get copied slices so each loaded payload is released
    # before the next load — resident overhead stays one run above the
    # output arrays; resident runs keep views (copying would only double
    # memory, the source arrays live on regardless — the partition_index
    # compat path).  A mid-list cut lands inside its term's run slice at
    # the doc boundary: rows of term w with doc < split_doc go left.
    parts: list = [[] for _ in range(k)]
    for run in runs:
        spilled = getattr(run, "term_ids", None) is None
        t, d, v = run.load()
        cuts = np.empty(k + 1, np.int64)
        cuts[0], cuts[k] = 0, t.shape[0]
        for i in range(1, k):
            c = int(np.searchsorted(t, bounds[i], side="left"))
            if ranks[i] > 0:
                sr = int(np.searchsorted(t, bounds[i], side="right"))
                c += int(np.searchsorted(d[c:sr], split_doc[i],
                                         side="left"))
            cuts[i] = c
        cuts = np.maximum.accumulate(cuts)
        for i in range(k):
            lo, hi = int(cuts[i]), int(cuts[i + 1])
            if hi > lo:
                sl = (t[lo:hi], d[lo:hi], v[lo:hi])
                parts[i].append(tuple(a.copy() for a in sl)
                                if spilled else sl)
    term_offsets = np.empty((k, vmax + 1), np.int32)
    doc_ids = np.full((k, nmax), int(n_docs), np.int32)
    values = np.zeros((k, nmax, n_b, n_f), np.float32)
    for i in range(k):
        t_lo, t_hi = int(t_first[i]), int(t_last[i]) + 1
        span = t_hi - t_lo
        loc_offs, loc_docs, _ = merge_run_parts(
            parts[i], t_lo, t_hi, n_b=n_b, n_f=n_f, out=values[i])
        parts[i] = None                 # free as each shard lands
        n = int(loc_docs.shape[0])
        term_offsets[i, :span + 1] = loc_offs[:span + 1]
        term_offsets[i, span + 1:] = n
        doc_ids[i, :n] = loc_docs
    # routing: term -> FIRST owning shard.  Sub-shard continuation terms
    # belong (in the table) to the earlier shard; later sub-shards are
    # reached by counting split boundaries <= the candidate doc
    # (kernels.csr_lookup.route_pairs).
    table_bnd = np.empty(k + 1, np.int64)
    table_bnd[0], table_bnd[k] = 0, vocab_size
    for i in range(1, k):
        table_bnd[i] = bounds[i] + (1 if ranks[i] > 0 else 0)
    table_bnd = np.maximum.accumulate(table_bnd)
    term_to_shard = np.repeat(np.arange(k, dtype=np.int32),
                              np.diff(table_bnd))
    any_split = bool((split_term >= 0).any())

    t = int(codec_tile or POSTING_TILE)
    over = dict(doc_ids=jnp.asarray(doc_ids), values=device_values(values),
                fences=jnp.asarray(build_fences(doc_ids)))
    if codec != "none":
        # pack BEFORE handing arrays to jax; the raw ids exist only
        # transiently here.  Fences must anchor at the codec tile so the
        # two-level bisect and the packed tiles stay aligned.
        over.update(_codec_arrays(codec, t, doc_ids, values, term_offsets))
        over["fences"] = jnp.asarray(build_fences(doc_ids, t))
    pidx = PartitionedIndex(
        term_to_shard=jnp.asarray(term_to_shard),
        range_lo=jnp.asarray(t_first.astype(np.int32)),
        idf=jnp.asarray(np.asarray(idf).astype(np.float32)),
        doc_len=jnp.asarray(np.asarray(doc_len).astype(np.float32)),
        seg_len=jnp.asarray(np.asarray(seg_len).astype(np.float32)),
        n_docs=int(n_docs), vocab_size=int(vocab_size), n_b=int(n_b),
        n_shards=int(k), functions=tuple(functions),
        term_offsets=jnp.asarray(term_offsets),
        range_hi=jnp.asarray(t_last.astype(np.int32)),
        split_term=jnp.asarray(split_term) if any_split else None,
        split_doc=jnp.asarray(split_doc) if any_split else None,
        **over)
    if mesh is not None:
        pidx = shard_partitioned_index(pidx, mesh)
    return pidx
