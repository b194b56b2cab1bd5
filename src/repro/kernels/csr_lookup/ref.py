"""Routed CSR lookup–merge, pure jnp — oracle AND the op's CPU lowering.

The math the fused kernel implements, expressed as one vectorized pass
over the *stacked* shard CSR (K, ...) with NO K-axis loop:

  route    k  = term_to_shard[w]          each query term to its owner
  gather   lo = term_offsets[k, w - range_lo[k]]   (the CSR offset gather)
           hi = term_offsets[k, ... + 1]
  bisect   pos over doc_ids[k, lo:hi)     the same 32-step branchless
                                          bisect as the single-CSR path
                                          (``core.index._bisect`` — the
                                          bitwise oracle of record)
  select   values[k, pos] * found         zeros for absent / OOV pairs

Because every (term, doc) pair is resolved against exactly its owning
shard, the cross-shard "merge" degenerates to exclusive single writes —
no K partial M_{q,d} matrices exist to sum, which is where the old
``vmap``-over-shards path paid K full-width bisects plus K dense partials
(BENCH_partitioned.json, PR 3: 2-3x slower than replicated at K=4).

Implementation trick: the shard axis is folded into the position space —
``doc_ids (K, N)`` viewed as ``(K*N,)`` with per-term base ``k*N`` — so
:func:`~repro.core.index._bisect` runs unchanged and the result is
bitwise-identical to ``csr_lookup_positions`` on the single CSR (each
shard's slice holds exactly the rows the global CSR holds for its
terms).  Envelope: the flattened view needs ``K * Nmax < 2^31`` (int32
positions) — the same per-host wall the single-CSR skeleton has; the
Pallas kernel (the TPU path) indexes shards natively and does not
inherit it.

Doc-range sub-sharding (hot Zipfian terms split across shards by doc-id
range — ``dist.sharding.plan_posting_ranges``) generalises the routing:
ownership is exclusive per (term, doc-range) instead of per term, so the
owner becomes a function of the PAIR.  :func:`route_pairs` resolves it
from two tiny (K,) replicated tables — ``split_term`` (the term that
continues into shard k from k-1) and ``split_doc`` (the first doc id
shard k owns of it): ``owner = first_owner + #{k : split_term[k] == w
and split_doc[k] <= d}``.  Everything downstream (the flat-space bisect,
the found mask) is unchanged, and absent-pair zeros keep the exclusive-
write merge exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bisect_steps(n: int) -> int:
    """Iterations for the branchless bisect to converge over a posting
    span of width <= n: each step at least halves ``hi - lo``, so
    ``floor(log2 n) + 1`` (= ``n.bit_length()``) steps reach width 0.
    The single-CSR path fixes 32 (any int32 nnz); a shard's span is
    statically bounded by its padded width ``Nmax``, which cuts the
    serving bisect to ~15 steps at bench scale — bitwise-identical,
    since the bisect is stationary once converged."""
    return max(int(n).bit_length(), 1)


GATHER_CHUNK = 512   # full rows' worth of elements per gather (gather_rows)


def gather_rows(values: jnp.ndarray, k: jnp.ndarray, pos: jnp.ndarray,
                cols=None) -> jnp.ndarray:
    """``values[k, pos]`` for ``values (K, N, n_b, n_f)`` and broadcastable
    int32 ``k``/``pos`` (clipped into range) -> ``(..., n_b, n_f)``.

    ``cols``, a static tuple of function indices, gathers only those
    columns: ``(..., n_b, len(cols))``, equal bit for bit to
    ``gather_rows(values, k, pos)[..., cols]``; ``None`` gathers all.

    Spelled as a gather of single elements from the ``(K, n_b, n_f, N)``
    view, not of whole ``(n_b, n_f)`` rows.  A TPU stores ``values`` in a
    compact layout with the posting axis minor; XLA gathers elements from
    that layout in place, while a row gather first relayouts the whole
    operand — a per-call copy of an index that fills most of HBM.  The
    element gather pads each element's index vector to a 128-lane row
    (~92 KB of indices per full gathered row), so a chunk holds at most
    ``GATHER_CHUNK * n_b * n_f`` elements: ``GATHER_CHUNK`` full rows, or
    ``n_f / len(cols)`` times as many narrowed ones.  The moved axis is a
    layout change only: on every backend the result is the row gather's,
    bit for bit.
    """
    k, pos = jnp.broadcast_arrays(k, pos)
    shape = pos.shape
    n_b, n_f = values.shape[2], values.shape[3]
    fs = jnp.arange(n_f) if cols is None else jnp.asarray(cols, jnp.int32)
    n_c = fs.shape[0]
    chunk = GATHER_CHUNK * n_f // n_c

    def rows(kc, pc):
        # the view is taken inside the chunk loop: a view hoisted out of
        # it becomes a loop operand of its own, which XLA lays out anew
        # — a full copy of values
        return jnp.moveaxis(values, 1, 3).at[
            kc[:, None, None], jnp.arange(n_b)[:, None],
            fs[None, :], pc[:, None, None]].get(mode="clip")

    kf, pf = k.reshape(-1), pos.reshape(-1)
    n = kf.shape[0]
    if n <= chunk:
        return rows(kf, pf).reshape(shape + (n_b, n_c))
    c = -(-n // chunk)
    kf = jnp.pad(kf, (0, c * chunk - n)).reshape(c, chunk)
    pf = jnp.pad(pf, (0, c * chunk - n)).reshape(c, chunk)
    out = jax.lax.map(lambda a: rows(*a), (kf, pf))
    return out.reshape((-1, n_b, n_c))[:n].reshape(shape + (n_b, n_c))


def _alive_at(alive, d):
    """Tombstone gather: ``alive`` (n_docs,) bool -> mask shaped like
    ``d``.  Out-of-range ids clip to the array edge; every caller ANDs
    the result under a found/in-window mask that is already False for
    ids not actually present, so the clipped garbage never surfaces.
    Folding the mask into the found check keeps deleted docs on the
    exact-zero path absent pairs already take (x * 0 = +0.0), so a
    tombstoned index is bitwise-equal to one rebuilt without the doc."""
    return alive.at[d].get(mode="clip")


def route_terms(term_ids: jnp.ndarray, term_offsets: jnp.ndarray,
                term_to_shard, range_lo):
    """Route global term ids to owning shards and posting ranges.

    term_ids (...,) int32 (raw query ids: negatives = padding, past-vocab
    legal), term_offsets (K, Vmax+1) — returns ``(k, lo, hi)`` all shaped
    like ``term_ids``, with ``lo == hi`` (empty range, never "found") for
    every invalid term.  ``term_to_shard=None`` is the single-CSR case
    (K == 1): everything routes to shard 0 at its own row.
    """
    vmax = term_offsets.shape[1] - 1
    w = term_ids.clip(0)
    if term_to_shard is None:
        k = jnp.zeros(w.shape, jnp.int32)
        row = w
    else:
        k = term_to_shard.at[w].get(mode="clip").astype(jnp.int32)
        row = w - range_lo.at[k].get(mode="clip")
    # past-vocab rows clip into the pinned-at-nnz tail -> lo == hi; the
    # widest shard has no tail, but there row == vmax only when the term
    # is past the vocab, and offsets[k, vmax] == nnz_k -> still empty
    row = row.clip(0, vmax)
    lo = term_offsets.at[k, row].get(mode="clip")
    hi = term_offsets.at[k, (row + 1).clip(0, vmax)].get(mode="clip")
    hi = jnp.where(term_ids >= 0, hi, lo)      # negatives: empty range
    return k, lo, hi


def route_pairs(term_ids: jnp.ndarray, doc_targets: jnp.ndarray,
                term_offsets: jnp.ndarray, term_to_shard, range_lo,
                split_term: jnp.ndarray, split_doc: jnp.ndarray):
    """Per-PAIR routing for doc-range sub-sharded indexes.

    term_ids and doc_targets must be broadcast to a common shape by the
    caller (one entry per (term, doc) pair); returns ``(k, lo, hi)`` in
    that shape.  ``term_to_shard`` maps a term to its FIRST owning shard;
    the (K,) ``split_term``/``split_doc`` tables advance ownership one
    shard per split boundary at or below ``d`` — sub-shards of a term are
    consecutive and their doc ranges are disjoint and ascending, so the
    count IS the owner offset.  Terms with no splits take offset 0 and
    reduce to :func:`route_terms` exactly.
    """
    vmax = term_offsets.shape[1] - 1
    w = term_ids.clip(0)
    k0 = term_to_shard.at[w].get(mode="clip").astype(jnp.int32)
    hop = ((split_term == w[..., None])
           & (split_doc <= doc_targets[..., None])).sum(-1).astype(jnp.int32)
    k = k0 + hop
    row = (w - range_lo.at[k].get(mode="clip")).clip(0, vmax)
    lo = term_offsets.at[k, row].get(mode="clip")
    hi = term_offsets.at[k, (row + 1).clip(0, vmax)].get(mode="clip")
    hi = jnp.where(term_ids >= 0, hi, lo)      # negatives: empty range
    return k, lo, hi


def route_hops(term_ids: jnp.ndarray, term_offsets: jnp.ndarray,
               term_to_shard, range_lo, n_hops: int):
    """Every route :func:`route_pairs` can pick for each term.

    term_ids (Q,) -> ``(k, lo, hi)``, each (Q, n_hops): column h is the
    route of a pair whose doc lies past h of the term's sub-shard splits
    — the same expressions as :func:`route_pairs` with ``hop = h`` (and
    :func:`route_terms` at h = 0), so a kernel that counts the hop per
    pair from the (K,) split tables lands on the identical range.  ``k``
    is clipped to the shard count, as every gather here already clips.
    """
    vmax = term_offsets.shape[1] - 1
    w = term_ids.clip(0)[:, None]
    hop = jnp.arange(n_hops, dtype=jnp.int32)[None, :]
    if term_to_shard is None:
        k = jnp.zeros(w.shape, jnp.int32) + hop
        row = w + 0 * hop
    else:
        k = term_to_shard.at[w].get(mode="clip").astype(jnp.int32) + hop
        row = w - range_lo.at[k].get(mode="clip")
    row = row.clip(0, vmax)
    lo = term_offsets.at[k, row].get(mode="clip")
    hi = term_offsets.at[k, (row + 1).clip(0, vmax)].get(mode="clip")
    hi = jnp.where(term_ids[:, None] >= 0, hi, lo)   # negatives: empty
    return (jnp.minimum(k, term_offsets.shape[0] - 1), lo.astype(jnp.int32),
            hi.astype(jnp.int32))


def _route(term_ids, doc_targets, term_offsets, term_to_shard, range_lo,
           split_term, split_doc):
    """Dispatch: per-term routing + broadcast when no sub-shards exist,
    per-pair routing when they do.  Shapes out are always pair-shaped."""
    if split_term is None:
        k, lo, hi = route_terms(term_ids, term_offsets, term_to_shard,
                                range_lo)
        shape = jnp.broadcast_shapes(term_ids.shape, doc_targets.shape)
        return (jnp.broadcast_to(k, shape), jnp.broadcast_to(lo, shape),
                jnp.broadcast_to(hi, shape))
    shape = jnp.broadcast_shapes(term_ids.shape, doc_targets.shape)
    return route_pairs(jnp.broadcast_to(term_ids, shape),
                       jnp.broadcast_to(doc_targets, shape),
                       term_offsets, term_to_shard, range_lo,
                       split_term, split_doc)


def lookup_pairs_ref(term_offsets: jnp.ndarray, doc_ids: jnp.ndarray,
                     values: jnp.ndarray, term_to_shard, range_lo,
                     term_ids: jnp.ndarray, doc_targets: jnp.ndarray,
                     split_term=None, split_doc=None,
                     alive=None, cols=None) -> jnp.ndarray:
    """Generic-batch routed lookup: term_ids (..., Q) x doc_targets
    broadcastable (...,) -> (..., Q, n_b, n_f), zeros for absent pairs.
    ``alive`` (n_docs,) bool, when given, tombstones docs: pairs whose
    doc is dead resolve to the same exact zeros as absent pairs.
    ``cols`` gathers only those function columns (:func:`gather_rows`)."""
    from ...core.index import _bisect

    K, N = doc_ids.shape
    d = jnp.broadcast_to(doc_targets[..., None], term_ids.shape)
    k, lo, hi = _route(term_ids, d, term_offsets, term_to_shard, range_lo,
                       split_term, split_doc)
    base = k * N
    flat = doc_ids.reshape(K * N)
    pos = _bisect(flat, base + lo, base + hi, d, n_iter=bisect_steps(N))
    in_list = (pos < base + hi) & (flat.at[pos].get(mode="clip") == d)
    if alive is not None:
        in_list = in_list & _alive_at(alive, d)
    vals = gather_rows(values, k, pos - base, cols)
    # select, not multiply-by-mask: XLA fuses the select into the gather
    # consumer, a bool-mask product materialises a second full-size pass
    # (~15% of the lookup on CPU); absent pairs are +0.0 either way
    return jnp.where(in_list[..., None, None], vals, 0.0)


def retrieve_lanes(query_terms: jnp.ndarray, term_offsets: jnp.ndarray,
                   term_to_shard, range_lo, range_hi, n_max: int):
    """Per-(query-slot, shard) posting ranges in the FLAT position space.

    First-stage retrieval inverts the serving lookup: instead of
    resolving one (term, doc) pair it must walk EVERY posting of every
    query term.  A term's postings live in its owning shard — or, for a
    doc-range sub-sharded hot term, in a consecutive run of shards each
    holding a disjoint doc slice (the same exclusive ownership
    :func:`route_pairs` resolves per pair) — so the (Q, K) lane grid
    covers the union exactly once: lane (q, k) is the possibly-empty
    slice of shard k's postings for query term q.

    Ownership mirrors the jnp partial-sum path: term-range based when
    ``range_hi`` is known (sub-sharded boundary terms are owned by every
    neighbour holding a doc slice), table equality for legacy
    checkpoints, and unconditional for the single-CSR case
    (``term_to_shard is None``, K == 1).

    Returns ``(lo, hi)``, each (Q, K) int32 positions into
    ``doc_ids.reshape(K * n_max)``; ``lo == hi`` for lanes owning
    nothing (invalid / OOV / past-vocab terms, non-owning shards).
    """
    k_count, vmax1 = term_offsets.shape
    vmax = vmax1 - 1
    w = query_terms.clip(0)[:, None]                      # (Q, 1)
    ks = jnp.arange(k_count, dtype=jnp.int32)[None, :]    # (1, K)
    valid = (query_terms >= 0)[:, None]
    if term_to_shard is None:
        owned = valid
        lo_k = jnp.zeros((1, k_count), jnp.int32)
    else:
        lo_k = range_lo[None, :]
        if range_hi is None:
            owned = (term_to_shard.at[query_terms.clip(0)]
                     .get(mode="clip")[:, None] == ks) & valid
        else:
            owned = (w >= lo_k) & (w <= range_hi[None, :]) & valid
    row = (w - lo_k).clip(0, vmax)
    lo = term_offsets[ks, row]
    hi = term_offsets[ks, (row + 1).clip(0, vmax)]
    hi = jnp.where(owned, hi, lo)
    lo = jnp.where(owned, lo, hi)
    base = ks * n_max
    return base + lo, base + hi


def merge_windows(doc_win: jnp.ndarray, val_win: jnp.ndarray,
                  n_valid: jnp.ndarray, blo, block: int,
                  lead=None, alive=None) -> jnp.ndarray:
    """Scatter gathered posting windows into one dense doc-block of M.

    ``doc_win`` (Q, K, W) doc ids / ``val_win`` (Q, K, W, n_b, n_f)
    values, of which the first ``n_valid`` (Q, K) entries per lane are
    real postings with doc ids in ``[blo, blo + block)``.  Because every
    (term, doc) pair is stored in exactly one shard, the lanes of a
    query slot are disjoint in doc space and the segment-sum writes each
    (doc, term) output cell at most once — zeros elsewhere, the sigma=0
    semantics — so the result equals the per-pair lookup bit-for-bit
    (modulo ±0, which the exact-zero merge semantics treat as equal).

    ``lead`` (Q, K), when given, shifts each lane's live span to
    ``[lead, lead + n_valid)``: the packed retrieve path DMAs windows
    aligned DOWN to the posting-tile boundary (the tile is the codec's
    atomic decode unit), so the first ``lead`` entries belong to doc ids
    below the block and must fall in the overflow bin with the tail.

    ``alive`` (n_docs,) bool, when given, routes tombstoned docs'
    postings to the overflow bin too — every retrieve path (jnp ref and
    both Pallas window paths) funnels through this merge, so folding
    the mask here deletes docs from first-stage scoring everywhere at
    once, with the same exact-zero result a rebuild without the doc
    would produce.

    Returns M (block, Q, n_b, n_f).
    """
    q_n, k_n, w_n = doc_win.shape
    idx = jnp.arange(w_n)[None, None, :]
    if lead is None:
        in_win = idx < n_valid[..., None]
    else:
        in_win = (idx >= lead[..., None]) & (idx < (lead + n_valid)[..., None])
    if alive is not None:
        in_win = in_win & _alive_at(alive, doc_win)
    seg = jnp.where(in_win, doc_win - blo, block)         # overflow bin
    seg = seg.reshape(q_n, k_n * w_n)
    vals = val_win.reshape((q_n, k_n * w_n) + val_win.shape[3:])
    m = jax.vmap(lambda v, s: jax.ops.segment_sum(
        v, s, num_segments=block + 1))(vals, seg)
    return jnp.swapaxes(m[:, :block], 0, 1)               # (block, Q, ...)


def retrieve_block_ref(term_offsets: jnp.ndarray, doc_ids: jnp.ndarray,
                       values: jnp.ndarray, term_to_shard, range_lo,
                       range_hi, query_terms: jnp.ndarray, blo,
                       block: int, alive=None) -> jnp.ndarray:
    """One doc block of the first-stage posting scan, pure jnp.

    Builds M rows for docs ``[blo, blo + block)`` x every query term by
    iterating the query's posting ranges instead of bisecting per
    (term, doc) pair: a term stores at most one posting per doc, so the
    postings of lane (q, k) inside the block are a contiguous slice of
    length <= ``block``, located with two range bisects (the same
    branchless :func:`~repro.core.index._bisect` the lookup runs) and
    gathered as one window.  Work per block is O(Q·K·(log Nmax + block))
    — independent of posting-list length — vs the per-pair lookup's
    O(Q·block·log) bisects; the kernel path DMAs the same windows
    tile-by-tile.  Returns M (block, Q, n_b, n_f).
    """
    from ...core.index import _bisect

    k_n, n = doc_ids.shape
    flat = doc_ids.reshape(k_n * n)
    lo_f, hi_f = retrieve_lanes(query_terms, term_offsets, term_to_shard,
                                range_lo, range_hi, n)
    steps = bisect_steps(n)
    s_lo = _bisect(flat, lo_f, hi_f,
                   jnp.broadcast_to(blo, lo_f.shape), n_iter=steps)
    s_hi = _bisect(flat, lo_f, hi_f,
                   jnp.broadcast_to(blo + block, lo_f.shape), n_iter=steps)
    p = s_lo[..., None] + jnp.arange(block)               # (Q, K, block)
    doc_win = flat.at[p].get(mode="clip")
    ks = jnp.arange(k_n, dtype=jnp.int32)[None, :, None]
    val_win = gather_rows(values, ks, p - ks * n)
    return merge_windows(doc_win, val_win, s_hi - s_lo, blo, block,
                         alive=alive)


def csr_lookup_ref(term_offsets: jnp.ndarray, doc_ids: jnp.ndarray,
                   values: jnp.ndarray, term_to_shard, range_lo,
                   query_terms: jnp.ndarray, doc_targets: jnp.ndarray,
                   split_term=None, split_doc=None,
                   alive=None, cols=None) -> jnp.ndarray:
    """The serving cartesian: query_terms (Q,) x doc_targets (B,) ->
    M_{q,d} (B, Q, n_b, n_f), or ``(B, Q, n_b, len(cols))`` with ``cols``.

    Without sub-shards, routing runs once on the (Q,) terms and
    broadcasts over candidates — cheaper than the single-CSR path's
    per-(B, Q) offset gathers — which is also exactly the dataflow of
    the Pallas kernel (scalar-prefetched per-term routing, doc-tiled
    grid).  With sub-shards the owner depends on the candidate, so
    routing is per (B, Q) pair (still one bisect per pair).
    """
    from ...core.index import _bisect

    K, N = doc_ids.shape
    shape = (doc_targets.shape[0], query_terms.shape[0])    # (B, Q)
    d = jnp.broadcast_to(doc_targets[:, None], shape)
    k, lo, hi = _route(query_terms[None], d, term_offsets, term_to_shard,
                       range_lo, split_term, split_doc)
    lo_f = k * N + lo
    hi_f = k * N + hi
    flat = doc_ids.reshape(K * N)
    pos = _bisect(flat, lo_f, hi_f, d, n_iter=bisect_steps(N))
    in_list = (pos < hi_f) & (flat.at[pos].get(mode="clip") == d)
    if alive is not None:
        in_list = in_list & _alive_at(alive, d)
    vals = gather_rows(values, k, pos - k * N, cols)
    # select over multiply-by-mask: see lookup_pairs_ref
    return jnp.where(in_list[..., None, None], vals, 0.0)


# ---------------------------------------------------------------------------
# packed-codec lowerings (core.codec tile-compressed postings)
# ---------------------------------------------------------------------------

def packed_bisect(packed, fences, k, lo, hi, target, *, tile: int,
                  spans=(0, 0), with_value: bool = False):
    """First shard-local position p in [lo, hi) with decode(k, p) >= target.

    Two-level, mirroring the Pallas kernel: level 1 bisects the
    UNCOMPRESSED fence row (the codec keeps fences raw — they are the
    tile skip pointers), one metadata gather picks up the winning tile's
    (bits, base, word offset), level 2 bisects inside the tile with
    probes that decode a single packed word (shift + mask).  That is
    O(log F + log tile) one-word gathers instead of O(log Nmax) probes
    each paying the full 4-gather random-access decode — the difference
    between the packed CPU lowering tracking the uncompressed one and a
    ~4x regression.  The split is exact (every tile strictly left of the
    winning fence is wholly < target, so the lower bound lives in the
    winning tile or at its right boundary), hence positions are
    bitwise-equal to ``core.index._bisect`` over the unpacked row.

    ``packed`` is ``(packed_words (K, W), tile_bits (K, F), tile_base
    (K, F), tile_word_off (K, F+1))``; k/lo/hi/target broadcastable
    int32 arrays in shard-LOCAL position space.

    ``spans = (max_span, max_len)`` is the pack-time loop-bound hint
    (``PartitionedIndex.codec_spans``): no routed range spans more than
    ``max_span`` tiles or holds more than ``max_len`` postings, so both
    levels can run just enough iterations to converge instead of the
    worst case over the whole fence row / tile — at bench scale that is
    1-2 fence probes instead of ~6.  ``(0, 0)`` = unknown, worst case.
    Extra iterations are no-ops (the bisect is stationary once
    converged), so a loose hint only costs time, never positions.

    ``with_value=True`` additionally returns the decoded doc id at
    ``pos``, reusing the tile metadata already gathered: one packed-word
    probe in-tile, and for ``pos`` on the tile's right boundary (the
    next tile's first element) the UNCOMPRESSED next fence — which is
    that element verbatim.  Callers use it for the found check without
    paying :func:`~repro.core.codec.unpack_at`'s fresh metadata gathers;
    positions past ``hi`` may decode garbage there, but every caller
    masks on ``pos < hi`` before the value matters.
    """
    # every probe gathers through a PRE-FLATTENED 1-D view with a
    # precomputed per-pair row offset — the same access pattern as the
    # uncompressed ref's flat bisect.  2-D advanced-index gathers
    # (``arr.at[k, idx]``) re-lower the two index operands every loop
    # iteration on CPU and cost ~2x per probe.
    words, bits, base_t, woff = packed
    f = fences.shape[1]
    fflat = fences.reshape(-1)
    k = jnp.clip(k, 0, fences.shape[0] - 1)     # one clamp, not per-probe
    kf = k * f
    j_lo = lo // tile
    j_hi = jnp.maximum((hi - 1) // tile, j_lo)
    max_span, max_len = spans
    f_steps = bisect_steps(min(max_span - 1, f) if max_span else f)
    t_steps = bisect_steps(min(max_len, tile) if max_len else tile)

    def fence_body(_, state):
        flo, fhi = state
        mid = (flo + fhi) // 2
        v = fflat[kf + jnp.clip(mid, 0, f - 1)]
        go_right = (v < target) & (flo < fhi)
        return (jnp.where(go_right, mid + 1, flo),
                jnp.where(go_right, fhi, mid))

    jf, _ = jax.lax.fori_loop(0, f_steps, fence_body,
                              (j_lo + 1, j_hi + 1))
    jt = jnp.clip(jf - 1, 0, f - 1)
    base = jt * tile
    kfj = kf + jt
    c = bits.reshape(-1)[kfj]
    tb = base_t.reshape(-1)[kfj]
    wo = woff.reshape(-1)[k * (f + 1) + jt]
    mask = (1 << jnp.minimum(c, 16)) - 1
    # flat word offset of the tile's first word; bp // 32 stays within
    # the row because rows are padded by max_tile_words trailing words
    kwo = k * words.shape[1] + wo
    wflat = words.reshape(-1)
    c32 = c == 32
    w_lo = jnp.maximum(base, lo)
    w_hi = jnp.minimum(base + tile, hi)

    def decode_word(r):
        # r in [0, tile]: r == tile only for converged/boundary probes
        # whose value is never consulted, and its word stays in-row (the
        # max_tile_words trailing pad); no per-probe clip needed
        bp = r * c
        wv = wflat[kwo + bp // 32]
        return jnp.where(c32, wv,
                         tb + (jax.lax.shift_right_logical(
                             wv, jnp.bitwise_and(bp, 31)) & mask))

    def tile_body(_, state):
        plo, phi = state
        mid = (plo + phi) // 2
        go_right = (decode_word(mid - base) < target) & (plo < phi)
        return (jnp.where(go_right, mid + 1, plo),
                jnp.where(go_right, phi, mid))

    pos, _ = jax.lax.fori_loop(0, t_steps, tile_body, (w_lo, w_hi))
    if not with_value:
        return pos
    # decode at pos with the metadata in hand: in-tile is one word probe;
    # on the right boundary the element IS the next tile's fence (raw)
    v_next = fflat[kf + jnp.clip(jt + 1, 0, f - 1)]
    in_tile = pos - base < tile
    v_at = jnp.where(in_tile, decode_word(jnp.where(in_tile, pos - base, 0)),
                     v_next)
    return pos, v_at


def _lane_scale(value_scale, range_lo, k, term_ids):
    """Per-(pair/lane) dequant scale: the owning shard's per-local-term
    scale row.  Only consulted where a pair is actually found / a lane
    actually owns postings, so clipped garbage rows are never applied."""
    vmax = value_scale.shape[1]
    w = term_ids.clip(0)
    if range_lo is None:
        row = w.clip(0, vmax - 1)
    else:
        row = (w - range_lo.at[k].get(mode="clip")).clip(0, vmax - 1)
    return value_scale.at[k, row].get(mode="clip")


def _lookup_packed(term_offsets, packed, fences, values, value_scale,
                   term_to_shard, range_lo, split_term, split_doc,
                   term_ids, d, *, tile: int, spans=(0, 0), alive=None,
                   cols=None):
    """Shared body of the packed lookup refs: route, two-level packed
    bisect, decode-at-found check, values gather of the ``cols`` columns
    (+ optional dequant).  ``term_ids``/``d`` already broadcast to the
    common pair shape."""
    k_n, nmax = values.shape[0], values.shape[1]
    k, lo, hi = _route(term_ids, d, term_offsets, term_to_shard, range_lo,
                       split_term, split_doc)
    # found only ever tests pos < hi <= nnz_k, where the decode is exact;
    # past-the-range positions are masked before the comparison matters
    pos, v_at = packed_bisect(packed, fences, k, lo, hi, d, tile=tile,
                              spans=spans, with_value=True)
    found = (pos < hi) & (v_at == d)
    if alive is not None:
        found = found & _alive_at(alive, d)
    if value_scale is not None:
        # int8 dequant: convert+scale fused into the gather consumer, one
        # full-size select at the end.  The barrier pins the (tiny,
        # pair-shaped) bisect outputs as materialised gather operands —
        # without it XLA threads the bisect producer chain into the
        # gather loop and the dequant pass runs ~1.4x slower on CPU.
        scale = _lane_scale(value_scale, range_lo, k, term_ids)
        kk, ix, sc, fd = jax.lax.optimization_barrier(
            (k, pos, scale, found))
        vals = gather_rows(values, kk, ix, cols).astype(jnp.float32)
        return jnp.where(fd[..., None, None], vals * sc[..., None, None], 0.0)
    kk, ix, fd = jax.lax.optimization_barrier((k, pos, found))
    vals = gather_rows(values, kk, ix, cols)
    # select over multiply-by-mask: see lookup_pairs_ref
    return jnp.where(fd[..., None, None], vals, 0.0)


def lookup_pairs_packed_ref(term_offsets, packed, fences, values,
                            value_scale, term_to_shard, range_lo,
                            term_ids, doc_targets, split_term=None,
                            split_doc=None, *, tile: int, spans=(0, 0),
                            alive=None, cols=None):
    """Packed-codec :func:`lookup_pairs_ref`: term_ids (..., Q) x
    doc_targets broadcastable (...,) -> (..., Q, n_b, n_f).  Ids decode
    losslessly, so found masks/positions — and with f32 ``values`` the
    outputs — are bitwise-equal to the uncompressed ref; int8 ``values``
    (+ ``value_scale``) dequantise on the fly."""
    d = jnp.broadcast_to(doc_targets[..., None], term_ids.shape)
    return _lookup_packed(term_offsets, packed, fences, values,
                          value_scale, term_to_shard, range_lo,
                          split_term, split_doc, term_ids, d, tile=tile,
                          spans=spans, alive=alive, cols=cols)


def csr_lookup_packed_ref(term_offsets, packed, fences, values,
                          value_scale, term_to_shard, range_lo,
                          query_terms, doc_targets, split_term=None,
                          split_doc=None, *, tile: int, spans=(0, 0),
                          alive=None, cols=None):
    """Packed-codec :func:`csr_lookup_ref`: query_terms (Q,) x
    doc_targets (B,) -> M (B, Q, n_b, n_f)."""
    shape = (doc_targets.shape[0], query_terms.shape[0])    # (B, Q)
    d = jnp.broadcast_to(doc_targets[:, None], shape)
    w = jnp.broadcast_to(query_terms[None], shape)
    return _lookup_packed(term_offsets, packed, fences, values,
                          value_scale, term_to_shard, range_lo,
                          split_term, split_doc, w, d, tile=tile,
                          spans=spans, alive=alive, cols=cols)


def retrieve_block_packed_ref(term_offsets, packed, fences, values,
                              value_scale, term_to_shard, range_lo,
                              range_hi, query_terms, blo, block: int,
                              *, tile: int, spans=(0, 0), alive=None):
    """Packed-codec :func:`retrieve_block_ref` — same lane ranges, the
    two range bisects run as packed two-level bisects, and the gathered
    id windows decode through :func:`~repro.core.codec.unpack_at`.
    Window entries past a lane's live span decode whatever the clip
    lands on; merge_windows masks them to the overflow bin exactly as
    the uncompressed path masks its clip-gather garbage."""
    from ...core.codec import unpack_at

    k_n, nmax = values.shape[0], values.shape[1]
    lo_f, hi_f = retrieve_lanes(query_terms, term_offsets, term_to_shard,
                                range_lo, range_hi, nmax)
    ks = jnp.broadcast_to(jnp.arange(k_n, dtype=jnp.int32)[None, :],
                          lo_f.shape)
    base = ks * nmax
    lo_l, hi_l = lo_f - base, hi_f - base
    s_lo = packed_bisect(packed, fences, ks, lo_l, hi_l,
                         jnp.broadcast_to(blo, lo_l.shape), tile=tile,
                         spans=spans)
    s_hi = packed_bisect(packed, fences, ks, lo_l, hi_l,
                         jnp.broadcast_to(blo + block, lo_l.shape),
                         tile=tile, spans=spans)
    p = s_lo[..., None] + jnp.arange(block)               # (Q, K, block)
    doc_win = unpack_at(*packed, ks[..., None], p, tile=tile)
    val_win = gather_rows(values, ks[..., None], p)
    if value_scale is not None:
        scale = _lane_scale(value_scale, range_lo, ks, query_terms[:, None])
        val_win = val_win.astype(jnp.float32) * scale[..., None, None, None]
    return merge_windows(doc_win, val_win, s_hi - s_lo, blo, block,
                         alive=alive)


# ---------------------------------------------------------------------------
# posting-tile cache (serving front end's hot-term cache, serving/tile_cache)
# ---------------------------------------------------------------------------

def cached_tile_lookup(cache_ids, cache_vals, slots, win_lo, win_hi,
                       doc_targets, scale=None):
    """Resolve (term, doc) pairs against cached posting tiles.

    The front end's tile cache (``serving.tile_cache.PostingTileCache``)
    routes pairs on the host — the owning shard, the posting range and
    the single tile that can contain the target are all computable from
    the replicated O(|v|)/O(K) tables plus the fence rows, none of the
    posting payload — so by the time this runs, every pair has been
    reduced to an in-tile bisect over one cached ``T``-wide tile:

    * ``cache_ids`` (C, T) int32 — resident tiles' doc ids (decoded,
      even under a packed codec: the cache stores tiles post-decode so
      hits skip the unpack as well as the DMA);
    * ``cache_vals`` (C, T, n_b, n_f) — the matching value rows, at the
      index's serve dtype (f32, or int8 under packed-q8);
    * ``slots`` / ``win_lo`` / ``win_hi`` (...,) int32 per pair — the
      pair's cache slot and its routed range clipped to that tile
      (shard-local ``[lo, hi)`` minus the tile base).  Pairs with no
      postings (OOV / padding / empty route) pass ``win_lo == win_hi``
      and resolve to the exact-zero rows every lookup path shares;
    * ``scale`` (...,) f32 — per-pair dequant scale (packed-q8 only).

    The bisect is ``core.index._bisect`` over the flattened cache with a
    per-pair base of ``slot * T`` — the identical probe sequence the
    uncompressed ref runs over ``doc_ids.reshape(K * N)`` restricted to
    one tile, so found masks and values are bitwise-equal to the
    uncoalesced oracle (``bisect_steps(T)`` iterations suffice: the
    window is at most ``T`` wide).
    """
    from ...core.index import _bisect

    c, t = cache_ids.shape
    flat = cache_ids.reshape(-1)
    base = slots * t
    lo = base + win_lo
    hi = base + win_hi
    pos = _bisect(flat, lo, hi, doc_targets, n_iter=bisect_steps(t))
    found = (pos < hi) & (flat.at[pos].get(mode="clip") == doc_targets)
    vals = cache_vals.reshape((c * t,) + cache_vals.shape[2:]) \
        .at[pos].get(mode="clip")
    if scale is not None:
        # int8 dequant fused into the gather consumer, mirroring
        # _lookup_packed's q8 tail (same select-over-mask policy)
        return jnp.where(found[..., None, None],
                         vals.astype(jnp.float32) * scale[..., None, None],
                         0.0)
    return jnp.where(found[..., None, None], vals, 0.0)
