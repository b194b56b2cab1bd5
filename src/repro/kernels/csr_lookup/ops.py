"""jit'd public wrappers for the fused csr_lookup serving kernels.

Backend dispatch differs from the sibling kernels on purpose: this op IS
the serving hot path, so on CPU it lowers to :func:`~.ref.csr_lookup_ref`
— the routed-gather jnp expression of the SAME fused dataflow (one
bisect per (term, doc) pair against the owning shard, no K partials),
bitwise-identical to the kernel — instead of the Pallas interpreter,
which emulates the grid cell-by-cell and is a correctness tool, not a
fast path.  ``interpret=True`` forces the interpreter (the oracle-parity
sweeps in the tests); ``interpret=False`` forces the compiled TPU
kernel.

The kernel path splits the lookup in two.  The Pallas kernel resolves
positions with the two-level tiled bisect: ``fences`` (every ``tile``-th
doc id, built at index-build time by ``core.index.build_fences``) are
bisected in SMEM first, then only the winning ``tile``-wide posting
slice is DMA'd.  One XLA gather then reads the value rows at those
positions from the compact ``values`` array, which no kernel takes as an
operand (see ``kernel.py`` for why).  ``doc_ids`` is padded here to a
whole number of tiles per shard and viewed as DMA rows; fences are
rebuilt on the fly whenever the provided array does not match the
requested ``tile`` (e.g. the parity sweep overriding the build-time
default).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import (LANES, as_rows, csr_lookup_pallas, window_rows,
                     window_rows_pallas)
from .ref import (bisect_steps, cached_tile_lookup, csr_lookup_packed_ref,
                  csr_lookup_ref, gather_rows, lookup_pairs_ref,
                  merge_windows, packed_bisect, retrieve_block_packed_ref,
                  retrieve_block_ref, retrieve_lanes, route_hops,
                  route_pairs, route_terms, _alive_at, _lane_scale)


def _check_packed_args(codec, packed, fences, values, tile, t):
    """Shared packed-arg validation: the codec's tile width is baked into
    the packed layout (word offsets, fence spacing), so a mismatched
    ``tile`` cannot be repacked on the fly the way raw fences are rebuilt
    — fail loudly instead of issuing wrong-offset DMAs in the kernel."""
    from ...core.index import fence_count

    if packed is None:
        raise ValueError(f"codec {codec!r} needs the packed posting "
                         "arrays (packed_words, tile_bits, tile_base, "
                         "tile_word_off)")
    if fences is None:
        raise ValueError(f"codec {codec!r} needs the build-time fence "
                         "rows (the codec keeps them uncompressed as "
                         "tile anchors; they cannot be rebuilt from "
                         "packed tiles at lookup time)")
    n_fence = fence_count(values.shape[1], t)
    if packed[1].shape[1] != n_fence or fences.shape[1] != n_fence:
        raise ValueError(
            f"tile={tile} does not match the packed tile layout "
            f"({packed[1].shape[1]} packed tiles / {fences.shape[1]} "
            f"fences vs {n_fence} expected); packed indexes serve only "
            "at their build-time codec tile")
    if codec == "packed-q8" and values.dtype != jnp.int8:
        raise ValueError("codec 'packed-q8' expects int8 values")


def _id_rows(doc_ids, t):
    """``doc_ids (K, N)`` -> ``(flat, rows)``: the ids padded to a whole
    number of tiles per shard (int32 max, so fences stay monotone) and
    flattened — shard k's ids start at ``k * F * t`` — plus the DMA-row
    view of that buffer the kernels read."""
    from ...core.index import fence_count

    n = doc_ids.shape[1]
    f = fence_count(n, t)
    flat = jnp.pad(doc_ids, ((0, 0), (0, f * t - n)),
                   constant_values=np.iinfo(np.int32).max).reshape(-1)
    return flat, as_rows(flat, window_rows(t, t))


def _word_rows(packed, mw):
    """DMA-row view of the flat packed-word buffer (shard k's words
    start at ``k * W``)."""
    return as_rows(packed[0].reshape(-1), window_rows(mw, 1))


def _lookup_found(term_offsets, term_to_shard, range_lo, split_term,
                  split_doc, query_terms, doc_targets, fences, rows,
                  values, value_scale, *, tile, stride, packed_meta=None,
                  max_tile_words=0, interpret=False):
    """Kernel-path lookup: route, resolve positions in the Pallas kernel,
    gather the found rows from ``values`` -> M (B, Q, n_b, n_f).

    The gather and the masking select are the jnp refs' expressions
    (``values[k, pos]`` where found, exact zeros elsewhere; q8 dequant
    by the pair's per-term scale), so M is bitwise-equal to them."""
    hops = 1 if split_term is None else term_offsets.shape[0]
    n = values.shape[1]
    with jax.named_scope("seine.lookup.positions"):
        k, lo, hi = route_hops(query_terms, term_offsets, term_to_shard,
                               range_lo, hops)
        if split_term is None:             # one entry that never matches
            split_term = jnp.full((1,), -1, jnp.int32)
            split_doc = jnp.zeros((1,), jnp.int32)
        pos = csr_lookup_pallas(
            k, lo, hi, query_terms.clip(0).astype(jnp.int32),
            split_term.astype(jnp.int32), split_doc.astype(jnp.int32),
            doc_targets.astype(jnp.int32), fences, rows, tile=tile,
            stride=stride, n_pos=n, packed_meta=packed_meta,
            max_tile_words=max_tile_words, interpret=interpret)
    with jax.named_scope("seine.lookup.gather"):
        flat = pos.T                                      # (B, Q)
        found = (flat >= 0)[..., None, None]
        f = jnp.maximum(flat, 0)
        kk = f // n
        vals = gather_rows(values, kk, f - kk * n)
        if value_scale is not None:
            sc = _lane_scale(value_scale, range_lo, kk,
                             query_terms[None, :])
            return jnp.where(found, vals.astype(jnp.float32)
                             * sc[..., None, None], 0.0)
        return jnp.where(found, vals, 0.0)


@partial(jax.jit,
         static_argnames=("tile", "interpret", "codec", "max_tile_words",
                          "codec_spans"))
def csr_lookup(term_offsets: jnp.ndarray, doc_ids: jnp.ndarray,
               values: jnp.ndarray, term_to_shard, range_lo,
               query_terms: jnp.ndarray, doc_targets: jnp.ndarray,
               *, fences: jnp.ndarray | None = None,
               split_term: jnp.ndarray | None = None,
               split_doc: jnp.ndarray | None = None,
               tile: int | None = None,
               interpret: bool | None = None,
               codec: str = "none",
               packed=None,
               value_scale: jnp.ndarray | None = None,
               max_tile_words: int = 0,
               codec_spans: tuple = (0, 0),
               alive: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fused lookup–merge: query_terms (Q,) x doc_targets (B,) over a
    K-stacked shard CSR -> M_{q,d} (B, Q, n_b, n_f); zeros for absent
    pairs, OOV / past-vocab terms and out-of-range doc ids.

    ``term_offsets (K, Vmax+1)`` / ``doc_ids (K, Nmax)`` /
    ``values (K, Nmax, n_b, n_f)`` are the PartitionedIndex layout; the
    single-CSR case is ``K == 1`` with ``term_to_shard=None`` (terms
    route to shard 0 at their own row).  ``split_term``/``split_doc``
    are the doc-range sub-shard tables of hot-term-split indexes (the
    owner then depends on the candidate doc, so routing is per pair);
    ``fences``/``tile`` configure the kernel's two-level bisect.

    ``codec="packed"``/``"packed-q8"`` serves tile-compressed postings
    (``core.codec``): ``doc_ids`` is None, ``packed`` carries the
    ``(packed_words, tile_bits, tile_base, tile_word_off)`` tuple (plus
    ``max_tile_words``, the static per-tile decode window), and for q8
    ``values`` is int8 with ``value_scale (K, Vmax)`` per-term dequant
    scales.  Ids decode losslessly, so packed results stay bitwise-equal
    to the uncompressed oracle; ``tile`` must equal the build-time codec
    tile (packed layouts cannot be re-tiled on the fly).
    ``codec_spans`` is the pack-time (max tiles spanned, max posting-list
    length) loop-bound hint the CPU lowering's two-level bisect uses —
    ``(0, 0)`` falls back to the worst-case iteration counts.

    ``alive`` (n_docs,) bool tombstones deleted docs: their pairs
    resolve to the same exact zeros as absent pairs.  On the CPU refs it
    folds into the found mask; on the kernel paths the output rows are
    masked per candidate doc — mathematically identical, since
    not-found rows are already exact zeros and the mask is per doc.
    """
    from ...core.index import POSTING_TILE, build_fences, fence_count

    t = int(tile or POSTING_TILE)
    if codec != "none":
        _check_packed_args(codec, packed, fences, values, tile, t)
        if interpret is None and jax.default_backend() != "tpu":
            return csr_lookup_packed_ref(
                term_offsets, packed, fences, values, value_scale,
                term_to_shard, range_lo, query_terms, doc_targets,
                split_term, split_doc, tile=t, spans=tuple(codec_spans),
                alive=alive)
        mw = int(max_tile_words)
        with jax.named_scope("seine.lookup.ids"):
            rows = _word_rows(packed, mw)
        out = _lookup_found(
            term_offsets, term_to_shard, range_lo, split_term, split_doc,
            query_terms, doc_targets, fences, rows,
            values, value_scale, tile=t, stride=packed[0].shape[1],
            packed_meta=(packed[2], packed[3]), max_tile_words=mw,
            interpret=bool(interpret))
        return _mask_dead_rows(out, alive, doc_targets)
    if interpret is None and jax.default_backend() != "tpu":
        return csr_lookup_ref(term_offsets, doc_ids, values, term_to_shard,
                              range_lo, query_terms, doc_targets,
                              split_term, split_doc, alive=alive)
    n_fence = fence_count(doc_ids.shape[1], t)
    with jax.named_scope("seine.lookup.ids"):
        # stored fences are spaced at the build-time POSTING_TILE —
        # rebuild whenever the requested tile disagrees (the parity
        # sweep's override)
        if (fences is None or t != POSTING_TILE
                or fences.shape[1] != n_fence):
            fences = build_fences(doc_ids, t)
        _, rows = _id_rows(doc_ids, t)
    out = _lookup_found(
        term_offsets, term_to_shard, range_lo, split_term, split_doc,
        query_terms, doc_targets, fences, rows, values, None, tile=t,
        stride=n_fence * t, interpret=bool(interpret))
    return _mask_dead_rows(out, alive, doc_targets)


def _mask_dead_rows(out, alive, doc_targets):
    """Tombstone the kernel lookup's output: ``out`` (B, Q, n_b, n_f)
    rows of dead candidate docs are zeroed.  Equal to folding ``alive``
    into the in-kernel found mask — the mask is per candidate doc, and
    not-found rows are exact zeros already (0 -> 0 either way)."""
    if alive is None:
        return out
    return jnp.where(_alive_at(alive, doc_targets)[:, None, None, None],
                     out, 0.0)


def _fetch_windows(starts, rows, *, width: int, align: int, interpret):
    """``starts (..., n_win)`` flat word offsets (multiples of ``align``)
    -> ``(..., n_win, width)`` words, through the window-gather kernel:
    it DMAs the covering rows, the window is sliced out here."""
    n_rows = window_rows(width, align)
    s = starts.reshape(-1, starts.shape[-1]).astype(jnp.int32)
    row0 = s // LANES
    got = window_rows_pallas(row0, rows, n_rows=n_rows, interpret=interpret)
    words = got.reshape(s.shape + (n_rows * LANES,))
    idx = (s - row0 * LANES)[..., None] + jnp.arange(width, dtype=jnp.int32)
    return jnp.take_along_axis(words, idx, axis=-1).reshape(
        starts.shape + (width,))


def _merge_window_block(ids, values, scale, ks, j0, s_lo, s_hi, blo,
                        block, t, alive):
    """Shared tail of both kernel-path doc blocks: lanes (Q, K) walk
    ``n_win`` tile-aligned windows from tile ``j0``; gather their value
    rows from the compact ``values`` (XLA — no kernel takes ``values``),
    dequantise by the lane ``scale`` when given, and segment-merge.  The
    windows start at the tile boundary below ``s_lo``, so the first
    ``lead`` entries belong to docs below the block and ``merge_windows``
    routes them to the overflow bin with the tail."""
    q_n, k_n = ks.shape
    w = ids.shape[2] * ids.shape[3]
    pos = (j0 * t)[..., None] + jnp.arange(w, dtype=jnp.int32)
    val_win = gather_rows(values, ks[..., None], pos)
    if scale is not None:
        val_win = val_win.astype(jnp.float32) * scale[..., None, None, None]
    return merge_windows(ids.reshape(q_n, k_n, w), val_win, s_hi - s_lo,
                         blo, block, lead=s_lo - j0 * t, alive=alive)


def _retrieve_block_windows(term_offsets, ids, values, term_to_shard,
                            range_lo, range_hi, query_terms, blo, block,
                            t, interpret, alive=None):
    """Kernel-path doc block: locate lane windows in jnp, gather their
    ids via the Pallas window kernel, merge with the shared scatter.

    The jnp part — lane ranges plus two range bisects per lane, the same
    branchless ``core.index._bisect`` the lookup runs, O(log Nmax) each —
    stays outside the kernel; the kernel only streams the located id
    windows.  ``ids`` is :func:`_id_rows`'s ``(flat, rows)`` pair,
    hoisted out of the top-k block loop so the tile pad is paid once
    per retrieve, not per block.
    """
    from ...core.index import _bisect

    flat, rows = ids
    k_n = term_offsets.shape[0]
    stride = flat.shape[0] // k_n
    q_n = query_terms.shape[0]
    lo_f, hi_f = retrieve_lanes(query_terms, term_offsets, term_to_shard,
                                range_lo, range_hi, stride)
    steps = bisect_steps(stride)
    s_lo = _bisect(flat, lo_f, hi_f, jnp.broadcast_to(blo, lo_f.shape),
                   n_iter=steps)
    s_hi = _bisect(flat, lo_f, hi_f,
                   jnp.broadcast_to(blo + block, lo_f.shape), n_iter=steps)
    ks = jnp.broadcast_to(jnp.arange(k_n, dtype=jnp.int32)[None, :],
                          (q_n, k_n))
    base = ks * stride
    j0 = (s_lo - base) // t
    n_win = -(-block // t) + 1                            # +1: lead spill
    tiles = jnp.clip(j0[..., None] + jnp.arange(n_win), 0, stride // t - 1)
    doc_win = _fetch_windows(base[..., None] + tiles * t, rows, width=t,
                             align=t, interpret=interpret)
    return _merge_window_block(doc_win, values, None, ks, j0, s_lo - base,
                               s_hi - base, blo, block, t, alive)


def _retrieve_block_windows_packed(term_offsets, packed, word_rows, fences,
                                   values, value_scale, term_to_shard,
                                   range_lo, range_hi, query_terms, blo,
                                   block, t, mw, interpret, alive=None):
    """Packed-codec kernel-path doc block.

    Lane windows start on posting-tile boundaries — the tile is the
    codec's atomic decode unit — so each lane's window run is aligned
    DOWN from its first live position (one extra window absorbs the
    spill).  The two range bisects run as packed two-level bisects; the
    kernel DMAs each window's ``max_tile_words`` packed words, and the
    bit-unpack happens OUT HERE in jnp — it is a vector gather per
    element, the same reason the merge scatter never entered the kernel.
    """
    words, bits, base_t, woff = packed
    k_n, n = values.shape[0], values.shape[1]
    f = bits.shape[1]
    q_n = query_terms.shape[0]
    lo_f, hi_f = retrieve_lanes(query_terms, term_offsets, term_to_shard,
                                range_lo, range_hi, n)
    ks = jnp.broadcast_to(jnp.arange(k_n, dtype=jnp.int32)[None, :],
                          lo_f.shape)
    base = ks * n
    lo_l, hi_l = lo_f - base, hi_f - base
    s_lo = packed_bisect(packed, fences, ks, lo_l, hi_l,
                         jnp.broadcast_to(blo, lo_l.shape), tile=t)
    s_hi = packed_bisect(packed, fences, ks, lo_l, hi_l,
                         jnp.broadcast_to(blo + block, lo_l.shape), tile=t)
    j0 = s_lo // t
    n_win = -(-block // t) + 1                            # +1: lead spill
    jwin = jnp.clip(j0[..., None] + jnp.arange(n_win), 0, f - 1)
    kw = ks[..., None]
    ww = _fetch_windows(kw * words.shape[1] + woff[kw, jwin], word_rows,
                        width=mw, align=1, interpret=interpret)
    # decode the id windows: tile metadata gathered per (lane, window),
    # words gathered per element from the DMA'd windows
    c = bits[kw, jwin]                                    # (Q, K, n_win)
    tb = base_t[kw, jwin]
    bp = jnp.arange(t)[None, None, None, :] * c[..., None]
    wv = jnp.take_along_axis(ww, jnp.clip(bp // 32, 0, mw - 1), axis=-1)
    rel = jax.lax.shift_right_logical(wv, jnp.bitwise_and(bp, 31)) \
        & ((1 << jnp.minimum(c, 16)) - 1)[..., None]
    ids = jnp.where(c[..., None] == 32, wv, tb[..., None] + rel)
    scale = None
    if value_scale is not None:
        scale = _lane_scale(value_scale, range_lo, ks, query_terms[:, None])
    return _merge_window_block(ids, values, scale, ks, j0, s_lo, s_hi, blo,
                               block, t, alive)


def _retrieve_dispatch(impl):
    """Map the index-level ``impl`` knob onto (use_ref, interpret).

    Unlike the lookup — where ``"jnp"`` is a *different* expression kept
    at the index layer for mesh partitioning — the retrieval scan's jnp
    reference IS the jnp path, so the mapping lives here: None/"fused"
    auto-dispatch (TPU kernel, jnp ref elsewhere), "jnp" forces the ref,
    "interpret" forces the Pallas interpreter (parity sweeps).
    """
    if impl not in (None, "fused", "jnp", "interpret"):
        raise ValueError(f"unknown retrieve impl {impl!r}; supported: "
                         "'fused', 'jnp', 'interpret'")
    if impl == "jnp":
        return True, False
    if impl == "interpret":
        return False, True
    return jax.default_backend() != "tpu", False


@partial(jax.jit, static_argnames=("block", "tile", "impl", "codec",
                                   "max_tile_words", "codec_spans"))
def csr_retrieve_block(term_offsets: jnp.ndarray, doc_ids: jnp.ndarray,
                       values: jnp.ndarray, term_to_shard, range_lo,
                       range_hi, query_terms: jnp.ndarray, blo, *,
                       block: int, tile: int | None = None,
                       impl: str | None = None, codec: str = "none",
                       packed=None,
                       value_scale: jnp.ndarray | None = None,
                       max_tile_words: int = 0,
                       codec_spans: tuple = (0, 0),
                       fences: jnp.ndarray | None = None,
                       alive: jnp.ndarray | None = None) -> jnp.ndarray:
    """Posting-range scan entry point: M rows for docs
    ``[blo, blo + block)`` x query_terms (Q,) over a K-stacked shard CSR
    -> (block, Q, n_b, n_f), built by walking the query's posting lists
    instead of bisecting per (term, doc) pair.

    Results are exact vs the per-pair lookup: exclusive shard ownership
    means the segment merge writes each cell at most once, zeros
    elsewhere (the sigma=0 semantics).  Dispatch via ``impl`` — see
    :func:`_retrieve_dispatch`; packed codecs as in :func:`csr_lookup`
    (``tile`` must equal the build-time codec tile); ``alive`` (n_docs,)
    bool tombstones deleted docs' rows to exact zeros on every path
    (the mask folds into the shared window merge).
    """
    use_ref, interpret = _retrieve_dispatch(impl)
    from ...core.index import POSTING_TILE

    t = int(tile or POSTING_TILE)
    if codec != "none":
        _check_packed_args(codec, packed, fences, values, tile, t)
        if use_ref:
            return retrieve_block_packed_ref(
                term_offsets, packed, fences, values, value_scale,
                term_to_shard, range_lo, range_hi, query_terms, blo,
                block, tile=t, spans=tuple(codec_spans), alive=alive)
        mw = int(max_tile_words)
        return _retrieve_block_windows_packed(
            term_offsets, packed, _word_rows(packed, mw), fences, values,
            value_scale, term_to_shard, range_lo, range_hi, query_terms,
            blo, block, t, mw, interpret, alive=alive)
    if use_ref:
        return retrieve_block_ref(term_offsets, doc_ids, values,
                                  term_to_shard, range_lo, range_hi,
                                  query_terms, blo, block, alive=alive)
    return _retrieve_block_windows(term_offsets, _id_rows(doc_ids, t),
                                   values, term_to_shard, range_lo,
                                   range_hi, query_terms, blo, block, t,
                                   interpret, alive=alive)


def csr_retrieve_topk(term_offsets: jnp.ndarray, doc_ids: jnp.ndarray,
                      values: jnp.ndarray, term_to_shard, range_lo,
                      range_hi, query_terms: jnp.ndarray, *, n_docs: int,
                      k: int, score_block_fn, doc_block: int | None = None,
                      tile: int | None = None, impl: str | None = None,
                      codec: str = "none", packed=None,
                      value_scale: jnp.ndarray | None = None,
                      max_tile_words: int = 0,
                      codec_spans: tuple = (0, 0),
                      fences: jnp.ndarray | None = None,
                      alive: jnp.ndarray | None = None,
                      extra_m_fn=None):
    """First-stage top-k driver: scan the whole corpus in doc blocks,
    score each block with ``score_block_fn(M_block, doc_ids_block) ->
    (block,)``, and keep a running device-side top-k.

    The merge is a streaming ``jax.lax.top_k`` over
    ``concat([running, block_scores])`` inside a ``fori_loop``; because
    the running entries come first and blocks arrive in ascending doc
    order, ties break toward the LOWER doc id — the same order as
    ``np.argsort(-scores, kind="stable")`` on the brute-force oracle.
    Returns ``(scores (k,), doc_ids (k,))``; when k exceeds the corpus,
    the tail slots carry ``-inf`` scores and doc id ``-1``.

    Exactness: the M blocks are bitwise-equal to the per-pair lookup
    (rtol=0/atol=0, tests/test_retrieval.py), so the ranking matches the
    brute-force oracle exactly.  Score VALUES are bitwise-equal too when
    the corpus fits one block (``doc_block`` defaults to the whole
    corpus up to 1024 docs — the single-block path skips the loop so its
    compilation context matches a direct score call); across multiple
    blocks XLA fuses the scorer into the loop body and may drift by
    ~1 ulp, which can only reorder docs whose true scores are closer
    than that noise — i.e. effective ties.

    Not jit'd here: ``score_block_fn`` is typically a fresh closure per
    call (it would force a retrace as a static argument), so callers jit
    their own wrapper — ``SeineEngine.retrieve`` does.

    ``alive`` (n_docs,) bool tombstones deleted docs: their M rows zero
    on every path AND their scores mask to ``-inf`` before the merge, so
    a deleted doc can never appear in the top-k; ``extra_m_fn(blo)
    -> (block, Q, n_b, n_f)``, when given, is added onto each base
    block before scoring.  The live index composes its delta run this
    way: exclusive (term, doc) ownership between base and delta makes
    the sum an exclusive write per cell (x + 0 = x exactly in f32), so
    the composed M — and hence the ranking — stays bitwise-equal to a
    monolithic rebuild.
    """
    n_docs = int(n_docs)
    k = int(k)
    block = int(doc_block or min(max(n_docs, 1), 1024))
    n_blocks = -(-max(n_docs, 1) // block)
    use_ref, interpret = _retrieve_dispatch(impl)
    from ...core.index import POSTING_TILE

    t = int(tile or POSTING_TILE)
    if codec != "none":
        _check_packed_args(codec, packed, fences, values, tile, t)
        if use_ref:
            def block_m(blo):
                return retrieve_block_packed_ref(
                    term_offsets, packed, fences, values, value_scale,
                    term_to_shard, range_lo, range_hi, query_terms, blo,
                    block, tile=t, spans=tuple(codec_spans), alive=alive)
        else:
            mw = int(max_tile_words)
            word_rows = _word_rows(packed, mw)

            def block_m(blo):
                return _retrieve_block_windows_packed(
                    term_offsets, packed, word_rows, fences, values,
                    value_scale, term_to_shard, range_lo, range_hi,
                    query_terms, blo, block, t, mw, interpret, alive=alive)
    elif use_ref:
        def block_m(blo):
            return retrieve_block_ref(term_offsets, doc_ids, values,
                                      term_to_shard, range_lo, range_hi,
                                      query_terms, blo, block, alive=alive)
    else:
        ids = _id_rows(doc_ids, t)

        def block_m(blo):
            return _retrieve_block_windows(
                term_offsets, ids, values, term_to_shard, range_lo,
                range_hi, query_terms, blo, block, t, interpret,
                alive=alive)

    init = (jnp.full((k,), -jnp.inf, jnp.float32),
            jnp.full((k,), -1, jnp.int32))

    def body(b, carry):
        run_v, run_i = carry
        blo = b * block
        m = block_m(blo)
        if extra_m_fn is not None:
            m = m + extra_m_fn(blo)
        docs = blo + jnp.arange(block, dtype=jnp.int32)
        s = score_block_fn(m, docs).astype(jnp.float32)
        s = jnp.where(docs < n_docs, s, -jnp.inf)
        if alive is not None:
            s = jnp.where(alive.at[docs].get(mode="clip"), s, -jnp.inf)
        top_v, idx = jax.lax.top_k(jnp.concatenate([run_v, s]), k)
        return top_v, jnp.concatenate([run_i, docs])[idx]

    if n_blocks == 1:
        return body(0, init)
    return jax.lax.fori_loop(0, n_blocks, body, init)


# ---------------------------------------------------------------------------
# posting-tile cache fetch/fill (serving.tile_cache.PostingTileCache)
# ---------------------------------------------------------------------------

# the in-cache pair resolution is the front end's hot path — jit the ref
# here (CPU and TPU share the expression: it is pure gathers + the
# branchless bisect, no DMA staging to specialise)
cached_tile_lookup = jax.jit(cached_tile_lookup)


@partial(jax.jit, static_argnames=("tile",))
def gather_tiles(doc_ids, values, rows, starts, *, tile: int):
    """Fetch raw posting tiles for the serving tile cache.

    ``rows`` (M,) shard indices x ``starts`` (M,) tile-aligned shard-local
    positions -> ``((M, tile) doc ids, (M, tile, n_b, n_f) values)``.
    Positions past the row tail clip-gather the last element — the padded
    doc id (``>= n_docs``, monotone), so every fetched tile stays sorted
    and the per-pair windows (clipped to the routed range before the
    in-tile bisect) never consult the duplicates.
    """
    n = doc_ids.shape[1]
    pos = (starts[:, None]
           + jnp.arange(tile, dtype=jnp.int32)[None, :]).clip(0, n - 1)
    r = rows[:, None]
    return doc_ids[r, pos], gather_rows(values, r, pos)


@partial(jax.jit, static_argnames=("tile",))
def gather_tiles_packed(packed, values, rows, starts, *, tile: int):
    """Packed-codec :func:`gather_tiles`: tile doc ids decode through
    :func:`~repro.core.codec.unpack_at` (one metadata gather per tile row,
    amortised over the whole tile), values gather from the serve payload
    (f32 under ``packed``, int8 under ``packed-q8`` — the cache keeps the
    storage dtype and dequantises at lookup).  In-tile positions past a
    short tail decode the pack-time pad (the row's last id), which keeps
    the fetched tile sorted exactly like the raw path's clip-gather."""
    from ...core.codec import unpack_at

    pos = starts[:, None] + jnp.arange(tile, dtype=jnp.int32)[None, :]
    ids = unpack_at(*packed, rows[:, None], pos, tile=tile)
    return ids, gather_rows(values, rows[:, None], pos)


@jax.jit
def fill_tile_cache(cache_ids, cache_vals, new_ids, new_vals, slots):
    """Write freshly-fetched tiles into cache slots (functional update).

    ``slots`` (M,) int32 — rows of ``new_ids``/``new_vals`` land at
    ``cache_{ids,vals}[slots]``; the cache capacity C is the drop
    sentinel (``mode="drop"``), so padding the fetch batch to a bucketed
    shape costs nothing and can never clobber a live slot."""
    return (cache_ids.at[slots].set(new_ids, mode="drop"),
            cache_vals.at[slots].set(new_vals, mode="drop"))


__all__ = ["cached_tile_lookup", "csr_lookup", "csr_lookup_packed_ref",
           "csr_lookup_ref", "csr_retrieve_block", "csr_retrieve_topk",
           "fill_tile_cache", "gather_tiles", "gather_tiles_packed",
           "lookup_pairs_ref", "merge_windows", "packed_bisect",
           "retrieve_block_packed_ref", "retrieve_block_ref",
           "retrieve_lanes", "route_pairs", "route_terms"]
