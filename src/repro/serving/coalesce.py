"""Cross-query (term, doc) coalescing for the serving front end.

Zipfian query streams share terms heavily, and re-ranking batches share
candidate documents, so a formed batch of R requests usually contains
far fewer DISTINCT (term, doc) pairs than the R * Q * B pair slots the
naive per-query path resolves.  :class:`CoalescingScorer` dedupes the
pair set on the host (one ``np.unique`` over packed 64-bit keys), pays
one routed bisect + one posting-tile fetch per distinct pair on device,
and scatters the resolved value rows back into each request's
(B, Q, n_b, n_f) interaction matrix by an index gather — exact by
construction, because every scattered row IS the row the uncoalesced
lookup would have produced (the oracle-parity tests hold scores to
rtol=0/atol=0 across retrievers x shard counts, sub-sharded Zipfian
corpora included).

The same dedupe collapses repeated terms WITHIN a single query: a
duplicated query term used to cost one routed bisect per occurrence;
now every occurrence maps to the same distinct pair and the gather
replicates its row per occurrence.  No count folding is needed — the
retrievers consume M with one row per query-term SLOT (tf, cosine
kernels, etc. are computed per slot), and an occurrence's row is
identical whether it was resolved once or twice, so replicating the
row is bitwise-equal to the naive path.

Scoring stays per request on purpose: batching R score subgraphs into
one jit program (or vmapping over requests) changes XLA's fusion
decisions enough to drift knrm/deeptilebars/hint scores by ~1 ulp,
which would break the repo's bitwise-parity story.  Per-request score
dispatches are cheap (~5 us each) next to the lookup they share.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .engine import make_qmeta

_DOC_MASK = np.int64(0xFFFFFFFF)


def plan_coalesced(requests: Sequence[Tuple[np.ndarray, np.ndarray]],
                   pair_pad: int = 0
                   ) -> Tuple[np.ndarray, np.ndarray, List[np.ndarray], int]:
    """Host-side coalescing plan over a formed batch.

    ``requests`` is a list of ``(query_terms (Q_r,), doc_ids (B_r,))``
    pairs (shapes may differ across requests).  Returns
    ``(terms (P,), docs (P,), inverses, n_distinct)``: the distinct
    (term, doc) pairs and, per request, the flat ``(B_r * Q_r,)`` int32
    gather index mapping pair slot ``(b, q)`` (row-major) to its row in
    the distinct set.

    The dedupe is TWO-LEVEL, not a flat unique over every pair slot: a
    formed batch holds ``sum(B_r * Q_r)`` slots (hundreds of thousands
    at re-ranking widths) and sorting that many packed keys on the host
    costs more than the device lookup it is trying to save.  Requests
    are outer products ``q ⊗ d``, so the slot space factors: unique the
    terms (tiny) and the docs (``sum B_r``, ~an order of magnitude
    smaller than the slot count) separately, place each slot on a
    compact (term-rank, doc-rank) grid, and mark presence with a
    vectorized scatter — no O(slots log slots) sort ever happens.  The
    distinct set and inverses fall out of one pass over the grid, in
    the same (term, doc)-sorted order the flat unique produced.  When
    the grid would be degenerate (enormous vocab x corpus footprint
    with almost no sharing) the flat packed-key unique is the safety
    net.

    ``pair_pad`` buckets the distinct count up to the next multiple
    (bounding jit compile counts under a live traffic mix); pad rows
    carry ``term = -1`` — an empty routed range on every lookup path —
    and no inverse ever references them.
    """
    if not requests:
        return (np.zeros(0, np.int32), np.zeros(0, np.int32), [], 0)
    all_t = np.concatenate([np.asarray(q).ravel() for q, _ in requests]) \
        .astype(np.int64)
    all_d = np.concatenate([np.asarray(d).ravel() for _, d in requests]) \
        .astype(np.int64)
    ut, tinv = np.unique(all_t, return_inverse=True)
    ud, dinv = np.unique(all_d, return_inverse=True)
    n_t, n_d = int(ut.shape[0]), int(ud.shape[0])
    if n_t * n_d > _GRID_CAP:
        return _plan_flat(requests, pair_pad)
    present = np.zeros(n_t * n_d, np.bool_)
    keys, ti, di = [], 0, 0
    for q, d in requests:
        nq = int(np.asarray(q).shape[0])
        nb = int(np.asarray(d).shape[0])
        # (B_r, Q_r) row-major, matching the (B, Q) reshape at score time
        k = (tinv[ti:ti + nq][None, :] * n_d
             + dinv[di:di + nb][:, None]).reshape(-1)
        keys.append(k)
        present[k] = True
        ti += nq
        di += nb
    pos = np.flatnonzero(present)
    n_distinct = int(pos.shape[0])
    # rank table: scatter each present cell's row index, then inverses
    # are one gather per request — no cumsum over the whole grid
    rank = np.empty(n_t * n_d, np.int32)
    rank[pos] = np.arange(n_distinct, dtype=np.int32)
    terms = ut[pos // n_d].astype(np.int32)
    docs = ud[pos % n_d].astype(np.int32)
    terms, docs = _pad_pairs(terms, docs, n_distinct, pair_pad)
    inverses = [rank[k] for k in keys]
    return terms, docs, inverses, n_distinct


# grid cells above which the factored plan falls back to the flat sort
# (a degenerate batch: huge term x doc footprint, near-zero sharing)
_GRID_CAP = 1 << 26


def _pad_pairs(terms, docs, n_distinct, pair_pad):
    if pair_pad > 0 and n_distinct % pair_pad:
        p = -(-n_distinct // pair_pad) * pair_pad
        terms = np.concatenate(
            [terms, np.full(p - n_distinct, -1, np.int32)])
        docs = np.concatenate([docs, np.zeros(p - n_distinct, np.int32)])
    return terms, docs


def _plan_flat(requests, pair_pad):
    """Flat packed-key unique — the original O(slots log slots) plan,
    kept as the fallback for batches whose (terms x docs) grid would
    dwarf the slot count.  Keys pack sign-preservingly into int64
    (``term << 32 | doc & 2^32-1`` — the OR never carries into the term
    bits), so padding terms (-1) and adversarial negative doc ids
    coalesce correctly."""
    keys = []
    for q, docs in requests:
        t = np.asarray(q).astype(np.int64)
        d = np.asarray(docs).astype(np.int64)
        keys.append(((t[None, :] << 32)
                     | (d[:, None] & _DOC_MASK)).reshape(-1))
    uniq, inverse = np.unique(np.concatenate(keys), return_inverse=True)
    n_distinct = int(uniq.shape[0])
    terms = (uniq >> 32).astype(np.int32)
    docs = (uniq & _DOC_MASK).astype(np.uint32).astype(np.int32)
    terms, docs = _pad_pairs(terms, docs, n_distinct, pair_pad)
    inverses, off = [], 0
    inverse = inverse.astype(np.int32)
    for k in keys:
        inverses.append(inverse[off:off + k.shape[0]])
        off += k.shape[0]
    return terms, docs, inverses, n_distinct


class CoalescingScorer:
    """Batch scorer sharing one distinct-pair lookup across requests.

    Wraps a mesh-less :class:`~repro.serving.engine.SeineEngine`: the
    engine's index resolves the distinct pairs (its ``lookup_pairs`` —
    raw or packed codec alike), then each request's scores come from a
    per-request jitted gather + retriever score, bitwise-equal to
    ``engine.score`` on the same (query, candidates).  An optional
    :class:`~repro.serving.tile_cache.PostingTileCache` takes over the
    distinct-pair resolution so hot posting tiles are served from the
    device-resident cache instead of re-fetched per batch.
    """

    def __init__(self, engine, *, cache=None, pair_pad: int = 256):
        if getattr(engine, "mesh", None) is not None:
            raise ValueError("CoalescingScorer is mesh-less only (it "
                             "bypasses the SPMD partial-sum lookup)")
        if pair_pad < 0:
            raise ValueError(f"pair_pad must be >= 0, got {pair_pad}")
        self.engine = engine
        self.index = engine.index
        self.spec = engine.spec
        self.cache = cache
        self.pair_pad = int(pair_pad)
        self._live = bool(getattr(engine.index, "is_live", False))
        spec = self.spec

        # every jit takes the index — or, live, a LiveView — as a pytree
        # ARGUMENT (the engine's pattern): jit would embed a closed-over
        # index in the program as literal constants, and compiled
        # programs keyed on shapes always consume the snapshot the batch
        # pinned.  Live, score_batch pins ONE view for its whole batch
        # (_current_view), so the lookup, the delta tail and every
        # per-request score see the same snapshot even if mutations land
        # mid-batch.
        self._batch_view = None

        def pair_lookup(view, t, d):
            # (P,) x (P,) -> (P, n_b, n_f): lookup_pairs takes (..., Q)
            # term ids against (...,) docs, so a Q=1 axis is added and
            # stripped — one routed bisect per distinct pair, on the raw
            # or packed path the index dispatches
            return view.lookup_pairs(t[:, None], d)[:, 0]

        self._plv = jax.jit(pair_lookup)
        self._pair_lookup = (
            lambda t, d: self._plv(self._current_view(), t, d))

        def pair_tail_view(view, t, d, base_vals):
            # the tile cache resolved the pairs against view.base only
            # (it binds one immutable generation): add the delta's rows
            # — exclusive doc-space ownership makes the sum exact — and
            # fold the tombstone mask
            if view.delta is not None:
                base_vals = base_vals \
                    + view.delta.lookup_pairs(t[:, None], d)[:, 0]
            if view.alive is not None:
                dead_ok = view.alive.at[d].get(mode="clip")
                base_vals = jnp.where(dead_ok[:, None, None],
                                      base_vals, 0.0)
            return base_vals

        self._pair_tail = jax.jit(pair_tail_view)

        def score_one(params, view, vals, inv, query_terms, doc_ids):
            m = vals[inv].reshape(
                (doc_ids.shape[0], query_terms.shape[0]) + vals.shape[1:])
            meta = make_qmeta(view, query_terms, doc_ids)
            return spec.score(params, m, meta, view.functions)

        sov = jax.jit(score_one)
        self._score_one = (
            lambda params, vals, inv, q, d:
            sov(params, self._current_view(), vals, inv, q, d))
        self._pairs_counter = obs.counter(
            "seine_coalesce_pair_slots_total",
            "pre-dedupe (term, doc) pair slots submitted")
        self._distinct_counter = obs.counter(
            "seine_coalesce_distinct_pairs_total",
            "distinct (term, doc) pairs looked up")
        self._dedupe_gauge = obs.gauge(
            "seine_coalesce_dedupe_ratio",
            "distinct / submitted pair slots, last batch")

    def _current_view(self):
        """The index the jits read: the batch-pinned LiveView, a fresh
        snapshot outside a batch, or the static index itself."""
        if not self._live:
            return self.index
        v = self._batch_view
        return v if v is not None else self.index.view

    def lookup_distinct(self, terms: np.ndarray, docs: np.ndarray):
        """(P,) distinct pairs -> (P, n_b, n_f) value rows (device).

        With a tile cache under a live index, the cache serves the BASE
        generation's rows and the delta/tombstone tail is applied on
        top per call — exact, and still one cached-tile probe per pair.
        If a compaction swapped the base under the batch before the
        frontend rebound the cache, the cache is bypassed for this call
        (the plain view-consistent lookup) rather than mixing rows of
        two generations.
        """
        if self.cache is not None:
            if self._live:
                view = self._current_view()
                if view.base is not self.cache.index:
                    # torn-epoch guard: cache still bound to the old
                    # generation — serve snapshot-consistent instead
                    return self._plv(view, jnp.asarray(terms),
                                     jnp.asarray(docs))
                vals = self.cache.lookup(terms, docs)
                return self._pair_tail(view, jnp.asarray(terms),
                                       jnp.asarray(docs), vals)
            return self.cache.lookup(terms, docs)
        return self._pair_lookup(jnp.asarray(terms), jnp.asarray(docs))

    def score_batch(self, requests: Sequence[Tuple[np.ndarray, np.ndarray]]
                    ) -> List[jnp.ndarray]:
        """Score a formed batch; returns per-request (B_r,) device arrays
        (callers block — the serving loop does, inside its timed span)."""
        terms, docs, inverses, n_distinct = plan_coalesced(
            requests, self.pair_pad)
        if obs.enabled():
            slots = sum(iv.shape[0] for iv in inverses)
            self._pairs_counter.inc(slots)
            self._distinct_counter.inc(n_distinct)
            self._dedupe_gauge.set(n_distinct / max(slots, 1))
        if self._live:
            # pin ONE snapshot for the whole batch: lookup, delta tail
            # and every per-request score resolve against it, so a
            # mutation landing mid-batch can never mix snapshots
            self._batch_view = self.index.view
        try:
            vals = self.lookup_distinct(terms, docs)
            out = []
            for (q, d), inv in zip(requests, inverses):
                out.append(self._score_one(self.engine.params, vals,
                                           jnp.asarray(inv),
                                           jnp.asarray(q),
                                           jnp.asarray(d)))
        finally:
            self._batch_view = None
        return out
