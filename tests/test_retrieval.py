"""First-stage retrieval: posting-scan parity + top-k exactness vs the
brute-force score-all-docs oracle, and the serving-path bug-fix sweep.

Exactness contract (see ``csr_retrieve_topk``): the scanned M blocks are
bitwise-equal to the per-pair lookup (rtol=0/atol=0), so recall@k vs the
oracle is 1.0 with ties resolved toward the lower doc id — the same
order as ``np.argsort(-scores, kind="stable")``.  Score VALUES are
bitwise on the single-block default; multi-block scans may drift ~1 ulp
(XLA fuses the scorer into the loop body), which cannot reorder docs
whose scores differ by more than that noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.data.synth_corpus import build_zipfian_index
from repro.dist.sharding import partition_index
from repro.kernels.csr_lookup import csr_retrieve_block
from repro.retrievers import get_retriever
from repro.serving.engine import (SeineEngine, ServeStats, make_qmeta,
                                  serve_batches, serve_retrieval)

K_SWEEP = (1, 2, 4)
RETRIEVERS = ("knrm", "deeptilebars", "hint", "deepimpact")
# mixed hostile query: valid terms, padding (-1), past-vocab (99)
QUERY = (3, 0, -1, 7, 99, 5)


@pytest.fixture(scope="module")
def small_index():
    return build_zipfian_index(n_docs=64, vocab=40)


def _stacked(index, k):
    """(term_offsets, doc_ids, values, t2s, range_lo, range_hi) for K
    shards; K == 1 is the single-CSR layout (leading axis added)."""
    if k == 1:
        return (index.term_offsets[None], index.doc_ids[None],
                index.values[None], None, None, None), index
    p = partition_index(index, k)
    return (p.term_offsets, p.doc_ids, p.values, p.term_to_shard,
            p.range_lo, p.range_hi), p


def _oracle(index, spec, params, q):
    """Brute force: score EVERY doc through the lookup path, stable
    argsort descending (ties -> lower doc id)."""
    all_docs = jnp.arange(index.n_docs, dtype=jnp.int32)
    m = index.qd_matrix(q, all_docs)
    meta = make_qmeta(index, q, all_docs)
    scores = np.asarray(spec.score(params, m, meta, index.functions))
    return scores, np.argsort(-scores, kind="stable")


def _score_fn(index, spec, params, q):
    def score_block(m, docs):
        meta = make_qmeta(index, q, docs.clip(0, index.n_docs - 1))
        return spec.score(params, m, meta, index.functions)
    return score_block


class TestRetrieveBlockParity:
    """The scanned M blocks ARE the lookup's M, bit for bit."""

    @pytest.mark.parametrize("k_shards", K_SWEEP)
    @pytest.mark.parametrize("block,blo", [(64, 0), (16, 16), (16, 48),
                                           (100, 0)])
    def test_block_matches_lookup(self, small_index, k_shards, block, blo):
        q = jnp.asarray(QUERY, dtype=jnp.int32)
        arrs, idx = _stacked(small_index, k_shards)
        all_docs = jnp.arange(small_index.n_docs, dtype=jnp.int32)
        want = np.asarray(small_index.qd_matrix(q, all_docs))[blo:blo + block]
        got = np.asarray(csr_retrieve_block(*arrs, q, blo, block=block))
        # rtol=0/atol=0 (not array_equal): the segment scatter may emit
        # +0.0 where the lookup's masked select emits -0.0 — numerically
        # identical, different bit patterns
        np.testing.assert_allclose(got[:want.shape[0]], want,
                                   rtol=0, atol=0)
        assert not np.any(got[want.shape[0]:])

    @pytest.mark.parametrize("k_shards", K_SWEEP)
    def test_interpret_kernel_matches(self, small_index, k_shards):
        """The Pallas window-gather kernel (interpret mode on CPU) lands
        on the same bits as the jnp ref."""
        q = jnp.asarray(QUERY, dtype=jnp.int32)
        arrs, _ = _stacked(small_index, k_shards)
        all_docs = jnp.arange(small_index.n_docs, dtype=jnp.int32)
        want = np.asarray(small_index.qd_matrix(q, all_docs))
        for blo, block in ((0, 64), (32, 16)):
            got = np.asarray(csr_retrieve_block(
                *arrs, q, blo, block=block, impl="interpret"))
            ref = want[blo:blo + block]
            np.testing.assert_allclose(got[:ref.shape[0]], ref,
                                       rtol=0, atol=0)

    def test_hot_term_subshard_block(self, hot_term_index):
        """Doc-range sub-sharded corpus: boundary terms live in several
        shards (disjoint doc slices) — the range-ownership lanes must
        still produce each posting exactly once."""
        p = partition_index(hot_term_index, 8)
        assert p.split_term is not None     # the sweep actually split
        q = jnp.asarray([0, 1, 5, -1, 17], dtype=jnp.int32)
        all_docs = jnp.arange(hot_term_index.n_docs, dtype=jnp.int32)
        want = np.asarray(hot_term_index.qd_matrix(q, all_docs))
        arrs = (p.term_offsets, p.doc_ids, p.values, p.term_to_shard,
                p.range_lo, p.range_hi)
        for blo in range(0, hot_term_index.n_docs, 16):
            got = np.asarray(csr_retrieve_block(*arrs, q, blo, block=16))
            np.testing.assert_allclose(got, want[blo:blo + 16],
                                       rtol=0, atol=0)


class TestRetrieveTopK:
    @pytest.mark.parametrize("retriever", RETRIEVERS)
    @pytest.mark.parametrize("k_shards", K_SWEEP)
    def test_recall_is_exact(self, small_index, retriever, k_shards):
        """recall@k = 1.0 vs brute force for K in {1,2,4} x k in
        {1,2,4}; scores bitwise on the single-block default path."""
        spec = get_retriever(retriever)
        params = spec.init(jax.random.key(0), small_index.n_b,
                           small_index.functions)
        q = jnp.asarray(QUERY, dtype=jnp.int32)
        scores, order = _oracle(small_index, spec, params, q)
        _, idx = _stacked(small_index, k_shards)
        fn = _score_fn(idx, spec, params, q)
        for k in (1, 2, 4):
            sv, si = idx.retrieve_topk(q, k, fn)
            np.testing.assert_array_equal(np.asarray(si), order[:k])
            np.testing.assert_allclose(np.asarray(sv), scores[order[:k]],
                                       rtol=0, atol=0)

    @pytest.mark.parametrize("k_shards", K_SWEEP)
    def test_multi_block_ids_exact(self, small_index, k_shards):
        """A blocked scan (doc_block < corpus) returns the same ranking;
        scores within fusion ulps of the oracle."""
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), small_index.n_b,
                           small_index.functions)
        q = jnp.asarray(QUERY, dtype=jnp.int32)
        scores, order = _oracle(small_index, spec, params, q)
        _, idx = _stacked(small_index, k_shards)
        fn = _score_fn(idx, spec, params, q)
        sv, si = idx.retrieve_topk(q, 10, fn, doc_block=16)
        np.testing.assert_array_equal(np.asarray(si), order[:10])
        np.testing.assert_allclose(np.asarray(sv), scores[order[:10]],
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("retriever", RETRIEVERS)
    def test_hot_term_subshard_corpus(self, hot_term_index, retriever):
        """The acceptance sweep's Zipfian corpus: hot term split across
        doc-range sub-shards, every retriever, recall@k = 1.0."""
        spec = get_retriever(retriever)
        params = spec.init(jax.random.key(1), hot_term_index.n_b,
                           hot_term_index.functions)
        p = partition_index(hot_term_index, 8)
        assert p.split_term is not None
        q = jnp.asarray([0, 1, 5, -1, 17], dtype=jnp.int32)
        scores, order = _oracle(hot_term_index, spec, params, q)
        fn = _score_fn(p, spec, params, q)
        for k in (1, 2, 4):
            sv, si = p.retrieve_topk(q, k, fn)
            np.testing.assert_array_equal(np.asarray(si), order[:k])
            np.testing.assert_allclose(np.asarray(sv), scores[order[:k]],
                                       rtol=0, atol=0)

    def test_k_exceeds_corpus(self, small_index):
        """k > n_docs: index-level call pads with (-inf, -1)."""
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), small_index.n_b,
                           small_index.functions)
        q = jnp.asarray(QUERY, dtype=jnp.int32)
        scores, order = _oracle(small_index, spec, params, q)
        fn = _score_fn(small_index, spec, params, q)
        k = small_index.n_docs + 36
        sv, si = small_index.retrieve_topk(q, k, fn)
        n = small_index.n_docs
        np.testing.assert_array_equal(np.asarray(si)[:n], order)
        assert np.all(np.asarray(si)[n:] == -1)
        assert np.all(np.isneginf(np.asarray(sv)[n:]))

    def test_k_exceeds_postings_touched(self, small_index):
        """A query whose posting lists touch fewer docs than k: zero-M
        docs still rank by the retriever's doc-dependent background
        score, exactly as brute force does."""
        offs = np.asarray(small_index.term_offsets)
        counts = np.diff(offs)
        # rarest populated term — touches the fewest docs
        w = int(np.argmin(np.where(counts > 0, counts, counts.max() + 1)))
        touched = int(counts[w])
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), small_index.n_b,
                           small_index.functions)
        q = jnp.asarray([w], dtype=jnp.int32)
        scores, order = _oracle(small_index, spec, params, q)
        fn = _score_fn(small_index, spec, params, q)
        k = min(touched + 8, small_index.n_docs)
        assert k > touched
        sv, si = small_index.retrieve_topk(q, k, fn)
        np.testing.assert_array_equal(np.asarray(si), order[:k])
        np.testing.assert_allclose(np.asarray(sv), scores[order[:k]],
                                   rtol=0, atol=0)

    def test_all_oov_query(self, small_index):
        """Every term OOV/padding: M is all zeros, ranking falls back to
        the background score — identical to brute force."""
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), small_index.n_b,
                           small_index.functions)
        q = jnp.asarray([-1, 99, 101], dtype=jnp.int32)
        scores, order = _oracle(small_index, spec, params, q)
        fn = _score_fn(small_index, spec, params, q)
        sv, si = small_index.retrieve_topk(q, 5, fn)
        np.testing.assert_array_equal(np.asarray(si), order[:5])
        np.testing.assert_allclose(np.asarray(sv), scores[order[:5]],
                                   rtol=0, atol=0)

    def test_unknown_impl_raises(self, small_index):
        fn = _score_fn(small_index, get_retriever("knrm"),
                       get_retriever("knrm").init(
                           jax.random.key(0), small_index.n_b,
                           small_index.functions),
                       jnp.asarray([0], jnp.int32))
        with pytest.raises(ValueError, match="unknown retrieve impl"):
            small_index.retrieve_topk(jnp.asarray([0], jnp.int32), 2, fn,
                                      impl="bogus")


class TestEngineRetrieve:
    @pytest.fixture(scope="class")
    def engine(self, small_index):
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), small_index.n_b,
                           small_index.functions)
        return SeineEngine(small_index, "knrm", params,
                           partition="term", n_shards=4)

    def test_matches_oracle_through_engine(self, small_index, engine):
        q = jnp.asarray(QUERY, dtype=jnp.int32)
        all_docs = jnp.arange(small_index.n_docs, dtype=jnp.int32)
        scores = np.asarray(engine.score(q, all_docs))
        order = np.argsort(-scores, kind="stable")
        sv, si = engine.retrieve(q, 10)
        np.testing.assert_array_equal(np.asarray(si), order[:10])

    def test_k_trimmed_to_corpus(self, small_index, engine):
        sv, si = engine.retrieve(jnp.asarray(QUERY, jnp.int32), 10_000)
        assert sv.shape == si.shape == (small_index.n_docs,)
        assert np.all(np.asarray(si) >= 0)      # no pad slots leak out

    def test_nonpositive_k_raises(self, engine):
        with pytest.raises(ValueError, match="k must be positive"):
            engine.retrieve(jnp.asarray(QUERY, jnp.int32), 0)

    def test_serve_retrieval_loop(self, engine):
        qs = [np.asarray(QUERY, np.int32),
              np.asarray([-1, 99, 101], np.int32)]
        results, stats = serve_retrieval(engine, qs, 5)
        assert len(results) == 2
        for sv, si in results:
            assert sv.shape == si.shape == (5,)
            assert (np.diff(sv) <= 0).all()     # descending scores
        assert stats.n_requests == 2
        assert stats.p95_ms >= stats.p50_ms >= 0


class TestServingPathFixes:
    """The three ISSUE-7 serving bugs stay fixed."""

    def _engine(self, small_index, **kw):
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), small_index.n_b,
                           small_index.functions)
        return SeineEngine(small_index, "knrm", params, **kw)

    def test_nonpositive_n_shards_raises(self, small_index):
        for bad in (0, -3):
            with pytest.raises(ValueError, match="n_shards must be"):
                self._engine(small_index, partition="term", n_shards=bad)

    def test_nonpositive_lookup_tile_raises(self, small_index):
        with pytest.raises(ValueError, match="lookup_tile must be"):
            self._engine(small_index, lookup_tile=0)

    def test_negative_batch_pad_raises(self, small_index):
        eng = self._engine(small_index)
        with pytest.raises(ValueError, match="batch_pad must be"):
            serve_batches(eng, [(np.asarray(QUERY, np.int32),
                                 np.arange(4))], batch_pad=-1)

    def test_sampling_off_timed_path(self, small_index):
        """The timed path runs the served program and nothing else: one
        dispatch of it per request (no second lookup program, no host
        sync for stats), while the pair counter comes from the request's
        host arrays — valid terms x real candidates, pads excluded."""
        eng = self._engine(small_index)
        q = np.asarray(QUERY, np.int32)
        docs = np.arange(13)
        serve_batches(eng, [(q, docs)], batch_pad=8)   # warm: compile
        served = eng._score
        calls = []

        def counted(*a):
            calls.append(1)
            return served(*a)

        eng._score = counted
        pairs = obs.counter("seine_lookup_pairs_total")
        before = pairs.get()
        out, stats = serve_batches(eng, [(q, docs)] * 3, batch_pad=8)
        assert len(calls) == 3 and stats.n_requests == 3
        assert pairs.get() - before == 3 * int((q >= 0).sum()) * 13
        assert all(o.shape == (13,) for o in out)
        # a bare score() counts no pairs: only the serve loops do
        jax.block_until_ready(eng.score(jnp.asarray(q), jnp.asarray(docs)))
        assert pairs.get() - before == 3 * int((q >= 0).sum()) * 13

    def test_quantile_snapshot_equivalence(self):
        """Cached-snapshot percentiles == sorting per access, and the
        snapshot is shared between reads and invalidated by record()."""
        rng = np.random.RandomState(7)
        vals = rng.lognormal(1.0, 0.8, size=500)
        s = ServeStats()
        for v in vals:
            s.record(float(v))
        for q in (0.0, 25.0, 50.0, 95.0, 99.9, 100.0):
            assert s.percentile_ms(q) == pytest.approx(
                float(np.percentile(vals, q)), rel=0, abs=0)
        snap1 = s._sorted_ms()
        assert s._sorted_ms() is snap1      # p50+p95 share one sort
        s.record(0.001)                     # below the lognormal's min
        assert s._sorted_ms() is not snap1  # new sample -> new snapshot
        assert s.percentile_ms(0.0) == 0.001

    def test_windowed_quantiles_still_windowed(self):
        """The snapshot respects the recent-window deque semantics the
        existing windowing test pins (oldest samples age out)."""
        s = ServeStats(window=8)
        for v in range(100):                # only 92..99 remain
            s.record(float(v))
        assert s.percentile_ms(0.0) == 92.0
        assert s.percentile_ms(100.0) == 99.0

    def test_sampled_stats_survive_past_vocab_terms(self, small_index):
        """Regression: a partitioned engine's routing stats once crashed
        indexing the host routing table with past-vocab terms (they
        have no table row; the device lookup clip-routes them).  The
        pair count now never touches that table: past-vocab terms are
        valid slots and count like any other."""
        eng = self._engine(small_index, partition="term", n_shards=4)
        q = np.asarray([3, 99, 1000, -1], np.int32)
        pairs = obs.counter("seine_lookup_pairs_total")
        before = pairs.get()
        out, _ = serve_batches(eng, [(q, np.arange(8))])
        assert np.isfinite(out[0]).all()
        assert pairs.get() - before == 3 * 8