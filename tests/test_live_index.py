"""Live index: LSM delta runs, tombstone deletes, compaction, epoch swap.

Exactness contracts under test (see ``repro.dist.live``):

* **Insert-only parity is bitwise.**  A LiveIndex built as base(half) +
  streamed inserts(other half) reproduces the from-scratch rebuild of
  the full corpus at rtol=0/atol=0 — lookups, qd matrices, retrieval
  scores AND the corpus stats (idf is vocab-derived, the per-doc
  pipeline is batch-composition-independent, and exclusive doc-space
  ownership makes the base+delta merge an exclusive write per cell).
* **Deletes are exact-zero + ``-inf``.**  A tombstoned doc's M rows are
  zero on every lookup path and its retrieval score is masked to
  ``-inf`` before the merge, so it can never surface in the top-k.
* **Compaction is bitwise-invisible.**  The merged next generation
  serves the same bits as the pre-compaction base+delta view — which is
  what lets queries run concurrently with the merge (every in-flight
  result must equal the quiescent answer, torn-epoch test below).
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.dist import LiveIndex, LiveView, live_index
from repro.dist.live import _explode_base, found_counts
from repro.dist.partition import partitioned_from_runs
from repro.dist.sharding import partition_index
from repro.retrievers import get_retriever
from repro.serving import SeineEngine, ServingFrontend
from repro.serving.engine import make_qmeta

K_SWEEP = (1, 2, 4)
RETRIEVERS = ("knrm", "deeptilebars", "hint", "deepimpact")
QUERY = (3, 0, -1, 7, 99, 5)    # dup term, pad slot, out-of-vocab id


def _halves(w):
    toks, segs = w["toks"], w["segs"]
    h = toks.shape[0] // 2
    return (toks[:h], segs[:h]), (toks[h:], segs[h:])


def _mk_live(w, k, *, codec="none", ckpt_dir=None, delta_shards=1,
             insert=True):
    """base(first half) + live-inserted second half."""
    (t0, s0), (t1, s1) = _halves(w)
    builder = w["builder"]
    base = builder.build_partitioned(t0, s0, k, batch_size=16, codec=codec)
    live = LiveIndex(base, builder._pipeline(), delta_shards=delta_shards,
                     batch_size=16, ckpt_dir=ckpt_dir)
    if insert:
        ids = live.insert(t1, s1)
        np.testing.assert_array_equal(
            ids, np.arange(base.n_docs, base.n_docs + t1.shape[0]))
    return live


def _score_fn(index, spec, params):
    n = index.n_docs

    def score_block(m, docs):
        meta = make_qmeta(index, jnp.asarray(QUERY, jnp.int32),
                          docs.clip(0, n - 1))
        return spec.score(params, m, meta, index.functions)
    return score_block


def _retriever(name, index):
    spec = get_retriever(name)
    params = spec.init(jax.random.key(0), index.n_b, index.functions)
    return spec, params


def _pairs(n_docs, vocab, n=24, seed=3):
    rng = np.random.RandomState(seed)
    t = rng.randint(-1, vocab, size=(n, 5)).astype(np.int32)
    d = rng.randint(0, n_docs, size=n).astype(np.int32)
    return jnp.asarray(t), jnp.asarray(d)


@pytest.fixture(scope="module")
def full2(seine_world):
    w = seine_world
    return w["builder"].build_partitioned(w["toks"], w["segs"], 2,
                                          batch_size=16)


@pytest.fixture(scope="module")
def live2(seine_world):
    """Insert-only live index; parity tests treat it as READ-ONLY.
    Mutation tests (delete/compact) build their own via _mk_live."""
    return _mk_live(seine_world, 2)


@pytest.fixture(scope="module")
def live2_dead(seine_world):
    """A delta plus three tombstones (base ids and a delta id); READ-ONLY."""
    live = _mk_live(seine_world, 2)
    live.delete([1, 3, live.n_docs - 2])
    return live


# ---------------------------------------------------------------------------
# insert-only parity: live == from-scratch rebuild, bit for bit
# ---------------------------------------------------------------------------
class TestInsertParity:
    def test_stats_bitwise(self, live2, full2):
        assert live2.n_docs == full2.n_docs
        np.testing.assert_allclose(np.asarray(live2.doc_len),
                                   np.asarray(full2.doc_len),
                                   rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(live2.seg_len),
                                   np.asarray(full2.seg_len),
                                   rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(live2.idf),
                                   np.asarray(full2.idf), rtol=0, atol=0)
        assert float(live2.avg_doc_len) == float(full2.avg_doc_len)
        assert live2.nnz == full2.nnz
        assert live2.delta_nnz > 0          # the delta is actually in play
        assert live2.generation == 0
        assert live2.tombstones == 0

    @pytest.mark.parametrize("impl", ("fused", "jnp"))
    def test_lookup_and_qd_bitwise(self, seine_world, live2, full2, impl):
        w = seine_world
        t, d = _pairs(full2.n_docs, w["vocab"].size)
        np.testing.assert_allclose(
            np.asarray(live2.lookup_pairs(t, d, impl=impl)),
            np.asarray(full2.lookup_pairs(t, d, impl=impl)),
            rtol=0, atol=0)
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(full2.n_docs, dtype=jnp.int32)
        np.testing.assert_allclose(
            np.asarray(live2.qd_matrix(q, docs, impl=impl)),
            np.asarray(full2.qd_matrix(q, docs, impl=impl)),
            rtol=0, atol=0)

    def test_qd_interpret_kernel(self, live2, full2):
        """The Pallas kernels (interpret mode on CPU) see the same bits
        through the live composition."""
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(full2.n_docs, dtype=jnp.int32)
        np.testing.assert_allclose(
            np.asarray(live2.qd_matrix(q, docs, impl="interpret")),
            np.asarray(full2.qd_matrix(q, docs, impl="interpret")),
            rtol=0, atol=0)

    @pytest.mark.parametrize("k_shards", K_SWEEP)
    def test_shard_sweep(self, seine_world, k_shards):
        w = seine_world
        live = _mk_live(w, k_shards)
        full = w["builder"].build_partitioned(w["toks"], w["segs"],
                                              k_shards, batch_size=16)
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(full.n_docs, dtype=jnp.int32)
        np.testing.assert_allclose(np.asarray(live.qd_matrix(q, docs)),
                                   np.asarray(full.qd_matrix(q, docs)),
                                   rtol=0, atol=0)
        spec, params = _retriever("deepimpact", full)
        sv, si = live.retrieve_topk(q, 5, _score_fn(live, spec, params))
        fv, fi = full.retrieve_topk(q, 5, _score_fn(full, spec, params))
        np.testing.assert_array_equal(np.asarray(si), np.asarray(fi))
        np.testing.assert_allclose(np.asarray(sv), np.asarray(fv),
                                   rtol=0, atol=0)

    @pytest.mark.parametrize("retriever", RETRIEVERS)
    def test_retrieve_bitwise(self, live2, full2, retriever):
        spec, params = _retriever(retriever, full2)
        q = jnp.asarray(QUERY, jnp.int32)
        for impl in ("fused", "jnp"):
            for k in (1, 2, 4):
                sv, si = live2.retrieve_topk(
                    q, k, _score_fn(live2, spec, params), impl=impl)
                fv, fi = full2.retrieve_topk(
                    q, k, _score_fn(full2, spec, params), impl=impl)
                np.testing.assert_array_equal(np.asarray(si),
                                              np.asarray(fi))
                np.testing.assert_allclose(np.asarray(sv), np.asarray(fv),
                                           rtol=0, atol=0)

    def test_retrieve_interpret(self, live2, full2):
        spec, params = _retriever("knrm", full2)
        q = jnp.asarray(QUERY, jnp.int32)
        sv, si = live2.retrieve_topk(q, 3, _score_fn(live2, spec, params),
                                     impl="interpret")
        fv, fi = full2.retrieve_topk(q, 3, _score_fn(full2, spec, params),
                                     impl="interpret")
        np.testing.assert_array_equal(np.asarray(si), np.asarray(fi))
        np.testing.assert_allclose(np.asarray(sv), np.asarray(fv),
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# engine-level live mode
# ---------------------------------------------------------------------------
class TestEngineLive:
    @pytest.mark.parametrize("retriever", ("knrm", "deepimpact"))
    def test_score_bitwise(self, seine_world, live2, full2, retriever):
        w = seine_world
        spec = get_retriever(retriever)
        params = spec.init(jax.random.key(0), full2.n_b, full2.functions)
        le = SeineEngine(live2, retriever, params)
        fe = SeineEngine(full2, retriever, params)
        rng = np.random.RandomState(11)
        for q in w["queries"][:4]:
            docs = rng.randint(0, full2.n_docs, size=8).astype(np.int32)
            np.testing.assert_allclose(np.asarray(le.score(q, docs)),
                                       np.asarray(fe.score(q, docs)),
                                       rtol=0, atol=0)

    def test_retrieve_bitwise(self, seine_world, live2, full2):
        spec = get_retriever("deepimpact")
        params = spec.init(jax.random.key(0), full2.n_b, full2.functions)
        le = SeineEngine(live2, "deepimpact", params)
        fe = SeineEngine(full2, "deepimpact", params)
        for q in seine_world["queries"][:3]:
            lv, li = le.retrieve(q, 5)
            fv, fi = fe.retrieve(q, 5)
            np.testing.assert_array_equal(np.asarray(li), np.asarray(fi))
            np.testing.assert_allclose(np.asarray(lv), np.asarray(fv),
                                       rtol=0, atol=0)

    def test_live_guards(self, live2):
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), live2.n_b, live2.functions)
        with pytest.raises(ValueError):
            SeineEngine(live2, "knrm", params, partition="term")


# ---------------------------------------------------------------------------
# packed codecs on the live base
# ---------------------------------------------------------------------------
class TestPackedCodec:
    def test_packed_base_bitwise_vs_rebuild(self, seine_world):
        """codec='packed' is lossless, so live(packed base) vs packed
        rebuild parity stays bitwise end to end."""
        w = seine_world
        live = _mk_live(w, 2, codec="packed")
        full = w["builder"].build_partitioned(w["toks"], w["segs"], 2,
                                              batch_size=16, codec="packed")
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(full.n_docs, dtype=jnp.int32)
        np.testing.assert_allclose(np.asarray(live.qd_matrix(q, docs)),
                                   np.asarray(full.qd_matrix(q, docs)),
                                   rtol=0, atol=0)
        spec, params = _retriever("hint", full)
        sv, si = live.retrieve_topk(q, 4, _score_fn(live, spec, params))
        fv, fi = full.retrieve_topk(q, 4, _score_fn(full, spec, params))
        np.testing.assert_array_equal(np.asarray(si), np.asarray(fi))
        np.testing.assert_allclose(np.asarray(sv), np.asarray(fv),
                                   rtol=0, atol=0)

    def test_q8_base_self_consistent(self, seine_world):
        """packed-q8 quantises over the BASE corpus only, so there is no
        bitwise rebuild oracle; instead retrieval must match the brute-
        force argsort over the live view's own qd matrix."""
        live = _mk_live(seine_world, 2, codec="packed-q8")
        assert live.codec == "packed-q8"
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(live.n_docs, dtype=jnp.int32)
        spec, params = _retriever("deepimpact", live)
        m = live.qd_matrix(q, docs)
        meta = make_qmeta(live, q, docs)
        scores = np.asarray(spec.score(params, m, meta, live.functions))
        order = np.argsort(-scores, kind="stable")
        sv, si = live.retrieve_topk(q, 5, _score_fn(live, spec, params))
        np.testing.assert_array_equal(np.asarray(si), order[:5])
        np.testing.assert_allclose(np.asarray(sv), scores[order[:5]],
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# tombstone deletes
# ---------------------------------------------------------------------------
class TestDeletes:
    def test_qd_rows_zero_and_lookup_masked(self, seine_world, full2):
        w = seine_world
        live = _mk_live(w, 2)
        dead = [1, 3, live.n_docs - 2]      # base ids + a delta id
        assert live.delete(dead) == 3
        assert live.tombstones == 3
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(full2.n_docs, dtype=jnp.int32)
        want = np.asarray(full2.qd_matrix(q, docs)).copy()
        want[np.asarray(dead)] = 0.0
        for impl in ("fused", "jnp"):
            np.testing.assert_allclose(
                np.asarray(live.qd_matrix(q, docs, impl=impl)), want,
                rtol=0, atol=0)
        t, d = _pairs(full2.n_docs, w["vocab"].size)
        ref = np.asarray(full2.lookup_pairs(t, d)).copy()
        ref[np.isin(np.asarray(d), dead)] = 0.0
        np.testing.assert_allclose(np.asarray(live.lookup_pairs(t, d)),
                                   ref, rtol=0, atol=0)

    @pytest.mark.parametrize("impl", ("fused", "interpret", "jnp"))
    def test_qd_matrix_cols_bitwise(self, seine_world, live2_dead, impl):
        """Base and delta narrow alike: ``cols=c`` == the full M's
        ``[..., c]`` bit for bit, dead docs included; the engine's score
        over the narrowed M == the spec's over the full one."""
        live = live2_dead
        assert live.delta_nnz > 0 and live.tombstones == 3
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(live.n_docs, dtype=jnp.int32)
        full = np.asarray(live.qd_matrix(q, docs, impl=impl))
        t, d = _pairs(live.n_docs, seine_world["vocab"].size)
        full_pairs = (None if impl == "interpret" else
                      np.asarray(live.lookup_pairs(t, d, impl=impl)))
        for cols in ((3,), (0, 1, 4)):
            np.testing.assert_array_equal(
                np.asarray(live.qd_matrix(q, docs, impl=impl, cols=cols)),
                full[..., list(cols)], err_msg=f"qd_matrix cols={cols}")
            if full_pairs is not None:
                np.testing.assert_array_equal(
                    np.asarray(live.lookup_pairs(t, d, impl=impl,
                                                 cols=cols)),
                    full_pairs[..., list(cols)],
                    err_msg=f"lookup_pairs cols={cols}")
        spec, params = _retriever("deeptilebars", live)
        eng = SeineEngine(live, "deeptilebars", params)
        eng._lookup_impl = impl
        assert len(eng._functions) == 3

        @jax.jit
        def full_m_score(params, v, q, d):
            return spec.score(params, v.qd_matrix(q, d, impl=impl),
                              make_qmeta(v, q, d), v.functions)

        np.testing.assert_array_equal(
            np.asarray(eng.score(q, docs)),
            np.asarray(full_m_score(params, live.view, q, docs)))

    def test_retrieve_excludes_dead(self, seine_world):
        live = _mk_live(seine_world, 2)
        dead = np.array([0, 2, 5, live.n_docs - 1])
        live.delete(dead)
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(live.n_docs, dtype=jnp.int32)
        spec, params = _retriever("knrm", live)
        m = live.qd_matrix(q, docs)
        meta = make_qmeta(live, q, docs)
        scores = np.asarray(spec.score(params, m, meta,
                                       live.functions)).copy()
        scores[dead] = -np.inf
        order = np.argsort(-scores, kind="stable")
        for impl in ("fused", "jnp"):
            sv, si = live.retrieve_topk(q, 6,
                                        _score_fn(live, spec, params),
                                        impl=impl)
            assert not np.isin(np.asarray(si), dead).any()
            np.testing.assert_array_equal(np.asarray(si), order[:6])
            np.testing.assert_allclose(np.asarray(sv), scores[order[:6]],
                                       rtol=0, atol=0)

    def test_delete_idempotent_and_bounds(self, seine_world):
        live = _mk_live(seine_world, 1, insert=False)
        assert live.delete([0, 0, 1]) == 2
        assert live.delete([0, 1]) == 0     # already dead: no-op
        assert live.tombstones == 2
        with pytest.raises(ValueError):
            live.delete([live.n_docs])
        with pytest.raises(ValueError):
            live.delete([-1])

    def test_update_reassigns_id(self, seine_world):
        w = seine_world
        live = _mk_live(w, 1)
        (t0, s0), _ = _halves(w)
        old_n = live.n_docs
        new_ids = live.update([4], t0[:1], s0[:1])
        np.testing.assert_array_equal(new_ids, [old_n])
        assert live.tombstones == 1
        # the old id is tombstoned; the new id serves the re-ingested
        # content (doc 0's tokens), bitwise equal to doc 0's own row —
        # the per-doc pipeline is batch-composition-independent
        q = jnp.arange(live.vocab_size, dtype=jnp.int32)
        got = np.asarray(live.qd_matrix(q, jnp.asarray([old_n], jnp.int32)))
        old = np.asarray(live.qd_matrix(q, jnp.asarray([4], jnp.int32)))
        ref = np.asarray(live.qd_matrix(q, jnp.asarray([0], jnp.int32)))
        assert not old.any()                # old id is tombstoned
        assert got.any()
        np.testing.assert_allclose(got, ref, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# compaction: the merge must be bitwise-invisible
# ---------------------------------------------------------------------------
class TestCompaction:
    @pytest.mark.parametrize("codec", ("none", "packed", "packed-q8"))
    def test_compact_bitwise_invisible(self, seine_world, codec):
        live = _mk_live(seine_world, 2, codec=codec)
        live.delete([1, 7, live.n_docs - 3])
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(live.n_docs, dtype=jnp.int32)
        spec, params = _retriever("deeptilebars", live)
        want_qd = np.asarray(live.qd_matrix(q, docs))
        wv, wi = live.retrieve_topk(q, 5, _score_fn(live, spec, params))
        old_nnz = live.nnz

        live.compact()

        assert live.generation == 1
        assert live.delta_nnz == 0
        # dead ROWS are dropped from the merged base, but the tombstone
        # mask persists: a dead id must keep scoring -inf (not as an
        # empty doc), or the swap would not be bitwise-invisible
        assert live.tombstones == 3
        assert live.nnz < old_nnz           # dead rows actually dropped
        # q8 is never re-quantised: the merged base carries dequantised
        # f32 and serves as lossless 'packed'
        assert live.codec == ("none" if codec == "none" else "packed")
        np.testing.assert_allclose(np.asarray(live.qd_matrix(q, docs)),
                                   want_qd, rtol=0, atol=0)
        sv, si = live.retrieve_topk(q, 5, _score_fn(live, spec, params))
        np.testing.assert_array_equal(np.asarray(si), np.asarray(wi))
        np.testing.assert_allclose(np.asarray(sv), np.asarray(wv),
                                   rtol=0, atol=0)

    def test_insert_after_compact_matches_rebuild(self, seine_world,
                                                  full2):
        """gen-1 base + fresh delta still composes bitwise with a from-
        scratch rebuild (no deletes, so the rebuild is a legal oracle)."""
        w = seine_world
        (t0, s0), (t1, s1) = _halves(w)
        h2 = t1.shape[0] // 2
        base = w["builder"].build_partitioned(t0, s0, 2, batch_size=16)
        live = LiveIndex(base, w["builder"]._pipeline(), batch_size=16)
        live.insert(t1[:h2], s1[:h2])
        live.compact()
        assert live.generation == 1
        live.insert(t1[h2:], s1[h2:])
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(full2.n_docs, dtype=jnp.int32)
        np.testing.assert_allclose(np.asarray(live.qd_matrix(q, docs)),
                                   np.asarray(full2.qd_matrix(q, docs)),
                                   rtol=0, atol=0)

    def test_background_compact(self, seine_world):
        live = _mk_live(seine_world, 1)
        live.delete([2])
        t = live.compact(wait=False)
        assert isinstance(t, threading.Thread)
        live.wait_compaction()
        assert live.generation == 1
        assert live.delta_nnz == 0

    def test_ckpt_epoch_swap(self, seine_world, tmp_path):
        from repro.ckpt import load_index
        ckpt = str(tmp_path / "live_idx")
        live = _mk_live(seine_world, 2, ckpt_dir=ckpt)
        live.delete([3])
        live.compact()
        restored = load_index(ckpt)
        assert restored.n_docs == live.n_docs
        assert restored.nnz == live.base.nnz
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(live.n_docs, dtype=jnp.int32)
        np.testing.assert_allclose(
            np.asarray(restored.qd_matrix(q, docs)),
            np.asarray(live.base.qd_matrix(q, docs)), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# concurrency: no query may ever observe a torn generation
# ---------------------------------------------------------------------------
class TestConcurrency:
    def test_queries_bitwise_stable_during_compaction(self, seine_world):
        """Compaction is bitwise-invisible, so EVERY query issued while
        the merge + epoch swap runs must equal the quiescent answer —
        a torn view (new base with old delta, or vice versa) would
        double- or drop postings and fail the bitwise bar."""
        live = _mk_live(seine_world, 2)
        live.delete([1, 4])
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(live.n_docs, dtype=jnp.int32)
        want = np.asarray(live.qd_matrix(q, docs))
        errors = []
        stop = threading.Event()

        def hammer():
            try:
                while not stop.is_set():
                    got = np.asarray(live.qd_matrix(q, docs))
                    np.testing.assert_allclose(got, want, rtol=0, atol=0)
            except Exception as e:          # noqa: BLE001 - collected
                errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for _ in range(3):
                live.compact()
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors, errors[0]
        assert live.generation == 3

    def test_frontend_serves_through_compaction(self, seine_world):
        w = seine_world
        live = _mk_live(w, 2)
        live.delete([2])
        spec = get_retriever("deepimpact")
        params = spec.init(jax.random.key(0), live.n_b, live.functions)
        eng = SeineEngine(live, "deepimpact", params)
        rng = np.random.RandomState(5)
        reqs = []
        for q in w["queries"][:6]:
            docs = rng.randint(0, live.n_docs, size=8).astype(np.int32)
            reqs.append((np.asarray(q), docs))
        want = [np.asarray(eng.score(q, d)) for q, d in reqs]
        with ServingFrontend(eng, max_batch=4, batch_timeout_ms=2,
                             coalesce=True, cache_tiles=32) as fe:
            compactor = threading.Thread(target=live.compact)
            compactor.start()
            try:
                for _ in range(4):
                    futs = [fe.submit(q, d) for q, d in reqs]
                    for f, w_ in zip(futs, want):
                        np.testing.assert_allclose(f.result(timeout=120),
                                                   w_, rtol=0, atol=0)
            finally:
                compactor.join()
            assert live.generation == 1
            # post-swap: the rebound tile cache serves the same bits
            futs = [fe.submit(q, d) for q, d in reqs]
            for f, w_ in zip(futs, want):
                np.testing.assert_allclose(f.result(timeout=120), w_,
                                           rtol=0, atol=0)


# ---------------------------------------------------------------------------
# frontend: live ingest + explicit engine swap
# ---------------------------------------------------------------------------
class TestFrontendLive:
    def test_insert_visible_and_bitwise(self, seine_world, full2):
        w = seine_world
        (t0, s0), (t1, s1) = _halves(w)
        base = w["builder"].build_partitioned(t0, s0, 2, batch_size=16)
        live = LiveIndex(base, w["builder"]._pipeline(), batch_size=16)
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), live.n_b, live.functions)
        eng = SeineEngine(live, "knrm", params)
        oracle = SeineEngine(full2, "knrm", params)
        q = np.asarray(w["queries"][0])
        with ServingFrontend(eng, max_batch=4, batch_timeout_ms=2,
                             coalesce=True, cache_tiles=16) as fe:
            docs0 = np.arange(4, dtype=np.int32)
            got0 = fe.submit(q, docs0).result(timeout=120)
            np.testing.assert_allclose(got0,
                                       np.asarray(oracle.score(q, docs0)),
                                       rtol=0, atol=0)
            live.insert(t1, s1)             # mid-serving ingest
            docs1 = np.arange(full2.n_docs - 6, full2.n_docs,
                              dtype=np.int32)
            got1 = fe.submit(q, docs1).result(timeout=120)
            np.testing.assert_allclose(got1,
                                       np.asarray(oracle.score(q, docs1)),
                                       rtol=0, atol=0)

    def test_swap_engine(self, seine_world, live2, full2):
        w = seine_world
        spec = get_retriever("deepimpact")
        params = spec.init(jax.random.key(0), full2.n_b, full2.functions)
        eng_a = SeineEngine(live2, "deepimpact", params)
        eng_b = SeineEngine(full2, "deepimpact", params)
        q = np.asarray(w["queries"][1])
        docs = np.arange(8, dtype=np.int32)
        before = obs.REGISTRY.get("seine_frontend_epoch_swaps_total")
        before = before.get() if before is not None else 0.0
        with ServingFrontend(eng_a, max_batch=2, batch_timeout_ms=1,
                             coalesce=True, cache_tiles=8) as fe:
            fe.submit(q, docs).result(timeout=120)
            fe.swap_engine(eng_b)
            got = fe.submit(q, docs).result(timeout=120)
            np.testing.assert_allclose(got,
                                       np.asarray(eng_b.score(q, docs)),
                                       rtol=0, atol=0)
            assert fe.engine is eng_b
        after = obs.REGISTRY.get("seine_frontend_epoch_swaps_total").get()
        assert after >= before + 1


# ---------------------------------------------------------------------------
# Zipfian sub-sharded base: the hard shard geometry through the live view
# ---------------------------------------------------------------------------
class TestZipfianSubshard:
    def _views(self, idx, split=48):
        """Compose a LiveView (base = docs [0,split) sub-sharded at k=8,
        delta = docs [split,64)) from the rows-built Zipfian corpus."""
        p_full = partition_index(idx, 8)
        assert p_full.split_term is not None
        run = _explode_base(p_full, None)
        t, d, v = run.load()
        lo = d < split
        from repro.core.build_pipeline import PostingRun
        mk = PostingRun.from_arrays
        common = dict(idf=np.asarray(idx.idf),
                      doc_len=np.asarray(idx.doc_len),
                      seg_len=np.asarray(idx.seg_len),
                      n_docs=idx.n_docs, vocab_size=idx.vocab_size,
                      n_b=idx.n_b, functions=idx.functions)
        base = partitioned_from_runs(
            [mk(np.ascontiguousarray(t[lo]), np.ascontiguousarray(d[lo]),
                np.ascontiguousarray(v[lo]))], 8, **common)
        assert base.split_term is not None  # still sub-sharded
        delta = partitioned_from_runs(
            [mk(np.ascontiguousarray(t[~lo]), np.ascontiguousarray(d[~lo]),
                np.ascontiguousarray(v[~lo]))], 1, **common)
        view = LiveView(base=base, delta=delta, alive=None,
                        doc_len=jnp.asarray(idx.doc_len),
                        seg_len=jnp.asarray(idx.seg_len),
                        n_docs=idx.n_docs)
        return view, p_full

    def test_qd_bitwise(self, hot_term_index):
        view, p_full = self._views(hot_term_index)
        q = jnp.asarray([0, 1, 5, -1, 17], jnp.int32)
        docs = jnp.arange(hot_term_index.n_docs, dtype=jnp.int32)
        want = np.asarray(p_full.qd_matrix(q, docs))
        for impl in ("fused", "jnp"):
            np.testing.assert_allclose(
                np.asarray(view.qd_matrix(q, docs, impl=impl)), want,
                rtol=0, atol=0)

    def test_retrieve_and_tombstones(self, hot_term_index):
        view, p_full = self._views(hot_term_index)
        idx = hot_term_index
        q = jnp.asarray([0, 1, 5, -1, 17], jnp.int32)
        docs = jnp.arange(idx.n_docs, dtype=jnp.int32)
        dead = np.array([0, 47, 48, 63])    # both sides of the split
        alive = np.ones(idx.n_docs, bool)
        alive[dead] = False
        masked = dataclasses.replace(view, alive=jnp.asarray(alive))
        want = np.asarray(p_full.qd_matrix(q, docs)).copy()
        want[dead] = 0.0
        np.testing.assert_allclose(np.asarray(masked.qd_matrix(q, docs)),
                                   want, rtol=0, atol=0)
        spec, params = _retriever("deepimpact", view)
        meta = make_qmeta(view, q, docs)
        scores = np.asarray(spec.score(params, view.qd_matrix(q, docs),
                                       meta, view.functions)).copy()
        scores[dead] = -np.inf
        order = np.argsort(-scores, kind="stable")

        def fn(m, docs_):
            meta_ = make_qmeta(view, q, docs_.clip(0, idx.n_docs - 1))
            return spec.score(params, m, meta_, view.functions)

        sv, si = masked.retrieve_topk(q, 8, fn)
        np.testing.assert_array_equal(np.asarray(si), order[:8])
        np.testing.assert_allclose(np.asarray(sv), scores[order[:8]],
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# found_counts + API edges
# ---------------------------------------------------------------------------
class TestFoundCountsAndEdges:
    def test_found_counts(self, seine_world, live2, full2):
        w = seine_world
        run = _explode_base(full2, None)
        t_all, d_all, _ = run.load()
        present = set(zip(t_all.tolist(), d_all.tolist()))
        rng = np.random.RandomState(7)
        qt = rng.randint(-1, w["vocab"].size, size=6).astype(np.int32)
        docs = rng.randint(0, full2.n_docs, size=9).astype(np.int32)
        found, valid = found_counts(live2.view, jnp.asarray(qt),
                                    jnp.asarray(docs))
        want_valid = int((qt >= 0).sum()) * len(docs)
        want_found = sum((int(t), int(d)) in present
                         for t in qt[qt >= 0] for d in docs)
        assert int(valid) == want_valid
        assert int(found) == want_found

    def test_found_counts_drop_on_delete(self, seine_world):
        live = _mk_live(seine_world, 1)
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(live.n_docs, dtype=jnp.int32)
        f0, v0 = found_counts(live.view, q, docs)
        live.delete(np.arange(live.n_docs // 2))
        f1, v1 = found_counts(live.view, q, docs)
        assert int(v1) == int(v0)
        assert int(f1) < int(f0)

    def test_live_index_convenience(self, seine_world, full2):
        w = seine_world
        live = live_index(w["builder"], w["toks"], w["segs"], k=2,
                          batch_size=16)
        assert live.generation == 0 and live.delta_nnz == 0
        q = jnp.asarray(QUERY, jnp.int32)
        docs = jnp.arange(full2.n_docs, dtype=jnp.int32)
        np.testing.assert_allclose(np.asarray(live.qd_matrix(q, docs)),
                                   np.asarray(full2.qd_matrix(q, docs)),
                                   rtol=0, atol=0)

    def test_metrics_exported(self, seine_world):
        live = _mk_live(seine_world, 1)
        live.delete([0])
        live.compact()
        for name in ("seine_live_docs", "seine_live_delta_nnz",
                     "seine_live_tombstones", "seine_live_generation"):
            assert obs.REGISTRY.get(name) is not None, name
        assert obs.REGISTRY.get("seine_live_ingest_docs_total").get() > 0
        assert obs.REGISTRY.get("seine_live_deletes_total").get() >= 1
        assert obs.REGISTRY.get("seine_live_compactions_total").get() >= 1
