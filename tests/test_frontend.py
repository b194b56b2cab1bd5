"""Async serving front end: coalescing/cache exactness + batching edges.

The oracle-parity sweeps hold coalesced (and cached) scores to
rtol=0/atol=0 against per-request ``engine.score`` — the same bitwise
bar every other lookup path in this repo clears — across retrievers,
shard counts and the sub-sharded Zipfian corpus.  The frontend tests
cover the batch-formation edges the ISSUE calls out: a lone request
must be served once the time budget lapses, deadline-expired requests
must be rejected (and counted) rather than served late, and a stale
cache tile must never survive an index swap.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.data.synth_corpus import build_zipfian_index
from repro.dist.sharding import partition_index
from repro.retrievers import get_retriever
from repro.serving import (CoalescingScorer, DeadlineExceeded,
                           PostingTileCache, SeineEngine, ServeStats,
                           ServingFrontend, plan_coalesced, run_open_loop)

K_SWEEP = (1, 2, 4)
RETRIEVERS = ("knrm", "deeptilebars", "hint", "deepimpact")


def _counter(name):
    m = obs.REGISTRY.get(name)
    return m.get() if m is not None else 0.0


def _engine(index, retriever="deepimpact"):
    spec = get_retriever(retriever)
    params = spec.init(jax.random.key(0), index.n_b, index.functions)
    return SeineEngine(index, retriever, params)


def _requests(index, n, seed=0, vocab=40):
    rng = np.random.RandomState(seed)
    reqs = []
    for r in range(n):
        q = rng.randint(0, vocab, size=4 + r % 3).astype(np.int32)
        if r % 3 == 1:
            q[1] = q[0]   # duplicated in-query term
            q[-1] = -1    # pad slot
        docs = rng.randint(0, index.n_docs, size=8).astype(np.int32)
        reqs.append((q, docs))
    return reqs


# ---------------------------------------------------------------------------
# host-side coalescing plan
# ---------------------------------------------------------------------------
class TestPlanCoalesced:
    def test_inverse_reconstructs_every_pair(self):
        reqs = [(np.array([3, 1, 3, -1], np.int32),
                 np.array([5, 2], np.int32)),
                (np.array([1, 7], np.int32),
                 np.array([2, 9, 5], np.int32))]
        terms, docs, inverses, n = plan_coalesced(reqs)
        assert n == len(set(zip(terms[:n].tolist(), docs[:n].tolist())))
        for (q, d), inv in zip(reqs, inverses):
            want = [(int(t), int(dd)) for dd in d for t in q]
            got = [(int(terms[i]), int(docs[i])) for i in inv]
            assert got == want

    def test_duplicate_terms_collapse(self):
        q = np.array([4, 4, 4], np.int32)
        d = np.array([1, 2], np.int32)
        _, _, inverses, n = plan_coalesced([(q, d)])
        assert n == 2                      # 2 distinct (4, doc) pairs
        assert inverses[0].shape == (6,)   # but all 6 slots mapped

    def test_pad_rows_are_empty_terms_and_unreferenced(self):
        reqs = [(np.array([2], np.int32), np.array([0, 1, 2], np.int32))]
        terms, docs, inverses, n = plan_coalesced(reqs, pair_pad=8)
        assert terms.shape == (8,) and n == 3
        assert (terms[n:] == -1).all()
        assert inverses[0].max() < n

    def test_negative_doc_ids_key_sign_preservingly(self):
        reqs = [(np.array([1], np.int32), np.array([-3, 3], np.int32))]
        terms, docs, _, n = plan_coalesced(reqs)
        assert n == 2 and -3 in docs.tolist()

    def test_empty_request_list(self):
        terms, docs, inverses, n = plan_coalesced([])
        assert n == 0 and inverses == []


# ---------------------------------------------------------------------------
# coalesced scoring vs the uncoalesced oracle (bitwise)
# ---------------------------------------------------------------------------
class TestCoalescedOracleParity:
    @pytest.mark.parametrize("retriever", RETRIEVERS)
    @pytest.mark.parametrize("k", K_SWEEP)
    def test_bitwise_equal_across_retrievers_and_shards(
            self, hot_term_index, retriever, k):
        idx = hot_term_index
        eng = _engine(partition_index(idx, k), retriever)
        sc = CoalescingScorer(eng, pair_pad=16)
        reqs = _requests(idx, 5, seed=k)
        got = sc.score_batch(reqs)
        for (q, d), g in zip(reqs, got):
            want = eng.score(jnp.asarray(q), jnp.asarray(d))
            np.testing.assert_array_equal(np.asarray(g), np.asarray(want))

    def test_sub_sharded_zipfian_parity(self, hot_term_index):
        # K=8 on the one-hot-term corpus forces doc-range sub-shards:
        # routing is per (term, doc) pair, the hardest coalescing case
        p = partition_index(hot_term_index, 8)
        assert p.split_term is not None
        eng = _engine(p)
        sc = CoalescingScorer(eng, pair_pad=16)
        reqs = _requests(hot_term_index, 6, seed=3)
        for (q, d), g in zip(reqs, sc.score_batch(reqs)):
            want = eng.score(jnp.asarray(q), jnp.asarray(d))
            np.testing.assert_array_equal(np.asarray(g), np.asarray(want))

    def test_in_query_duplicates_route_once(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        sc = CoalescingScorer(eng, pair_pad=0)
        q = np.array([5, 5, 5, 5], np.int32)
        d = np.array([1, 2, 3], np.int32)
        before = _counter("seine_coalesce_distinct_pairs_total")
        (got,) = sc.score_batch([(q, d)])
        assert _counter("seine_coalesce_distinct_pairs_total") \
            - before == 3          # 3 distinct pairs, not 12 slots
        want = eng.score(jnp.asarray(q), jnp.asarray(d))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_rejects_meshed_engine(self):
        class FakeMeshed:
            mesh = object()
        with pytest.raises(ValueError, match="mesh-less"):
            CoalescingScorer(FakeMeshed())


# ---------------------------------------------------------------------------
# posting-tile cache
# ---------------------------------------------------------------------------
class TestPostingTileCache:
    @pytest.mark.parametrize("codec", ("none", "packed", "packed-q8"))
    def test_parity_and_second_pass_hits(self, hot_term_index, codec):
        pidx = partition_index(hot_term_index, 4, codec=codec)
        cache = PostingTileCache(pidx, budget_tiles=8)
        rng = np.random.RandomState(1)
        t = np.concatenate([np.array([0, 0, -1, 200, 3], np.int32),
                            rng.randint(-1, 45, size=60).astype(np.int32)])
        d = np.concatenate([np.array([0, 63, 2, 1, -3], np.int32),
                            rng.randint(-2, 70, size=60).astype(np.int32)])
        want = np.asarray(pidx.lookup_pairs(
            jnp.asarray(t)[:, None], jnp.asarray(d))[:, 0])
        np.testing.assert_array_equal(np.asarray(cache.lookup(t, d)), want)
        h0 = _counter("seine_tile_cache_hits_total")
        m0 = _counter("seine_tile_cache_misses_total")
        np.testing.assert_array_equal(np.asarray(cache.lookup(t, d)), want)
        assert _counter("seine_tile_cache_hits_total") > h0
        assert _counter("seine_tile_cache_misses_total") == m0

    def test_eviction_pressure_stays_exact(self):
        idx = build_zipfian_index(n_docs=512, vocab=64, n_hot=2,
                                  tail_decay=1.2, seed=5)
        pidx = partition_index(idx, 2, codec="packed", codec_tile=64)
        cache = PostingTileCache(pidx, budget_tiles=2)
        e0 = _counter("seine_tile_cache_evictions_total")
        rng = np.random.RandomState(2)
        for _ in range(4):
            t = rng.randint(0, 64, size=40).astype(np.int32)
            d = rng.randint(0, 512, size=40).astype(np.int32)
            want = np.asarray(pidx.lookup_pairs(
                jnp.asarray(t)[:, None], jnp.asarray(d))[:, 0])
            np.testing.assert_array_equal(
                np.asarray(cache.lookup(t, d)), want)
        assert _counter("seine_tile_cache_evictions_total") > e0

    def test_batch_working_set_over_budget_spills_exactly(self):
        idx = build_zipfian_index(n_docs=512, vocab=64, n_hot=2,
                                  tail_decay=1.2, seed=5)
        pidx = partition_index(idx, 4, codec="packed-q8", codec_tile=64)
        cache = PostingTileCache(pidx, budget_tiles=1)
        rng = np.random.RandomState(3)
        t = rng.randint(0, 64, size=120).astype(np.int32)
        d = rng.randint(0, 512, size=120).astype(np.int32)
        o0 = _counter("seine_tile_cache_overflow_pairs_total")
        want = np.asarray(pidx.lookup_pairs(
            jnp.asarray(t)[:, None], jnp.asarray(d))[:, 0])
        np.testing.assert_array_equal(np.asarray(cache.lookup(t, d)), want)
        assert _counter("seine_tile_cache_overflow_pairs_total") > o0

    def test_stale_tile_never_served_after_swap(self, hot_term_index):
        # same CSR structure, different values: a stale tile would
        # return OLD values bit-for-bit — the most dangerous staleness
        a = build_zipfian_index(seed=0)
        pa = partition_index(a, 2, codec="packed")
        t = np.arange(20, dtype=np.int32) % 5
        d = (np.arange(20, dtype=np.int32) * 3) % a.n_docs
        cache = PostingTileCache(pa, budget_tiles=8)
        got_a = np.asarray(cache.lookup(t, d))     # warm: tiles resident
        want_a = np.asarray(pa.lookup_pairs(
            jnp.asarray(t)[:, None], jnp.asarray(d))[:, 0])
        np.testing.assert_array_equal(got_a, want_a)
        bv = build_zipfian_index(seed=9)           # different values
        pb = partition_index(bv, 2, codec="packed")
        epoch = cache.epoch
        cache.swap_index(pb)
        assert cache.epoch == epoch + 1
        want_b = np.asarray(pb.lookup_pairs(
            jnp.asarray(t)[:, None], jnp.asarray(d))[:, 0])
        got_b = np.asarray(cache.lookup(t, d))
        np.testing.assert_array_equal(got_b, want_b)
        # the assertion has teeth only if the swapped values differ
        assert not np.array_equal(want_a, want_b)

    def test_rejects_bad_budget_and_plain_index(self, hot_term_index):
        pidx = partition_index(hot_term_index, 2)
        with pytest.raises(ValueError, match="budget"):
            PostingTileCache(pidx, budget_tiles=0)
        with pytest.raises(ValueError, match="PartitionedIndex"):
            PostingTileCache(hot_term_index, budget_tiles=4)


# ---------------------------------------------------------------------------
# async front end
# ---------------------------------------------------------------------------
class TestServingFrontend:
    def test_async_scores_bitwise_equal(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2, codec="packed"))
        reqs = _requests(hot_term_index, 10, seed=4)
        with ServingFrontend(eng, max_batch=4, batch_timeout_ms=5,
                             batch_pad=4, cache_tiles=8,
                             pair_pad=16) as fe:
            futs = [fe.submit(q, d) for q, d in reqs]
            for (q, d), f in zip(reqs, futs):
                want = eng.score(jnp.asarray(q), jnp.asarray(d))
                np.testing.assert_array_equal(f.result(timeout=120),
                                              np.asarray(want))
        assert fe.stats.n_requests == len(reqs)
        assert fe.stats.queue_ms_per_request >= 0.0

    def test_lone_request_served_after_timeout(self, hot_term_index):
        # batch-formation edge: max_batch never reached — the time
        # budget must close the batch, not strand the request
        eng = _engine(partition_index(hot_term_index, 2))
        with ServingFrontend(eng, max_batch=64,
                             batch_timeout_ms=10) as fe:
            q, d = _requests(hot_term_index, 1)[0]
            got = fe.submit(q, d).result(timeout=120)
            want = eng.score(jnp.asarray(q), jnp.asarray(d))
            np.testing.assert_array_equal(got, np.asarray(want))

    def test_batch_of_one(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        with ServingFrontend(eng, max_batch=1, batch_timeout_ms=0,
                             coalesce=False) as fe:
            q, d = _requests(hot_term_index, 1)[0]
            got = fe.submit(q, d).result(timeout=120)
            want = eng.score(jnp.asarray(q), jnp.asarray(d))
            np.testing.assert_array_equal(got, np.asarray(want))
        assert fe.stats.n_requests == 1

    def test_empty_queue_close_is_prompt(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        fe = ServingFrontend(eng, max_batch=8, batch_timeout_ms=50)
        time.sleep(0.05)         # worker is blocked on an empty queue
        t0 = time.perf_counter()
        fe.close()
        assert time.perf_counter() - t0 < 5.0
        assert fe.stats.n_requests == 0

    def test_deadline_expired_rejected_and_counted(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        m0 = _counter("seine_serve_slo_misses_total")
        # an SLO far below compile latency: requests queued behind the
        # first batch's compile age past it deterministically
        fe = ServingFrontend(eng, max_batch=1, batch_timeout_ms=0,
                             coalesce=False, slo_ms=0.001)
        reqs = _requests(hot_term_index, 6, seed=6)
        futs = [fe.submit(q, d) for q, d in reqs]
        outcomes = []
        for f in futs:
            try:
                f.result(timeout=120)
                outcomes.append("served")
            except DeadlineExceeded:
                outcomes.append("rejected")
        fe.close()
        n_rej = outcomes.count("rejected")
        assert n_rej >= 1
        assert _counter("seine_serve_slo_misses_total") - m0 == n_rej

    def test_empty_candidates_short_circuit(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        with ServingFrontend(eng, max_batch=2, batch_timeout_ms=1) as fe:
            got = fe.submit(np.array([1, 2], np.int32),
                            np.zeros(0, np.int32)).result(timeout=120)
        assert got.shape == (0,)

    def test_submit_after_close_raises(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        fe = ServingFrontend(eng)
        fe.close()
        with pytest.raises(RuntimeError, match="closed"):
            fe.submit(np.array([1], np.int32), np.array([0], np.int32))
        fe.close()   # idempotent

    def test_invalid_config_rejected(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        with pytest.raises(ValueError, match="max_batch"):
            ServingFrontend(eng, max_batch=0)
        with pytest.raises(ValueError, match="slo_ms"):
            ServingFrontend(eng, slo_ms=-1)
        with pytest.raises(ValueError, match="coalesce"):
            ServingFrontend(eng, coalesce=False, cache_tiles=4)

    def test_open_loop_accounting(self, hot_term_index):
        eng = _engine(partition_index(hot_term_index, 2))
        reqs = _requests(hot_term_index, 8, seed=7)
        fe = ServingFrontend(eng, max_batch=4, batch_timeout_ms=2,
                             slo_ms=60_000, pair_pad=16)
        res = run_open_loop(fe, reqs, target_qps=400, seed=1)
        fe.close()
        assert res.n_submitted == 8
        assert res.n_served + res.n_rejected == 8
        assert 0.0 <= res.goodput <= 1.0
        assert res.stats is fe.stats
        # timed from the due instant: never before the submission the
        # frontend's own record starts from, and lateness is reported
        assert res.late_ms.shape == (8,) and (res.late_ms >= 0).all()
        served = np.isfinite(res.latency_ms)
        assert served.sum() == res.n_served == res.stats.n_requests
        assert res.p50_ms >= res.stats.p50_ms
        assert res.p95_ms >= res.stats.p95_ms


# ---------------------------------------------------------------------------
# ServeStats thread safety + queue fields
# ---------------------------------------------------------------------------
class TestServeStatsConcurrency:
    def test_concurrent_recorders_and_readers(self):
        stats = ServeStats(window=1 << 12)
        n_threads, per = 8, 400
        stop = threading.Event()

        def write(k):
            for i in range(per):
                stats.record(float(i % 50), queue_ms=float(i % 7))

        def read():
            while not stop.is_set():
                stats.percentile_ms(95.0)
                _ = stats.queue_ms_per_request

        readers = [threading.Thread(target=read) for _ in range(2)]
        writers = [threading.Thread(target=write, args=(k,))
                   for k in range(n_threads)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join()
        stop.set()
        for t in readers:
            t.join()
        assert stats.n_requests == n_threads * per
        want_total = n_threads * sum(i % 50 for i in range(per))
        assert stats.total_ms == pytest.approx(want_total)
        want_queue = sum(i % 7 for i in range(per)) / per
        assert stats.queue_ms_per_request == pytest.approx(want_queue)
        # snapshot cache settled: quantiles over the final window work
        assert stats.percentile_ms(50.0) >= 0.0

    def test_queue_depth_high_water(self):
        stats = ServeStats()
        stats.note_queue_depth(3)
        stats.note_queue_depth(9)
        stats.note_queue_depth(1)
        assert stats.queue_depth == 1
        assert stats.max_queue_depth == 9
