"""Oracle-parity harness for the term-partitioned index.

The single-CSR SegmentInvertedIndex is the oracle: for every lookup path —
raw qd_matrix rows, retriever scores through the engine, mesh-placed
engines — the K-shard PartitionedIndex must reproduce it EXACTLY
(``rtol=0, atol=0``; partial-row merge is x + 0 + ... + 0).  The sweep
covers K in {1, 2, 4} x the four indexed retrievers of ISSUE 2, plus the
adversarial id space: absent pairs, OOV terms (-1), terms past the vocab,
out-of-range and negative doc ids.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prophelpers import sweep
from repro.core.index import PairLookupIndex
from repro.dist.sharding import (partition_index, partitioned_index_shardings,
                                 plan_posting_ranges, plan_term_ranges)
from repro.launch.mesh import make_host_mesh
from repro.retrievers import get_retriever
from repro.serving import SeineEngine, ServeStats, serve_batches

K_SWEEP = (1, 2, 4)
RETRIEVERS = ("knrm", "deeptilebars", "hint", "deepimpact")


def _adversarial_queries(w, rng, n=4):
    """Query-term batches mixing present, absent, padded and OOV ids."""
    idx = w["index"]
    toks = w["toks"]
    qs = []
    for _ in range(n):
        d = rng.randint(0, len(w["ds"].docs))
        present = np.unique(toks[d][toks[d] >= 0])
        absent = np.setdiff1d(np.arange(idx.vocab_size),
                              np.unique(toks))[:2]
        q = np.full(8, -1, np.int32)
        sel = rng.choice(present, size=min(3, present.size), replace=False)
        q[:sel.size] = sel
        q[4:4 + absent.size] = absent
        q[6] = idx.vocab_size + rng.randint(1, 10)    # past the vocab
        q[7] = 0                                      # first term edge
        qs.append(q)
    return qs


def _adversarial_docs(idx, rng):
    """Candidate ids mixing real, boundary, out-of-range and negative."""
    return np.array([0, idx.n_docs - 1,
                     rng.randint(0, idx.n_docs),
                     idx.n_docs,                       # one past the end
                     idx.n_docs + rng.randint(1, 50),  # far out of range
                     -3],                              # negative
                    np.int32)


class TestPlanTermRanges:
    def test_balanced_by_nnz(self, seine_world):
        idx = seine_world["index"]
        offs = np.asarray(idx.term_offsets, np.int64)
        max_list = int(np.diff(offs).max())
        for k in (1, 2, 4, 7, 16):
            bounds = plan_term_ranges(offs, k)
            assert bounds[0] == 0 and bounds[-1] == idx.vocab_size
            assert (np.diff(bounds) >= 0).all()
            per_shard = offs[bounds[1:]] - offs[bounds[:-1]]
            assert per_shard.sum() == idx.nnz
            # balanced by nnz: no shard exceeds the even split by more than
            # one posting list (cuts are quantiles of the nnz cumsum)
            assert per_shard.max() <= idx.nnz // k + max_list

    def test_rejects_bad_k(self, seine_world):
        with pytest.raises(ValueError):
            plan_term_ranges(np.asarray(seine_world["index"].term_offsets), 0)

    def test_more_shards_than_terms(self):
        # 3 populated terms, 8 shards -> degenerate empty ranges are legal
        offs = np.array([0, 2, 2, 5], np.int64)
        bounds = plan_term_ranges(offs, 8)
        assert len(bounds) == 9
        assert (np.diff(bounds) >= 0).all()
        assert bounds[-1] == 3


class TestPlanPostingRanges:
    def test_no_hot_terms_matches_term_plan(self, seine_world):
        """Without a list exceeding the even split, the posting planner
        must reproduce plan_term_ranges exactly (zero ranks) — the legacy
        plan, repair and shard layout stay bit-identical."""
        offs = np.asarray(seine_world["index"].term_offsets, np.int64)
        for k in (1, 2, 4):
            bounds, ranks = plan_posting_ranges(offs, k)
            assert not ranks.any()
            np.testing.assert_array_equal(bounds, plan_term_ranges(offs, k))

    def test_hot_term_cut_mid_list(self, hot_term_index):
        """A dominating list takes mid-list cuts at the exact quantile
        targets; resulting posting ranges are balanced to ceil(nnz/k)."""
        offs = np.asarray(hot_term_index.term_offsets, np.int64)
        k = 8
        bounds, ranks = plan_posting_ranges(offs, k)
        assert ranks.any(), "hot corpus must produce mid-list cuts"
        pos = offs[bounds] + ranks
        assert pos[0] == 0 and pos[-1] == offs[-1]
        assert (np.diff(pos) > 0).all(), "no zero-nnz shards"
        assert int(np.diff(pos).max()) <= -(-int(offs[-1]) // k) + 1

    def test_rejects_bad_k(self, hot_term_index):
        with pytest.raises(ValueError):
            plan_posting_ranges(
                np.asarray(hot_term_index.term_offsets, np.int64), 0)


class TestDocRangeSubShards:
    """Structural invariants of a sub-sharded PartitionedIndex."""

    def test_split_tables_consistent(self, hot_term_index):
        idx = hot_term_index
        p = partition_index(idx, 8)
        st = np.asarray(p.split_term)
        sd = np.asarray(p.split_doc)
        lo = np.asarray(p.range_lo)
        hi = np.asarray(p.range_hi)
        t2s = np.asarray(p.term_to_shard)
        assert st[0] == -1                    # shard 0 never continues
        for k in np.flatnonzero(st >= 0):
            w = int(st[k])
            # a continued term starts the shard's local range and also
            # ends the previous shard's
            assert lo[k] == w and hi[k - 1] == w
            # the routing table points at the FIRST owner
            assert t2s[w] < k
            # split docs ascend along a term's consecutive sub-shards
            if st[k - 1] == w:
                assert sd[k - 1] < sd[k]
        # every shard's range is non-empty and ranges cover the vocab
        assert (hi >= lo).all()
        assert lo[0] == 0 and hi[-1] == idx.vocab_size - 1

    def test_per_device_bytes_shrink_on_hot_corpus(self, hot_term_index):
        """THE byte claim sub-sharding restores: with the hot list split,
        per-device bytes keep falling ~1/K instead of pinning at the hot
        list's padded width."""
        idx = hot_term_index
        with pytest.warns(UserWarning, match="skewed posting lists"):
            nosplit = partition_index(idx, 8, split_hot=False)
        split = partition_index(idx, 8)
        assert split.doc_ids.shape[1] < nosplit.doc_ids.shape[1]
        assert split.per_device_nbytes < nosplit.per_device_nbytes

    def test_lookup_pairs_batched_shapes_sub_sharded(self, hot_term_index):
        idx = hot_term_index
        p = partition_index(idx, 8)
        rng = np.random.RandomState(0)
        terms = jnp.asarray(
            rng.randint(-1, idx.vocab_size, (3, 5)).astype(np.int32))
        docs = jnp.asarray(rng.randint(0, idx.n_docs, (3,)).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(p.lookup_pairs(terms, docs)),
            np.asarray(idx.lookup_pairs(terms, docs)))

    def test_jnp_partial_sum_exact_sub_sharded(self, hot_term_index):
        """The SPMD partial-sum expression with range-based ownership:
        each sub-shard of a term owns a disjoint doc slice, so the
        summation merge stays x + 0 + ... + 0 (bitwise)."""
        idx = hot_term_index
        p = partition_index(idx, 8)
        q = jnp.asarray(np.array([0, 1, 17, -1, 45], np.int32))
        docs = jnp.asarray(np.arange(0, idx.n_docs + 4, 3, dtype=np.int32))
        np.testing.assert_array_equal(
            np.asarray(p.qd_matrix(q, docs, impl="jnp")),
            np.asarray(idx.qd_matrix(q, docs, impl="jnp")))

    def test_mesh_placed_sub_sharded_engine_matches(self, hot_term_index):
        from repro.launch.mesh import make_host_mesh
        idx = hot_term_index
        mesh = make_host_mesh(data=len(jax.devices()))
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), idx.n_b, idx.functions)
        plain = SeineEngine(idx, "knrm", params)
        part = SeineEngine(idx, "knrm", params, mesh=mesh,
                           partition="term", n_shards=8)
        assert part.index.split_term is not None
        q = jnp.asarray(np.array([0, 3, 11, -1], np.int32))
        docs = jnp.arange(32)
        np.testing.assert_allclose(np.asarray(part.score(q, docs)),
                                   np.asarray(plain.score(q, docs)),
                                   rtol=0, atol=0)

    def test_ckpt_round_trip_sub_sharded(self, hot_term_index, tmp_path):
        """save_index/load_index carry the split tables and rebuild
        fences: the restored index serves bitwise-identically."""
        from repro.ckpt import load_index, save_index
        idx = hot_term_index
        p = partition_index(idx, 8)
        d = save_index(str(tmp_path / "idx"), p)
        r = load_index(d)
        for name in ("term_offsets", "doc_ids", "values", "term_to_shard",
                     "range_lo", "range_hi", "split_term", "split_doc"):
            np.testing.assert_array_equal(
                np.asarray(getattr(r, name)), np.asarray(getattr(p, name)),
                err_msg=name)
        q = jnp.asarray(np.array([0, 1, 17, -1], np.int32))
        docs = jnp.asarray(np.arange(0, idx.n_docs, 5, dtype=np.int32))
        np.testing.assert_array_equal(np.asarray(r.qd_matrix(q, docs)),
                                      np.asarray(p.qd_matrix(q, docs)))


class TestPartitionStructure:
    def test_shards_cover_index_exactly(self, seine_world):
        idx = seine_world["index"]
        for k in K_SWEEP:
            p = partition_index(idx, k)
            assert isinstance(p, PairLookupIndex)
            assert p.n_shards == k and p.nnz == idx.nnz
            assert p.term_to_shard.shape == (idx.vocab_size,)
            # routing is contiguous non-decreasing: term ranges
            t2s = np.asarray(p.term_to_shard)
            assert (np.diff(t2s) >= 0).all()
            # every shard's local CSR is internally consistent
            offs = np.asarray(p.term_offsets)
            assert (offs[:, 0] == 0).all()
            assert (np.diff(offs, axis=1) >= 0).all()
            assert offs[:, -1].sum() == idx.nnz

    def test_per_device_bytes_shrink(self, seine_world):
        """The scaling claim: per-device bytes fall ~1/K (replicated
        routing table + doc stats are the only leftovers)."""
        idx = seine_world["index"]
        base = partition_index(idx, 1).per_device_nbytes
        for k in (2, 4):
            per_dev = partition_index(idx, k).per_device_nbytes
            assert per_dev < base / k + base / 8, \
                f"K={k}: {per_dev} bytes/device vs K=1 {base}"

    def test_no_global_skeleton_on_a_shard(self, seine_world):
        """Each stacked shard slice must hold ~nnz/K postings, not nnz."""
        idx = seine_world["index"]
        p = partition_index(idx, 4)
        assert p.doc_ids.shape[1] < idx.nnz // 2

    def test_hot_term_sub_sharded_and_exact(self, hot_term_index):
        """A Zipfian hot posting list is now SPLIT by doc range: no skew
        warning, padded width tracks the even split, and lookups stay
        exact — the ~1/K byte claim survives stopword-heavy corpora."""
        import warnings
        idx = hot_term_index
        with warnings.catch_warnings():
            warnings.simplefilter("error")       # skew must NOT warn
            p = partition_index(idx, 8)
        assert p.split_term is not None and p.split_doc is not None
        assert (np.asarray(p.split_term) >= 0).any()
        ideal = -(-idx.nnz // 8)
        assert p.doc_ids.shape[1] <= 2 * ideal
        assert p.nnz == idx.nnz
        q = jnp.asarray(np.array([0, 1, 17, -1], np.int32))
        docs = jnp.asarray(np.arange(0, idx.n_docs, 7, dtype=np.int32))
        np.testing.assert_array_equal(np.asarray(p.qd_matrix(q, docs)),
                                      np.asarray(idx.qd_matrix(q, docs)))

    @pytest.mark.parametrize("dtype", [np.float32, np.int8])
    def test_device_values_in_chunks_is_exact(self, dtype):
        """Index-sized values move to the device, and back, a chunk of
        postings at a time (the last chunk overlapping); the array that
        lands is the source array, bit for bit."""
        from repro.dist.partition import device_values, host_values
        rng = np.random.RandomState(0)
        host = (rng.randn(3, 1001, 4, 3) * 50).astype(dtype)
        chunk = host[:, :128].nbytes
        got = device_values(host, chunk_bytes=chunk)
        assert got.dtype == host.dtype
        np.testing.assert_array_equal(np.asarray(got), host)
        back = host_values(got, chunk_bytes=chunk)
        assert back.dtype == host.dtype
        np.testing.assert_array_equal(back, host)

    def test_hot_term_skew_warns_without_split(self, hot_term_index):
        """split_hot=False restores the old term-aligned-only plan: the
        unsplittable hot list pads every shard up to it — warned — and
        lookups must STILL be exact."""
        import warnings
        idx = hot_term_index
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            p = partition_index(idx, 8, split_hot=False)
        assert any("skewed posting lists" in str(w.message) for w in caught)
        assert p.split_term is None
        q = jnp.asarray(np.array([0, 1, 17, -1], np.int32))
        docs = jnp.asarray(np.arange(0, idx.n_docs, 7, dtype=np.int32))
        np.testing.assert_array_equal(np.asarray(p.qd_matrix(q, docs)),
                                      np.asarray(idx.qd_matrix(q, docs)))


class TestOracleParity:
    def test_qd_matrix_bitwise(self, seine_world):
        """THE invariant: partitioned lookup == single-CSR lookup, bitwise,
        for every id (present / absent / OOV / out-of-range)."""
        w = seine_world
        idx = w["index"]

        @sweep(K_SWEEP, n_seeds=3)
        def prop(k, seed):
            rng = np.random.RandomState(seed)
            p = partition_index(idx, k)
            docs = jnp.asarray(_adversarial_docs(idx, rng))
            for q in _adversarial_queries(w, rng):
                oracle = np.asarray(idx.qd_matrix(jnp.asarray(q), docs))
                got = np.asarray(p.qd_matrix(jnp.asarray(q), docs))
                np.testing.assert_array_equal(got, oracle)

        prop()

    @pytest.mark.parametrize("impl,k,codec", [
        (impl, k, codec) for impl in ("fused", "interpret", "jnp")
        for k in (0, 1, 4) for codec in ("none", "packed", "packed-q8")
        if codec == "none" or (k and impl != "jnp")])
    def test_qd_matrix_cols_bitwise(self, seine_world, impl, k, codec):
        """``qd_matrix(..., cols=c)`` == ``qd_matrix(...)[..., c]`` bit for
        bit on every lookup path (K = 0: the single-CSR index), and
        ``lookup_pairs`` likewise where the path has one."""
        w = seine_world
        idx = w["index"]
        p = idx if k == 0 else partition_index(idx, k, codec=codec)
        rng = np.random.RandomState(k)
        docs = jnp.asarray(_adversarial_docs(idx, rng))
        q = jnp.asarray(_adversarial_queries(w, rng, n=1)[0])
        terms = jnp.asarray(
            rng.randint(-1, idx.vocab_size, (3, 5)).astype(np.int32))
        pair_docs = jnp.asarray(
            rng.randint(0, idx.n_docs, (3,)).astype(np.int32))
        full = np.asarray(p.qd_matrix(q, docs, impl=impl))
        pairs = {} if k == 0 else {"impl": impl}
        full_pairs = (None if impl == "interpret" else
                      np.asarray(p.lookup_pairs(terms, pair_docs, **pairs)))
        for cols in ((3,), (0, 1, 4), (0, 1, 2, 3)):
            np.testing.assert_array_equal(
                np.asarray(p.qd_matrix(q, docs, impl=impl, cols=cols)),
                full[..., list(cols)], err_msg=f"qd_matrix cols={cols}")
            if full_pairs is not None:
                np.testing.assert_array_equal(
                    np.asarray(p.lookup_pairs(terms, pair_docs, cols=cols,
                                              **pairs)),
                    full_pairs[..., list(cols)],
                    err_msg=f"lookup_pairs cols={cols}")

    def test_lookup_pairs_batched_shapes(self, seine_world):
        """lookup_pairs parity holds under extra batch dims too."""
        idx = seine_world["index"]
        p = partition_index(idx, 4)
        rng = np.random.RandomState(0)
        terms = jnp.asarray(
            rng.randint(-1, idx.vocab_size, (3, 5)).astype(np.int32))
        docs = jnp.asarray(rng.randint(0, idx.n_docs, (3,)).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(p.lookup_pairs(terms, docs)),
            np.asarray(idx.lookup_pairs(terms, docs)))

    def test_engine_scores_all_retrievers(self, seine_world):
        """Engine-level parity: SeineEngine(partition='term') reproduces
        the plain engine's scores for every indexed retriever x K."""
        w = seine_world
        idx = w["index"]
        docs = jnp.arange(16)
        for retriever in RETRIEVERS:
            spec = get_retriever(retriever)
            params = spec.init(jax.random.key(0), idx.n_b, idx.functions)
            oracle = SeineEngine(idx, retriever, params)
            ref = {int(i): np.asarray(oracle.score(jnp.asarray(q), docs))
                   for i, q in enumerate(w["queries"][:3])}
            for k in K_SWEEP:
                eng = SeineEngine(idx, retriever, params,
                                  partition="term", n_shards=k)
                assert eng.index.n_shards == k
                for i, q in enumerate(w["queries"][:3]):
                    got = np.asarray(eng.score(jnp.asarray(q), docs))
                    np.testing.assert_allclose(
                        got, ref[int(i)], rtol=0, atol=0,
                        err_msg=f"{retriever} K={k} query {i}")

    def test_mesh_placed_engine_matches(self, seine_world):
        """partition='term' through a live mesh placement stays exact."""
        w = seine_world
        idx = w["index"]
        mesh = make_host_mesh(data=len(jax.devices()))
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), idx.n_b, idx.functions)
        plain = SeineEngine(idx, "knrm", params)
        part = SeineEngine(idx, "knrm", params, mesh=mesh,
                           partition="term", n_shards=2)
        q = jnp.asarray(w["queries"][0])
        docs = jnp.arange(32)
        np.testing.assert_allclose(np.asarray(part.score(q, docs)),
                                   np.asarray(plain.score(q, docs)),
                                   rtol=0, atol=0)

    def test_placement_specs(self, seine_world):
        """Stacked shard arrays split on their leading K axis; routing
        table and per-doc stats replicate."""
        from jax.sharding import PartitionSpec as P
        idx = seine_world["index"]
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        p = partition_index(idx, 1, mesh=mesh)
        sh = partitioned_index_shardings(mesh, p)
        assert sh.values.spec == P("model")
        assert sh.doc_ids.spec == P("model")
        assert sh.term_to_shard.spec == P()
        assert sh.doc_len.spec == P()
        for f in dataclasses.fields(p):
            v = getattr(p, f.name)
            if hasattr(v, "sharding"):
                assert v.sharding == getattr(sh, f.name)


class TestEngineInit:
    def test_meshless_engine_has_empty_data_axes(self, seine_world):
        """Regression: _data_axes was only assigned under ``mesh is not
        None`` while _place reads it unconditionally — a mesh-less engine
        must carry the empty default instead of a latent AttributeError."""
        w = seine_world
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), w["index"].n_b,
                           w["index"].functions)
        eng = SeineEngine(w["index"], "knrm", params)
        assert eng._data_axes == ()
        assert eng._lookup_impl == "fused"
        mesh = make_host_mesh(data=len(jax.devices()))
        meng = SeineEngine(w["index"], "knrm", params, mesh=mesh)
        assert meng._data_axes != () and meng._lookup_impl == "jnp"

    @pytest.mark.parametrize("k", [1, 4])
    def test_programs_take_the_index_as_an_argument(self, seine_world, k):
        """jit embeds closed-over arrays as literal constants: a scoring
        program that closed over the index would carry all of it in its
        text (at MQ2007 scale ~10 GB).  The engine passes it in instead,
        so the program text stays far smaller than the values."""
        w = seine_world
        pidx = partition_index(w["index"], k)
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), pidx.n_b, pidx.functions)
        eng = SeineEngine(pidx, "knrm", params)
        q = jnp.asarray(np.arange(8, dtype=np.int32))
        d = jnp.asarray(np.arange(16, dtype=np.int32))
        text = eng._score.lower(params, eng.index, q, d).as_text()
        assert len(text) < pidx.values.nbytes / 4
        np.testing.assert_array_equal(np.asarray(eng.score(q, d)),
                                      np.asarray(eng.score(q, d)))


class TestServeStatsPercentiles:
    def test_percentiles_and_mean(self):
        stats = ServeStats()
        for ms in [1.0, 2.0, 3.0, 4.0, 100.0]:
            stats.record(ms)
        assert stats.n_requests == 5
        assert stats.ms_per_request == pytest.approx(22.0)
        assert stats.p50_ms == pytest.approx(3.0)
        # tail visible: p95 near the straggler, far above the mean
        assert stats.p95_ms > 80.0
        assert stats.percentile_ms(0.0) == pytest.approx(1.0)

    def test_empty_stats_are_zero(self):
        stats = ServeStats()
        assert stats.ms_per_request == 0.0
        assert stats.p50_ms == 0.0 and stats.p95_ms == 0.0

    def test_window_bounds_memory_but_totals_stay_exact(self):
        stats = ServeStats(window=10)
        for ms in range(100):
            stats.record(float(ms))
        assert len(stats.latencies_ms) == 10          # bounded
        assert stats.n_requests == 100                # exact running count
        assert stats.total_ms == pytest.approx(sum(range(100)))
        assert stats.p50_ms == pytest.approx(94.5)    # recent-window quantile

    def test_serve_batches_records_latencies(self, seine_world):
        w = seine_world
        spec = get_retriever("knrm")
        params = spec.init(jax.random.key(0), w["index"].n_b,
                           w["index"].functions)
        eng = SeineEngine(w["index"], "knrm", params,
                          partition="term", n_shards=2)
        reqs = [(w["queries"][i % len(w["queries"])], np.arange(8))
                for i in range(5)]
        _, stats = serve_batches(eng, reqs)
        assert len(stats.latencies_ms) == stats.n_requests == 5
        assert stats.total_ms == pytest.approx(sum(stats.latencies_ms))
        assert stats.p50_ms <= stats.p95_ms <= max(stats.latencies_ms)
