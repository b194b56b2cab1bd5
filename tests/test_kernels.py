"""Per-kernel allclose sweeps vs the pure-jnp oracles (deliverable c).

Pallas kernels run in interpret mode on CPU (the container has no TPU);
shapes/dtypes swept per kernel, asserting against ref.py.  csr_lookup is
the exception twice over: it is the *serving* hot path, so its sweep is
held to rtol=0/atol=0 against ``csr_lookup_positions`` (the single-CSR
oracle of record), and its CPU lowering is the routed-jnp ref rather
than the interpreter (ops.py) — both lowerings are swept here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (csr_lookup, embed_bag, embed_bag_ref,
                           flash_attention, flash_attn_ref, knrm_pool,
                           knrm_pool_ref, seg_interact, seg_interact_ref)
from repro.retrievers import all_retrievers, get_retriever


class TestSegInteract:
    @pytest.mark.parametrize("V,S,Ls,De", [
        (64, 4, 128, 32), (300, 7, 256, 128), (256, 3, 128, 64),
        (128, 2, 128, 200),   # De needs padding to 128-multiple
    ])
    def test_matches_oracle(self, V, S, Ls, De):
        k = jax.random.split(jax.random.key(V * S + De), 3)
        ev = jax.random.normal(k[0], (V, De))
        st = jax.random.normal(k[1], (S, Ls, De))
        lens = jax.random.randint(k[2], (S,), 0, Ls + 1)
        mask = (jnp.arange(Ls)[None] < lens[:, None]).astype(jnp.float32)
        out = seg_interact(ev, st, mask)
        ref = seg_interact_ref(ev, st * mask[..., None], mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_empty_segment_zeroes(self):
        ev = jax.random.normal(jax.random.key(0), (64, 32))
        st = jax.random.normal(jax.random.key(1), (2, 128, 32))
        mask = jnp.zeros((2, 128)).at[0, :10].set(1.0)
        out = np.asarray(seg_interact(ev, st, mask))
        assert (out[:, 1, :] == 0).all(), "empty segment must produce zeros"

    def test_bf16_inputs(self):
        ev = jax.random.normal(jax.random.key(0), (128, 64), jnp.bfloat16)
        st = jax.random.normal(jax.random.key(1), (3, 128, 64), jnp.bfloat16)
        mask = jnp.ones((3, 128), jnp.float32)
        out = seg_interact(ev, st, mask)
        ref = seg_interact_ref(ev, st, mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)

    def test_matches_index_builder_values(self, seine_world):
        """The kernel computes the same dot/cos/gauss the index stores."""
        w = seine_world
        idx = w["index"]
        table = np.asarray(w["provider"].table())
        d = 5
        toks, segs = w["toks"][d], w["segs"][d]
        n_b = idx.n_b
        Ls = 128
        seg_tokens = np.zeros((n_b, Ls, table.shape[1]), np.float32)
        mask = np.zeros((n_b, Ls), np.float32)
        for b in range(n_b):
            sel = toks[(segs == b) & (toks >= 0)][:Ls]
            seg_tokens[b, :sel.size] = table[sel]
            mask[b, :sel.size] = 1.0
        present = np.unique(toks[toks >= 0])[:8].astype(np.int32)
        out = np.asarray(seg_interact(jnp.asarray(table),
                                      jnp.asarray(seg_tokens),
                                      jnp.asarray(mask)))[present]
        m = np.asarray(idx.qd_matrix(jnp.asarray(present),
                                     jnp.asarray([d])))[0]
        for name, ki in (("dot", 0), ("cosine", 1), ("gauss_max", 2)):
            fi = idx.fn_index(name)
            np.testing.assert_allclose(out[..., ki], m[..., fi],
                                       rtol=1e-3, atol=1e-4,
                                       err_msg=f"{name} mismatch")


class TestCsrLookup:
    """Oracle-parity sweep for the fused serving lookup.

    The single-CSR legacy path (``csr_lookup_positions`` via
    ``qd_matrix(impl="jnp")``) is the oracle; every csr_lookup lowering —
    the routed-jnp CPU path AND the Pallas kernel in interpret mode —
    must reproduce it exactly (rtol=0/atol=0) across K in {1, 2, 4} and
    posting-tile widths {64, 256, 1024} (the kernel's two-level bisect),
    including OOV (-1) terms, past-vocab terms, absent pairs,
    out-of-range / negative doc ids, padded-tail candidate sets, and a
    Zipfian hot-term corpus whose dominant posting list is doc-range
    sub-sharded (per-pair routing).
    """
    K_SWEEP = (1, 2, 4)
    TILE_SWEEP = (64, 256, 1024)
    RETRIEVERS = ("knrm", "deeptilebars", "hint", "deepimpact")

    def _adversarial(self, w, seed, n_docs_tail=3):
        """(query (8,), docs (8,)) mixing every hostile id class; the
        candidate tail repeats docs[0] — the serve_batches pad pattern."""
        idx = w["index"]
        rng = np.random.RandomState(seed)
        toks = w["toks"]
        d = rng.randint(0, len(w["ds"].docs))
        present = np.unique(toks[d][toks[d] >= 0])
        absent = np.setdiff1d(np.arange(idx.vocab_size),
                              np.unique(toks))[:2]
        q = np.full(8, -1, np.int32)                  # OOV padding
        sel = rng.choice(present, size=min(3, present.size), replace=False)
        q[:sel.size] = sel
        q[4:4 + absent.size] = absent                 # absent pairs
        q[6] = idx.vocab_size + rng.randint(1, 10)    # past the vocab
        q[7] = 0                                      # first-term edge
        core = np.array([0, idx.n_docs - 1,
                         rng.randint(0, idx.n_docs),
                         idx.n_docs,                       # one past the end
                         idx.n_docs + rng.randint(1, 50),  # far out of range
                         -3], np.int32)                    # negative
        docs = np.concatenate(                             # padded tail
            [core, np.full(n_docs_tail, core[0], np.int32)])
        return jnp.asarray(q), jnp.asarray(docs)

    def test_ref_lowering_bitwise(self, seine_world):
        """CPU fused lowering == oracle for single-CSR and every K."""
        from repro.dist.sharding import partition_index
        idx = seine_world["index"]
        for seed in range(3):
            q, docs = self._adversarial(seine_world, seed)
            oracle = np.asarray(idx.qd_matrix(q, docs, impl="jnp"))
            np.testing.assert_array_equal(
                np.asarray(idx.qd_matrix(q, docs)), oracle)
            for k in self.K_SWEEP:
                p = partition_index(idx, k)
                np.testing.assert_array_equal(
                    np.asarray(p.qd_matrix(q, docs)), oracle,
                    err_msg=f"K={k} seed={seed} fused-ref")

    def test_interpret_kernel_bitwise(self, seine_world):
        """The Pallas kernel itself (interpret mode: scalar-prefetch
        routing, in-kernel bisect, dynamic values DMA) == oracle."""
        from repro.dist.sharding import partition_index
        idx = seine_world["index"]
        for seed in range(2):
            q, docs = self._adversarial(seine_world, seed)
            oracle = np.asarray(idx.qd_matrix(q, docs, impl="jnp"))
            np.testing.assert_array_equal(
                np.asarray(idx.qd_matrix(q, docs, impl="interpret")), oracle)
            for k in self.K_SWEEP:
                p = partition_index(idx, k)
                np.testing.assert_array_equal(
                    np.asarray(p.qd_matrix(q, docs, impl="interpret")),
                    oracle, err_msg=f"K={k} seed={seed} pallas-interpret")

    @pytest.mark.parametrize("tile", (64, 256, 1024))
    def test_tiled_kernel_bitwise_across_tile_widths(self, seine_world,
                                                     tile):
        """The two-level bisect is exact at EVERY tile width: the fence
        bisect plus the single DMA'd tile must reproduce the oracle for
        single-CSR and every K — tiles smaller, equal to and larger than
        the shard's posting span all take the same answer path."""
        from repro.dist.sharding import partition_index
        idx = seine_world["index"]
        q, docs = self._adversarial(seine_world, seed=0)
        oracle = np.asarray(idx.qd_matrix(q, docs, impl="jnp"))
        np.testing.assert_array_equal(
            np.asarray(idx.qd_matrix(q, docs, impl="interpret", tile=tile)),
            oracle, err_msg=f"single-CSR tile={tile}")
        for k in self.K_SWEEP:
            p = partition_index(idx, k)
            np.testing.assert_array_equal(
                np.asarray(p.qd_matrix(q, docs, impl="interpret",
                                       tile=tile)),
                oracle, err_msg=f"K={k} tile={tile}")

    def test_sub_sharded_hot_term_bitwise(self, hot_term_index):
        """Doc-range sub-sharding routes per PAIR (the owner depends on
        the candidate doc): both the routed-jnp lowering and the
        pair-routed interpret kernel must reproduce the single-CSR
        oracle across tile widths, including doc ids that straddle the
        sub-shard split boundaries."""
        from repro.dist.sharding import partition_index
        idx = hot_term_index
        p = partition_index(idx, 8)
        assert p.split_term is not None, "corpus must trigger sub-sharding"
        splits = np.asarray(p.split_doc)[np.asarray(p.split_term) >= 0]
        q = jnp.asarray(np.array([0, 1, 17, -1, idx.vocab_size + 3, 39],
                                 np.int32))
        docs = jnp.asarray(np.concatenate([
            splits, splits - 1,                  # straddle every boundary
            [0, idx.n_docs - 1, idx.n_docs, -3]]).astype(np.int32))
        oracle = np.asarray(idx.qd_matrix(q, docs, impl="jnp"))
        np.testing.assert_array_equal(
            np.asarray(p.qd_matrix(q, docs)), oracle, err_msg="fused-ref")
        for tile in self.TILE_SWEEP:
            np.testing.assert_array_equal(
                np.asarray(p.qd_matrix(q, docs, impl="interpret",
                                       tile=tile)),
                oracle, err_msg=f"pallas-interpret tile={tile}")

    def test_engine_sub_sharded_scores_all_retrievers(self, hot_term_index):
        """Engine-level: fused serving over a sub-sharded index — with a
        non-default lookup_tile — reproduces the single-CSR scores for
        every indexed retriever."""
        from repro.dist.sharding import partition_index
        from repro.retrievers import get_retriever
        from repro.serving import SeineEngine
        idx = hot_term_index
        docs = jnp.arange(16)
        q = jnp.asarray(np.array([0, 1, 5, 17, 23, -1], np.int32))
        for retriever in self.RETRIEVERS:
            spec = get_retriever(retriever)
            params = spec.init(jax.random.key(0), idx.n_b, idx.functions)
            oracle = SeineEngine(idx, retriever, params)
            oracle._lookup_impl = "jnp"
            ref = np.asarray(oracle.score(q, docs))
            eng = SeineEngine(partition_index(idx, 8), retriever, params,
                              lookup_tile=64)
            np.testing.assert_allclose(
                np.asarray(eng.score(q, docs)), ref, rtol=0, atol=0,
                err_msg=f"{retriever} sub-sharded")

    def test_raw_op_matches_lookup_positions(self, seine_world):
        """The op against csr_lookup_positions directly (not through
        qd_matrix), on an all-real id batch — positions, found mask and
        value rows all agree."""
        from repro.core.index import csr_lookup_positions
        idx = seine_world["index"]
        rng = np.random.RandomState(7)
        q = jnp.asarray(rng.randint(0, idx.vocab_size, 6).astype(np.int32))
        docs = jnp.asarray(rng.randint(0, idx.n_docs, 16).astype(np.int32))
        w = jnp.broadcast_to(q[None], (16, 6))
        d = jnp.broadcast_to(docs[:, None], (16, 6))
        pos, in_list = csr_lookup_positions(idx.term_offsets, idx.doc_ids,
                                            w, d)
        want = (idx.values.at[pos].get(mode="clip")
                * in_list[..., None, None])
        got = csr_lookup(idx.term_offsets[None], idx.doc_ids[None],
                         idx.values[None], None, None, q, docs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_engine_fused_scores_all_retrievers(self, seine_world):
        """Engine-level: the fused serving path reproduces the legacy
        lookup's scores exactly for every indexed retriever x K."""
        from repro.dist.sharding import partition_index
        from repro.retrievers import get_retriever
        from repro.serving import SeineEngine
        w = seine_world
        idx = w["index"]
        docs = jnp.arange(16)
        for retriever in self.RETRIEVERS:
            spec = get_retriever(retriever)
            params = spec.init(jax.random.key(0), idx.n_b, idx.functions)
            oracle = SeineEngine(idx, retriever, params)
            oracle._lookup_impl = "jnp"     # legacy lookup, same jit shape
            for i, qq in enumerate(w["queries"][:2]):
                q = jnp.asarray(qq)
                ref = np.asarray(oracle.score(q, docs))
                for k in self.K_SWEEP:
                    eng = SeineEngine(partition_index(idx, k), retriever,
                                      params)
                    assert eng._lookup_impl == "fused"
                    np.testing.assert_allclose(
                        np.asarray(eng.score(q, docs)), ref, rtol=0, atol=0,
                        err_msg=f"{retriever} K={k} query {i}")

    def test_unknown_impl_rejected(self, seine_world):
        """Typos must not silently select the fused path (and lookup_pairs
        has no interpreter lowering to fall back to)."""
        from repro.dist.sharding import partition_index
        idx = seine_world["index"]
        p = partition_index(idx, 2)
        q, docs = jnp.zeros(4, jnp.int32), jnp.arange(4)
        for fn in (idx.qd_matrix, p.qd_matrix):
            with pytest.raises(ValueError, match="unknown lookup impl"):
                fn(q, docs, impl="fussed")
        with pytest.raises(ValueError, match="unknown lookup impl"):
            p.lookup_pairs(q[None], docs[:1], impl="interpret")

    def test_bisect_depth_is_sufficient(self):
        """bit_length(N) bisect steps reach the 32-step fixed point for
        every width <= N (the depth cut the serving path relies on)."""
        from repro.core.index import _bisect
        from repro.kernels.csr_lookup.ref import bisect_steps
        rng = np.random.RandomState(0)
        for n in (1, 2, 3, 7, 64, 1000, 1 << 14):
            arr = jnp.asarray(np.sort(rng.randint(0, n, n)).astype(np.int32))
            t = jnp.asarray(rng.randint(-1, n + 1, 64).astype(np.int32))
            lo = jnp.zeros_like(t)
            hi = jnp.full_like(t, n)
            np.testing.assert_array_equal(
                np.asarray(_bisect(arr, lo, hi, t, bisect_steps(n))),
                np.asarray(_bisect(arr, lo, hi, t, 32)), err_msg=f"n={n}")


def _all_functions_spec(read):
    """A scorer over the ``read`` functions that declares every function
    of the index as needed (the case the narrowing leaves alone)."""
    from repro.core.interactions import FUNCTION_NAMES
    from repro.retrievers import RetrieverSpec, fidx

    def score(params, m, meta, functions):
        return sum(m[..., fidx(functions, f)].sum((1, 2)) for f in read)

    return RetrieverSpec(name="all_functions", init=lambda *a: {},
                         score=score, needs=FUNCTION_NAMES)


class TestFunctionColumns:
    """The served lookup gathers only the functions its ranker reads
    (``RetrieverSpec.needs``): every narrowed gather equals the full
    gather's columns, and every engine score the full M's, bit for bit."""

    @pytest.mark.parametrize("cols", ((3,), (0, 1, 4), (0, 1, 2, 3)))
    @pytest.mark.parametrize("n", (7, 5000))
    @pytest.mark.parametrize("dtype", (np.float32, np.int8))
    def test_gather_rows_cols_bitwise(self, dtype, n, cols):
        """One chunk (n = 7) and the chunk loop (n = 5,000: more than a
        chunk of full and of narrowed rows alike)."""
        from repro.kernels.csr_lookup.ref import GATHER_CHUNK, gather_rows
        rng = np.random.RandomState(n + len(cols))
        values = jnp.asarray((rng.randn(2, 300, 4, 9) * 50).astype(dtype))
        k = jnp.asarray(rng.randint(0, 2, n).astype(np.int32))
        pos = jnp.asarray(rng.randint(-5, 310, n).astype(np.int32))
        full = np.asarray(gather_rows(values, k, pos))
        got = np.asarray(gather_rows(values, k, pos, cols))
        assert got.shape == (n, 4, len(cols)) and got.dtype == dtype
        np.testing.assert_array_equal(got, full[..., list(cols)])
        loops = "scan" in str(jax.make_jaxpr(
            lambda v, a, b: gather_rows(v, a, b, cols))(values, k, pos))
        assert loops == (n > GATHER_CHUNK * 9 // len(cols))

    @pytest.mark.parametrize("retriever", all_retrievers())
    def test_engine_narrowed_scores_bitwise(self, seine_world, retriever):
        """``SeineEngine.score`` (M narrowed to ``needs``) == the spec's
        score over the full M with the full ``functions``, K in {1, 4}."""
        from repro.dist.sharding import partition_index
        from repro.serving import SeineEngine
        from repro.serving.engine import make_qmeta
        w = seine_world
        idx = w["index"]
        spec = get_retriever(retriever)
        params = spec.init(jax.random.key(0), idx.n_b, idx.functions)

        @jax.jit
        def full_m_score(params, p, q, d):
            return spec.score(params, p.qd_matrix(q, d),
                              make_qmeta(p, q, d), p.functions)

        docs = jnp.arange(16)
        for k in (1, 4):
            p = partition_index(idx, k)
            eng = SeineEngine(p, retriever, params)
            assert eng._functions == tuple(
                f for f in idx.functions if f in spec.needs)
            for i, qq in enumerate(w["queries"][:2]):
                q = jnp.asarray(qq)
                np.testing.assert_array_equal(
                    np.asarray(eng.score(q, docs)),
                    np.asarray(full_m_score(params, p, q, docs)),
                    err_msg=f"{retriever} K={k} query {i}")

    @pytest.mark.parametrize("retriever,gathered",
                             (("knrm", 1), ("deeptilebars", 3),
                              ("all_functions", 9)))
    def test_lookup_functions_gauge(self, seine_world, monkeypatch,
                                    retriever, gathered):
        """``seine_engine_lookup_functions`` reads the functions gathered
        per pair; a ranker that needs all nine gets the full lookup."""
        from repro import obs
        from repro.retrievers import base
        from repro.serving import SeineEngine
        monkeypatch.setitem(base._REGISTRY, "all_functions",
                            _all_functions_spec(("tf", "cosine")))
        idx = seine_world["index"]
        spec = get_retriever(retriever)
        params = spec.init(jax.random.key(0), idx.n_b, idx.functions)
        assert obs.enabled()
        eng = SeineEngine(idx, retriever, params)
        assert obs.gauge("seine_engine_lookup_functions").get() == gathered
        assert (eng._cols is None) == (gathered == len(idx.functions))
        q = jnp.asarray(seine_world["queries"][0])
        assert np.isfinite(np.asarray(eng.score(q, jnp.arange(8)))).all()

    def test_scorer_reading_outside_needs_raises(self, seine_world,
                                                 monkeypatch):
        """A scorer that reads a function missing from its ``needs``
        raises at trace time; it never reads zeros in its place."""
        from repro.retrievers import RetrieverSpec, base, fidx
        from repro.serving import SeineEngine

        def score(params, m, meta, functions):
            return m[..., fidx(functions, "tf")].sum((1, 2))

        monkeypatch.setitem(base._REGISTRY, "reads_tf", RetrieverSpec(
            name="reads_tf", init=lambda *a: {}, score=score,
            needs=("cosine",)))
        eng = SeineEngine(seine_world["index"], "reads_tf", {})
        with pytest.raises(ValueError, match="not in tuple"):
            eng.score(jnp.asarray(seine_world["queries"][0]),
                      jnp.arange(8))


class TestKnrmPool:
    @pytest.mark.parametrize("B,Q,nb", [(4, 8, 20), (2, 130, 5), (1, 6, 64)])
    def test_matches_oracle(self, B, Q, nb):
        k = jax.random.split(jax.random.key(B * Q + nb), 2)
        c = jax.random.uniform(k[0], (B, Q, nb), minval=-1, maxval=1)
        m = (jax.random.uniform(k[1], (B, nb)) > 0.3).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(knrm_pool(c, m)),
                                   np.asarray(knrm_pool_ref(c, m)),
                                   rtol=1e-5, atol=1e-6)

    def test_matches_retriever_features(self):
        from repro.retrievers.knrm import kernel_features
        c = jax.random.uniform(jax.random.key(0), (2, 6, 10),
                               minval=-1, maxval=1)
        m = jnp.ones((2, 10))
        a = knrm_pool(c, m)
        b = kernel_features(c, m[:, None, :])
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,Hq,Hkv,hd,bq,bk", [
        (2, 128, 4, 2, 32, 64, 64),
        (1, 256, 8, 8, 64, 128, 64),
        (2, 64, 4, 1, 16, 32, 32),
        (1, 96, 2, 2, 32, 32, 32),      # non-power-of-two seq
    ])
    def test_matches_oracle_causal(self, B, S, Hq, Hkv, hd, bq, bk):
        ks = jax.random.split(jax.random.key(S + Hq), 3)
        q = jax.random.normal(ks[0], (B, S, Hq, hd))
        k = jax.random.normal(ks[1], (B, S, Hkv, hd))
        v = jax.random.normal(ks[2], (B, S, Hkv, hd))
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        ref = flash_attn_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_noncausal(self):
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (1, 64, 4, 32))
        k = jax.random.normal(ks[1], (1, 64, 2, 32))
        v = jax.random.normal(ks[2], (1, 64, 2, 32))
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        ref = flash_attn_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_matches_model_attention(self):
        """kernel == models.layers.gqa_attention (the dry-run stand-in)."""
        from repro.models.layers import gqa_attention
        ks = jax.random.split(jax.random.key(7), 3)
        q = jax.random.normal(ks[0], (2, 64, 8, 32))
        k = jax.random.normal(ks[1], (2, 64, 2, 32))
        v = jax.random.normal(ks[2], (2, 64, 2, 32))
        a = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        b = gqa_attention(q, k, v, causal=True, chunk=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


class TestEmbedBag:
    @pytest.mark.parametrize("V,D,B,maxbag", [
        (100, 32, 8, 10), (50, 16, 4, 6), (200, 128, 16, 20), (30, 8, 5, 3),
    ])
    def test_matches_oracle(self, V, D, B, maxbag):
        rng = np.random.RandomState(V + B)
        lens = rng.randint(0, maxbag, B)
        nnz = max(int(lens.sum()), 1)
        offsets = np.concatenate([[0], np.cumsum(lens)])[:-1].astype(np.int32)
        idx = rng.randint(0, V, nnz).astype(np.int32)
        table = jax.random.normal(jax.random.key(0), (V, D))
        a = embed_bag(table, jnp.asarray(idx), jnp.asarray(offsets), n_bags=B)
        b = embed_bag_ref(table, jnp.asarray(idx), jnp.asarray(offsets),
                          n_bags=B)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    def test_empty_bags_zero(self):
        table = jax.random.normal(jax.random.key(0), (10, 4))
        idx = jnp.asarray([1, 2])
        offs = jnp.asarray([0, 2, 2])  # bags: [1,2], [], []
        out = np.asarray(embed_bag(table, idx, offs, n_bags=3))
        assert (out[1] == 0).all() and (out[2] == 0).all()
