"""The benchmark's vectorised corpus against the program's own generator
steps, and the traffic generator's stratified schedule."""
import numpy as np
import pytest

from bench import corpus as C
from bench import traffic

CFG = dict(n_docs=400, n_queries=24, n_topics=32, vocab_per_topic=300,
           n_background=2000, avg_doc_len=600, max_len=1408, n_segments=20,
           q_len=8, corpus_seed=7)


@pytest.fixture(scope="module")
def raw():
    return C._raw_corpus(CFG, np.random.default_rng(CFG["corpus_seed"]))


def test_vocabulary_equals_build_vocabulary(raw):
    from repro.core.vocab import build_vocabulary

    docs, _, _, _, _, n_raw, (r2s, idf) = raw
    v = build_vocabulary([d[d >= 0] for d in docs], n_raw)
    assert np.array_equal(r2s, v.raw_to_slot)
    assert np.array_equal(idf, v.idf)


def test_segmentation_equals_segment_corpus(raw):
    from repro.core.segment import segment_corpus

    docs, dlen, _, _, _, n_raw, (r2s, _) = raw
    slot = np.where(docs >= 0, r2s[np.maximum(docs, 0)], -1)
    toks, segs = segment_corpus([slot[i, :dlen[i]] for i in range(len(dlen))],
                                CFG["n_segments"], max_len=CFG["max_len"])
    assert np.array_equal(C.segment(slot, dlen, CFG["n_segments"]), segs)
    assert np.array_equal(np.where(docs >= 0, slot, -1), toks)


def test_shapes_follow_synth_corpus(raw):
    docs, dlen, doc_topics, queries, q_topics, n_raw, (r2s, _) = raw
    assert n_raw == 2000 + 32 * 300
    assert 500 < dlen.mean() < 700 and dlen.max() <= CFG["max_len"]
    assert np.allclose(doc_topics.sum(1), 1.0)
    assert all(2 <= len(q) <= 6 for q in queries)
    assert np.allclose(q_topics.sum(1), 1.0)
    # every query term survives the vocabulary's cut, and comes from the
    # slice of one of the query's topics
    for q, qt in zip(queries, q_topics):
        assert np.all(r2s[q] >= 0)
        assert set((q - 2000) // 300) <= set(np.flatnonzero(qt))


def test_queries_keep_their_terms():
    c = C.generate(CFG, 2**33 + 3)
    real = (c.queries >= 0).sum(1)
    assert real.min() >= 2 and real.max() <= 6 and 3.0 < real.mean() < 4.5


def test_seeds_relabel_the_same_structure():
    a, b = C.generate(CFG, 1), C.generate(CFG, 2**33 + 1)
    assert a.tokens.shape == b.tokens.shape and a.vocab_size == b.vocab_size
    assert not np.array_equal(a.tokens, b.tokens)
    for c in (a, b):
        assert c.tokens.max() < c.vocab_size

    def uniq(c):      # distinct terms per doc: the index's postings
        return sorted(len(np.unique(t[t >= 0])) for t in c.tokens)

    assert uniq(a) == uniq(b)
    assert np.array_equal(np.sort(a.idf), np.sort(b.idf))


def test_schedule_is_stratified():
    c = C.generate(CFG, 5)
    mix = {"rate_rps": 50.0, "arrivals": {"kind": "poisson"},
           "queries": {"kind": "uniform"},
           "depth": {"kind": "geometric", "min": 5, "mean": 19.4, "max": 64},
           "check_requests": 8}
    r1 = traffic.schedule(mix, c, 1, 4.0)
    r2 = traffic.schedule(mix, c, 2, 4.0)
    assert len(r1) == len(r2) == 200
    due = np.array([r.due_s for r in r1])
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] < 4.0
    gaps = lambda rs: np.sort(np.diff([r.due_s for r in rs] + [4.0]))
    assert np.allclose(gaps(r1), gaps(r2))
    sizes = lambda rs: sorted(len(r.docs) for r in rs)
    assert sizes(r1) == sizes(r2)
    assert 18 < np.mean(sizes(r1)) < 21 and max(sizes(r1)) <= 64
    for r in r1:
        assert len(np.unique(r.docs)) == len(r.docs)
        assert r.docs.max() < CFG["n_docs"]
        pool = set(C.relevant(c, r.query))
        assert set(r.docs[:min(len(pool), len(r.docs))]) <= pool
    pick = traffic.check_sample(r1, mix, 1)
    longest = int(np.argmax([len(r.docs) for r in r1]))
    assert len(pick) == 8 and longest in pick


@pytest.fixture(scope="module")
def small():
    return C.generate(CFG, 11)


def _mix(arrivals, queries=None):
    return {"rate_rps": 40.0, "arrivals": arrivals,
            "queries": queries or {"kind": "uniform"},
            "depth": {"kind": "fixed", "n": 3}, "check_requests": 4}


def test_order_seed_fixes_the_arrival_pattern(small):
    mix = _mix({"kind": "poisson", "order_seed": 7})
    due = lambda s: [r.due_s for r in traffic.schedule(mix, small, s, 5.0)]
    assert due(1) == due(2**40 + 9)
    free = _mix({"kind": "poisson"})
    a = [r.due_s for r in traffic.schedule(free, small, 1, 5.0)]
    b = [r.due_s for r in traffic.schedule(free, small, 2, 5.0)]
    assert a != b and len(a) == len(b) == 200


def test_onoff_arrivals_fall_in_the_on_phases(small):
    mix = _mix({"kind": "onoff", "period_s": 1.0, "on_share": 0.25})
    due = np.array([r.due_s for r in traffic.schedule(mix, small, 3, 5.0)])
    assert due.size == 200 and np.all(np.diff(due) >= 0)
    assert np.all(due % 1.0 < 0.25)
    assert np.bincount(due.astype(int)).tolist() == [40] * 5


def test_zipf_queries_follow_their_counts(small):
    mix = _mix({"kind": "poisson"}, {"kind": "zipf", "s": 1.0})
    qs = [r.query for r in traffic.schedule(mix, small, 4, 5.0)]
    counts = np.sort(np.bincount(qs, minlength=CFG["n_queries"]))[::-1]
    p = 1.0 / np.arange(1, CFG["n_queries"] + 1)
    want = 200 * p / p.sum()
    assert counts.sum() == 200 and np.all(np.abs(counts - want) < 1)
    other = [r.query for r in traffic.schedule(mix, small, 5, 5.0)]
    assert sorted(np.bincount(other)) == sorted(np.bincount(qs))
    with pytest.raises(ValueError):
        traffic.schedule(_mix({"kind": "poisson"}, {"kind": "nope"}),
                         small, 4, 1.0)
