"""The one traffic generator: a mix file of parameters -> a request schedule.

A mix (``bench/traffic/<name>.json``) is data only; a new mix needs no
code.  It states:

``rate_rps``         mean offered load, requests per second (open loop);
``limit_ms``         the cell's latency limit;
``arrivals``         when requests are due:
                     ``{"kind": "poisson", "order_seed": s}`` or
                     ``{"kind": "onoff", "period_s": p, "on_share": f,
                     "order_seed": s}`` (all of the load in the first
                     ``f`` of every period, Poisson inside it);
                     ``order_seed`` fixes the order of the gaps for every
                     run of the mix; without it ``--seed`` draws it;
``queries``          which query a request asks: ``{"kind": "uniform"}``
                     (the whole set in turn) or ``{"kind": "zipf", "s":
                     x}`` (popularity falling as rank ** -x over a ranking
                     of the queries drawn from the seed);
``depth``            candidates per request: ``{"kind": "fixed", "n": N}``
                     or ``{"kind": "geometric", "min": a, "mean": m,
                     "max": b}`` (a + a geometric count, capped at b);
``batch_pad``        the frontend's candidate padding, chosen so that one
                     score shape serves every request of the mix;
``check_requests``   how many served requests the reference checks;
``checks``           the limit of each number compared.

Every random count is stratified: a run's ``N = rate x seconds`` gaps are
the N quantiles of the exponential at ``(i + 0.5) / N``, scaled to fill
their span exactly; depths and Zipf counts are quantiles the same way.
So every seed offers the same amount of work, and with ``order_seed``
the same arrival pattern: seeds differ in which queries and documents are
asked, not in how much or how bunched the load is.  A run of one seed
against another then differs by the system, not by the draw of a tail.

Candidates of a request are drawn from the documents judged relevant to
its query (the topical documents a first stage would return), filled up
with other documents where the pool is short.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .corpus import Corpus, relevant, seed_words


@dataclasses.dataclass
class Request:
    due_s: float             # offset from the window's start
    query: int
    terms: np.ndarray        # (q_len,) int32
    docs: np.ndarray         # (n,) int32


def _quantile_gaps(n: int) -> np.ndarray:
    """The n quantiles of the unit exponential at ``(i + 0.5) / n``."""
    return -np.log1p(-(np.arange(n) + 0.5) / n)


def _fill(gaps: np.ndarray, span: float) -> np.ndarray:
    """Due offsets in [0, span) from gaps scaled to fill it."""
    gaps = gaps * (span / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def arrivals(spec: dict, rate: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due offsets in [0, seconds), ``round(rate x seconds)`` of them."""
    n = max(1, int(round(rate * seconds)))
    if "order_seed" in spec:
        rng = np.random.default_rng(seed_words(spec["order_seed"], 5))
    if spec["kind"] == "poisson":
        return _fill(rng.permutation(_quantile_gaps(n)), seconds)
    if spec["kind"] == "onoff":
        period, share = float(spec["period_s"]), float(spec["on_share"])
        n_per = max(1, int(round(seconds / period)))
        counts = np.full(n_per, n // n_per)
        counts[:n % n_per] += 1
        out = [p * period + _fill(rng.permutation(_quantile_gaps(k)),
                                  period * share)
               for p, k in enumerate(counts) if k]
        due = np.concatenate(out)
        return due[due < seconds]
    raise ValueError(f"unknown arrivals kind {spec['kind']!r}")


def query_order(spec: dict, n: int, n_q: int,
                rng: np.random.Generator) -> np.ndarray:
    """The query of each of ``n`` requests."""
    if spec["kind"] == "uniform":
        return np.concatenate([rng.permutation(n_q)
                               for _ in range(-(-n // n_q))])[:n]
    if spec["kind"] == "zipf":
        p = 1.0 / np.arange(1, n_q + 1, dtype=np.float64) ** spec["s"]
        p /= p.sum()
        # largest remainders: each rank's count, the counts summing to n
        want = p * n
        counts = np.floor(want).astype(np.int64)
        counts[np.argsort(counts - want, kind="stable")[:n - counts.sum()]] \
            += 1
        ranked = rng.permutation(n_q)           # the most popular first
        return rng.permutation(np.repeat(ranked, counts))
    raise ValueError(f"unknown queries kind {spec['kind']!r}")


def depths(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    d = mix["depth"]
    if d["kind"] == "fixed":
        return np.full(n, int(d["n"]), np.int64)
    if d["kind"] == "geometric":
        u = (np.arange(n) + 0.5) / n
        p = 1.0 / (d["mean"] - d["min"] + 1.0)
        k = np.floor(np.log1p(-u) / np.log1p(-p)).astype(np.int64)
        return rng.permutation(np.minimum(d["min"] + k, d["max"]))
    raise ValueError(f"unknown depth kind {d['kind']!r}")


def schedule(mix: dict, corpus: Corpus, seed: int, seconds: float, *,
             rate: float = None, stream: int = 2) -> list:
    """The requests of one window, in due order."""
    rng = np.random.default_rng(seed_words(seed, stream))
    due = arrivals(mix["arrivals"], rate or mix["rate_rps"], seconds, rng)
    n = due.size
    qs = query_order(mix["queries"], n, corpus.queries.shape[0], rng)
    sizes = depths(mix, n, rng)
    n_docs = corpus.tokens.shape[0]
    pools = {}
    out = []
    for t, q, k in zip(due, qs, sizes):
        if q not in pools:
            pools[q] = relevant(corpus, q)
        pool = pools[q]
        take = rng.choice(pool, size=min(k, pool.size), replace=False)
        if take.size < k:
            rest = np.setdiff1d(np.arange(n_docs), pool)
            take = np.concatenate(
                [take, rng.choice(rest, size=k - take.size, replace=False)])
        out.append(Request(float(t), int(q), corpus.queries[q],
                           take.astype(np.int32)))
    return out


def check_sample(requests: list, mix: dict, seed: int) -> list:
    """Indices of the requests the reference checks: drawn from the seed,
    with the one of most candidates always among them."""
    rng = np.random.default_rng(seed_words(seed, 3))
    n = min(int(mix["check_requests"]), len(requests))
    longest = int(np.argmax([len(r.docs) for r in requests]))
    rest = np.delete(np.arange(len(requests)), longest)
    pick = rng.choice(rest, size=n - 1, replace=False) if n > 1 else []
    return sorted([longest, *map(int, pick)])
