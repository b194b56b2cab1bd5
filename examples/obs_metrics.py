"""Observability walkthrough: metrics, spans and exporters over one
index-build-and-serve lifecycle (the repro.obs quick-start, runnable).

    PYTHONPATH=src python examples/obs_metrics.py

Builds the smoke index (instrumented build stages fill the
``seine_build_*`` counters and ``build.stage*`` spans), partitions it
(``seine_shard_*`` balance gauges), serves a few batched requests
(``seine_serve_*``, ``seine_lookup_pairs_total`` and the
``engine.score`` / ``serve.request`` spans),
then shows the three export surfaces:

* ``obs.to_prometheus()``  — Prometheus text exposition (what
  ``launch/serve.py --metrics-out out.prom`` writes);
* ``obs.dump("path.json")`` — the JSON snapshot (what the bench lane
  uploads as OBS_bench.json);
* ``obs.span_stats()``      — in-process span aggregates.

The same snapshot is what ``scripts/bench_gate.py`` reads its
shard-balance printout from.  The full metric-name table lives in the
``repro.obs`` module docstring.
"""
import json
import tempfile

import numpy as np

from repro import obs
from repro.configs import seine_smoke
from repro.core import (HashProvider, IndexBuilder, build_vocabulary,
                        segment_corpus)
from repro.data.batching import candidates_for_query, pad_queries
from repro.data.synth_corpus import generate
from repro.retrievers import get_retriever
from repro.serving import SeineEngine, serve_batches

import jax


def main() -> None:
    obs.reset()                       # a clean registry for the demo

    # -- build (stages 1-4 instrumented by core.build_pipeline) ---------
    cfg = seine_smoke()
    ds = generate(cfg, seed=0)
    vocab = build_vocabulary(ds.docs, ds.n_raw_tokens)
    toks, segs = segment_corpus([vocab.map_tokens(d) for d in ds.docs],
                                cfg.n_segments, max_len=160)
    builder = IndexBuilder(cfg, vocab, HashProvider(vocab.size,
                                                    cfg.embed_dim, seed=0))
    index = builder.build_partitioned(toks, segs, 2, batch_size=16)

    # -- serve (engine + serve_batches instrumented) --------------------
    queries = pad_queries(ds.queries, vocab.map_tokens, q_len=6)
    rng = np.random.RandomState(0)
    requests = [(queries[i % len(queries)],
                 candidates_for_query(ds.qrels[i % len(queries)], rng, 32))
                for i in range(8)]
    requests.append((queries[0], np.zeros(0, np.int32)))  # degenerate
    spec = get_retriever("knrm")
    params = spec.init(jax.random.key(0), cfg.n_segments, index.functions)
    engine = SeineEngine(index, "knrm", params)
    _, stats = serve_batches(engine, requests, batch_pad=16)

    # -- export surfaces -------------------------------------------------
    print("== selected metrics ==")
    for name in ("seine_build_docs_total", "seine_shard_nnz",
                 "seine_serve_requests_total",
                 "seine_serve_degenerate_requests_total",
                 "seine_lookup_pairs_total"):
        for labels, value in obs.REGISTRY.get(name).samples():
            tag = "".join(f"{{{k}={v}}}" for k, v in labels)
            print(f"  {name}{tag} = {value:g}")
    p95 = obs.histogram("seine_serve_latency_ms").percentile(95)
    print(f"  seine_serve_latency_ms p95 ~ {p95:g} ms "
          f"(bucket resolution; exact recent-window p95: "
          f"{stats.p95_ms:.2f} ms)")

    print("\n== span aggregates ==")
    for name, st in sorted(obs.span_stats().items()):
        print(f"  {name}: n={st.count} total={st.total_s * 1e3:.1f} ms")

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        snap_path = f.name
    obs.dump(snap_path)               # JSON snapshot, OBS_bench.json-style
    with open(snap_path) as f:
        snap = json.load(f)
    print(f"\n== JSON snapshot ({snap_path}) ==")
    print(f"  {len(snap['metrics'])} metric families, "
          f"{len(snap['spans'])} span names")

    prom = obs.to_prometheus()        # what --metrics-out writes
    again = obs.parse_prometheus(prom)
    print(f"\n== Prometheus text ==\n  {len(prom.splitlines())} lines, "
          f"{len(again)} families parse back")


if __name__ == "__main__":
    main()
