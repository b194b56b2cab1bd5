#!/usr/bin/env python3
"""Drive SEINE's serving path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip, MQ2007 scale
    python chip_smoke.py --four-chips   # four chips: the term-partitioned mesh

One chip: a SEINE_LETOR corpus (n_b = 20 segments x 9 interaction
functions, embed_dim 128, ~600-token documents) at MQ2007's 65,323 docs
is generated from ``--seed``, segmented and built through
``IndexBuilder.build_partitioned(..., 1)``.  Then, through the serving
entry points a user calls:

  score     ``SeineEngine.score`` with knrm, 8 query terms x 2,048
            candidates.  M from the Pallas kernel path equals the
            ``impl="jnp"`` lookup bit for bit;
  retrieve  ``SeineEngine.retrieve`` with k = 10; ids and scores equal
            the ``impl="jnp"`` scan;
  frontend  ``ServingFrontend(coalesce=True)``; scores equal
            ``engine.score`` bit for bit;
  q8        a ``pack_index(pidx, "packed-q8")`` copy served the same way
            (its kernel M equals its jnp reference bit for bit), with
            recall@10 against the f32 index >= 0.9.

``--four-chips`` runs only the mesh phase, at 16,384 docs: K = 4
term-range shards placed on a mesh whose ``model`` axis spans the four
chips (``SeineEngine(mesh=..., partition="term")``), checked to hold
about a quarter of the posting bytes per chip and to score bit for bit
like the mesh-less fused engine on one chip.

Everything runs in this one process, which holds the chip(s).  A failed
check or phase raises, so the exit code is nonzero; the last line of
standard output is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MQ2007_DOCS = 65_323
MESH_DOCS = 16_384    # --four-chips: the one-chip comparison index fits too
N_CANDIDATES = 2_048
Q_LEN = 8
N_REQUESTS = 4
TOP_K = 10
Q8_RECALL_GATE = 0.9


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileClock:
    """Backend-compile seconds, read from JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.total = 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_event(name, secs, **_):
            if name == event:
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def lap(self) -> float:
        t, self.total = self.total, 0.0
        return round(t, 2)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def has_kernel(jitted, *args) -> bool:
    """Whether the program ``jitted`` runs for ``args`` holds a Pallas
    kernel (lowering only: nothing is compiled twice)."""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def make_corpus(n_docs: int, seed: int):
    """SEINE_LETOR corpus at ``n_docs``, segmented at its full length."""
    from repro.configs.seine_letor import SEINE_LETOR
    from repro.core import build_vocabulary, segment_corpus
    from repro.data.batching import pad_queries
    from repro.data.synth_corpus import generate

    cfg = dataclasses.replace(SEINE_LETOR, n_docs=n_docs)
    ds = generate(cfg, seed=seed)
    vocab = build_vocabulary(ds.docs, ds.n_raw_tokens)
    slot_docs = [vocab.map_tokens(d) for d in ds.docs]
    max_len = max(len(d) for d in slot_docs)
    toks, segs = segment_corpus(slot_docs, cfg.n_segments, max_len=max_len)
    queries = pad_queries(ds.queries, vocab.map_tokens, q_len=Q_LEN)
    return cfg, ds, vocab, toks, segs, queries, max_len


def make_requests(ds, queries, seed: int, n: int, n_cand: int):
    import numpy as np

    from repro.data.batching import candidates_for_query

    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        qi = i % len(queries)
        cands = candidates_for_query(ds.qrels[qi], rng, n_cand)
        out.append((queries[qi], cands.astype(np.int32)))
    return out


def serve_phases(tag, engine, requests, clock, dev, jnp_lookup):
    """score / retrieve / frontend through ``engine``; returns the
    retrieve results.  ``jnp_lookup(index, q, d)`` is the plain-jnp M.
    Every program here takes the index as an argument: jit embeds a
    closed-over array in the program as a literal constant."""
    import jax
    import numpy as np

    from repro.serving import ServingFrontend
    from repro.serving.engine import make_qmeta

    spec, params, pidx = engine.spec, engine.params, engine.index

    # score: the kernel path's M against the jnp lookup, then the engine
    fused_m = jax.jit(lambda p, q, d: p.qd_matrix(q, d))
    ref_m = jax.jit(jnp_lookup)
    q0, d0 = requests[0]
    kernel = has_kernel(fused_m, pidx, q0, d0) and has_kernel(
        engine._score, params, pidx, q0, d0)
    check(kernel, f"{tag}: no tpu_custom_call in the scored programs")
    scores = []
    t0 = time.perf_counter()
    for q, d in requests:
        m = np.asarray(fused_m(pidx, q, d))
        m_ref = np.asarray(ref_m(pidx, q, d))
        check(m.shape == (d.shape[0], Q_LEN, pidx.n_b, len(pidx.functions)),
              f"{tag}: M shape {m.shape}")
        check(np.isfinite(m).all(), f"{tag}: M not finite")
        check(np.array_equal(m, m_ref),
              f"{tag}: kernel M != jnp M "
              f"(max |diff| {np.abs(m - m_ref).max()})")
        s = np.asarray(jax.block_until_ready(engine.score(q, d)))
        check(s.shape == d.shape and np.isfinite(s).all(),
              f"{tag}: engine scores shape/finite")
        scores.append(s)
    log(f"{tag}.score", requests=len(requests), candidates=d0.shape[0],
        q_terms=Q_LEN, tpu_custom_call=kernel, compile_s=clock.lap(),
        wall_s=round(time.perf_counter() - t0, 2), **memory(dev))

    # retrieve: the engine's scan against the same scan over impl="jnp"
    def jnp_retrieve(p, q):
        def score_block(m, docs):
            d = docs.clip(0, p.n_docs - 1)
            return spec.score(params, m, make_qmeta(p, q, d), p.functions)
        return p.retrieve_topk(q, TOP_K, score_block, impl="jnp")

    jnp_retrieve = jax.jit(jnp_retrieve)
    results = []
    t0 = time.perf_counter()
    for q, _ in requests:
        sc, ids = (np.asarray(a) for a in engine.retrieve(q, TOP_K))
        sc_ref, ids_ref = (np.asarray(a) for a in jnp_retrieve(pidx, q))
        check(ids.shape == (TOP_K,) and np.isfinite(sc).all(),
              f"{tag}: retrieve shape/finite")
        check(np.array_equal(ids, ids_ref), f"{tag}: retrieve ids "
              f"{ids.tolist()} != jnp scan {ids_ref.tolist()}")
        check(np.array_equal(sc, sc_ref), f"{tag}: retrieve scores "
              f"{sc.tolist()} != jnp scan {sc_ref.tolist()}")
        results.append(ids)
    log(f"{tag}.retrieve", queries=len(requests), k=TOP_K,
        compile_s=clock.lap(), wall_s=round(time.perf_counter() - t0, 2),
        **memory(dev))

    # frontend: coalesced continuous batching, bitwise vs engine.score
    t0 = time.perf_counter()
    with ServingFrontend(engine, max_batch=len(requests),
                         coalesce=True) as fe:
        futs = [fe.submit(q, d) for q, d in requests]
        got = [f.result() for f in futs]
    for s, s_ref in zip(got, scores):
        check(np.array_equal(s, s_ref), f"{tag}: frontend scores != "
              f"engine.score (max |diff| {np.abs(s - s_ref).max()})")
    log(f"{tag}.frontend", requests=len(requests), coalesce=True,
        compile_s=clock.lap(), wall_s=round(time.perf_counter() - t0, 2),
        **memory(dev))
    return results


def one_chip(args, dev, clock) -> None:
    import jax
    import numpy as np

    from repro.core import HashProvider, IndexBuilder
    from repro.dist.partition import pack_index
    from repro.retrievers import get_retriever
    from repro.serving import SeineEngine

    t0 = time.perf_counter()
    cfg, ds, vocab, toks, segs, queries, max_len = make_corpus(
        MQ2007_DOCS, args.seed)
    log("corpus", docs=MQ2007_DOCS, max_len=max_len, vocab=vocab.size,
        wall_s=round(time.perf_counter() - t0, 2))

    t0 = time.perf_counter()
    builder = IndexBuilder(cfg, vocab,
                           HashProvider(vocab.size, cfg.embed_dim,
                                        seed=args.seed))
    pidx = builder.build_partitioned(toks, segs, 1, batch_size=64)
    values = pidx.values
    log("build", nnz=pidx.nnz, values_shape=tuple(values.shape),
        values_bytes=values.size * values.dtype.itemsize,
        values_layout=values.format.layout, compile_s=clock.lap(),
        wall_s=round(time.perf_counter() - t0, 2), **memory(dev))
    del values

    spec = get_retriever("knrm")
    params = spec.init(jax.random.key(args.seed), cfg.n_segments,
                       pidx.functions)
    requests = make_requests(ds, queries, args.seed, N_REQUESTS,
                             N_CANDIDATES)
    engine = SeineEngine(pidx, "knrm", params)
    f32_ids = serve_phases(
        "f32", engine, requests, clock, dev,
        lambda p, q, d: p.qd_matrix(q, d, impl="jnp"))

    # q8: pack from the f32 index, then free it before serving the copy
    t0 = time.perf_counter()
    q8 = pack_index(pidx, "packed-q8")
    del engine, pidx
    gc.collect()
    log("q8.pack", values_bytes=q8.values_q.size,
        compile_s=clock.lap(), wall_s=round(time.perf_counter() - t0, 2),
        **memory(dev))
    q8_engine = SeineEngine(q8, "knrm", params)
    q8_ids = serve_phases(
        "q8", q8_engine, requests, clock, dev,
        lambda p, q, d: p.lookup_pairs(
            jax.numpy.broadcast_to(q[None], (d.shape[0],) + q.shape), d))
    recall = float(np.mean([len(set(a.tolist()) & set(b.tolist())) / TOP_K
                            for a, b in zip(q8_ids, f32_ids)]))
    log("q8.recall", recall_at_10=recall, gate=Q8_RECALL_GATE)
    check(recall >= Q8_RECALL_GATE, f"q8 recall@10 {recall} < gate")
    log("done", **memory(dev))


def four_chips(args, devs, clock) -> None:
    import jax
    import numpy as np

    from repro.core import HashProvider, IndexBuilder
    from repro.launch.mesh import make_host_mesh
    from repro.retrievers import get_retriever
    from repro.serving import SeineEngine

    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    t0 = time.perf_counter()
    cfg, ds, vocab, toks, segs, queries, max_len = make_corpus(
        MESH_DOCS, args.seed)
    builder = IndexBuilder(cfg, vocab,
                           HashProvider(vocab.size, cfg.embed_dim,
                                        seed=args.seed))
    pidx = builder.build_partitioned(toks, segs, 4, batch_size=64)
    log("build", docs=MESH_DOCS, shards=pidx.n_shards, nnz=pidx.nnz,
        values_bytes=pidx.values.size * 4, compile_s=clock.lap(),
        wall_s=round(time.perf_counter() - t0, 2))
    check(pidx.n_shards == 4, f"built {pidx.n_shards} shards, not 4")

    spec = get_retriever("knrm")
    params = spec.init(jax.random.key(args.seed), cfg.n_segments,
                       pidx.functions)
    requests = make_requests(ds, queries, args.seed, N_REQUESTS,
                             N_CANDIDATES)
    fused = SeineEngine(pidx, "knrm", params)
    mesh = make_host_mesh(data=1, model=4)
    meshed = SeineEngine(pidx, "knrm", params, mesh=mesh, partition="term")

    placed = meshed.index.values
    per_dev = {s.device.id: s.data.nbytes for s in placed.addressable_shards}
    total = placed.size * placed.dtype.itemsize
    log("mesh.place", mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
        bytes_per_device=per_dev, total_bytes=total)
    check(len(per_dev) == 4, f"values on {len(per_dev)} devices, not 4")
    for dev_id, b in per_dev.items():
        check(abs(b - total / 4) <= 0.01 * total,
              f"device {dev_id} holds {b} of {total} posting bytes")

    t0 = time.perf_counter()
    for q, d in requests:
        s_mesh = np.asarray(meshed.score(q, d))
        s_one = np.asarray(fused.score(q, d))
        check(np.isfinite(s_one).all(), "fused scores not finite")
        check(np.array_equal(s_mesh, s_one), "mesh scores != fused "
              f"(max |diff| {np.abs(s_mesh - s_one).max()})")
    log("mesh.score", requests=len(requests),
        candidates=requests[0][1].shape[0],
        compile_s=clock.lap(), wall_s=round(time.perf_counter() - t0, 2))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip term-partitioned mesh "
                         "phase and its one-chip comparison")
    args = ap.parse_args()

    import repro
    repro.use_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    log("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__)
    if args.four_chips:
        four_chips(args, devs, clock)
    else:
        one_chip(args, devs[0], clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
