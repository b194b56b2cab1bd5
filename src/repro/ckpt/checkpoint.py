"""Fault-tolerant checkpointing.

Design for 1000+-node operation:
* ATOMIC writes: serialize to `<dir>/tmp.<step>` then `os.replace` — a
  preempted writer never corrupts the latest checkpoint;
* keep-k retention + a MANIFEST (json) holding step, pytree structure,
  data-pipeline state and the logical mesh the run used;
* arrays stored LOGICALLY (unsharded host npz). Restore may target a
  different mesh shape — reshard-on-load is what makes elastic rescale
  work (shrink 512 -> 256 chips after a pod loss, or grow back);
* async: the device->host gather happens on the caller thread but the file
  write can be pushed to a background thread (``async_write=True``) so the
  train loop overlaps I/O with the next step.
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

_SEP = "/"
_log = obs.get_logger("repro.ckpt")


def _flatten_with_paths(tree) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = _SEP.join(_key_str(k) for k in path)
        out.append((name, leaf))
    return out


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def save_checkpoint(ckpt_dir: str, step: int, tree: Any, *,
                    extra: Optional[Dict] = None, keep: int = 3,
                    async_write: bool = False) -> str:
    """Returns the final checkpoint path."""
    os.makedirs(ckpt_dir, exist_ok=True)
    flat = _flatten_with_paths(tree)
    arrays = {name: np.asarray(leaf) for name, leaf in flat}
    manifest = {
        "step": int(step),
        "names": [n for n, _ in flat],
        "extra": extra or {},
        "time": time.time(),
    }
    final = os.path.join(ckpt_dir, f"ckpt_{step:010d}")

    def write():
        try:
            with obs.span("ckpt.save"):
                # one tmp dir per writer: two async writers of the same
                # step (a periodic save and the final one) must not
                # publish each other's half-written directory
                tmp = (final + f".tmp{os.getpid()}"
                       f"-{threading.get_ident()}")
                os.makedirs(tmp, exist_ok=True)
                np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
                with open(os.path.join(tmp, "manifest.json"), "w") as f:
                    json.dump(manifest, f)
                if os.path.exists(final):
                    import shutil
                    shutil.rmtree(final)
                os.replace(tmp, final)          # atomic publish
                _retain(ckpt_dir, keep)
            obs.counter("seine_ckpt_saves_total",
                        "checkpoint publishes").inc()
        except BaseException as e:
            obs.counter("seine_ckpt_write_errors_total",
                        "failed (a)sync ckpt/index writes").inc()
            _log.error("checkpoint write failed", path=final, err=repr(e))
            raise

    if async_write:
        _spawn_async(write)
    else:
        write()
    return final


_ASYNC_THREADS: List[threading.Thread] = []
_ASYNC_ERRORS: List[BaseException] = []


def _spawn_async(write) -> None:
    """Run ``write`` on a daemon thread, capturing any failure for
    :func:`wait_async` to re-raise — a background writer must never fail
    silently (the obs error counter records it; the join surfaces it)."""
    def run():
        try:
            write()
        except BaseException as e:
            _ASYNC_ERRORS.append(e)
    t = threading.Thread(target=run, daemon=True)
    t.start()
    _ASYNC_THREADS.append(t)


def wait_async() -> None:
    """Join every background writer; re-raise the first captured failure."""
    for t in _ASYNC_THREADS:
        t.join()
    _ASYNC_THREADS.clear()
    if _ASYNC_ERRORS:
        err = _ASYNC_ERRORS[0]
        _ASYNC_ERRORS.clear()
        raise err


def _retain(ckpt_dir: str, keep: int) -> None:
    steps = sorted(all_steps(ckpt_dir))
    for s in steps[:-keep]:
        import shutil
        shutil.rmtree(os.path.join(ckpt_dir, f"ckpt_{s:010d}"),
                      ignore_errors=True)


def all_steps(ckpt_dir: str) -> List[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for n in os.listdir(ckpt_dir):
        m = re.fullmatch(r"ckpt_(\d{10})", n)
        if m and os.path.exists(os.path.join(ckpt_dir, n, "manifest.json")):
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


# ---------------------------------------------------------------------------
# per-shard SEINE index checkpointing (Algorithm 1's saveAsPickleFile slot)
# ---------------------------------------------------------------------------

_INDEX_MANIFEST = "index_manifest.json"


def save_index(index_dir: str, index: Any, *,
               async_write: bool = False) -> str:
    """Persist a SEINE index with one file PER SHARD.

    A :class:`~repro.dist.partition.PartitionedIndex` writes each term-
    range shard's (term_offsets, doc_ids, values) slice to its own
    ``shard_<k>.npz`` — so at production scale each pod serialises only
    the shard it built/holds and no host ever gathers the stacked arrays
    — plus one ``common.npz`` with the replicated structures (routing
    table, range starts, idf, per-doc stats).  A single-CSR
    :class:`~repro.core.index.SegmentInvertedIndex` is the K=1 special
    case.  Atomic like :func:`save_checkpoint`: tmp dir + ``os.replace``.
    ``async_write=True`` pushes the file I/O + publish to a background
    thread (device->host gather stays on the caller thread); failures
    are recorded on ``seine_ckpt_write_errors_total`` and re-raised by
    :func:`wait_async`.  Returns the final directory path.
    """
    from ..core.index import SegmentInvertedIndex
    from ..dist.partition import PartitionedIndex

    os.makedirs(os.path.dirname(index_dir) or ".", exist_ok=True)
    if isinstance(index, PartitionedIndex):
        kind, n_shards = "partitioned", index.n_shards
        common = {"term_to_shard": index.term_to_shard,
                  "range_lo": index.range_lo}
        # sub-shard / fence metadata (absent on legacy indexes; loaders
        # treat missing keys as None / derive them)
        for name in ("range_hi", "split_term", "split_doc"):
            a = getattr(index, name)
            if a is not None:
                common[name] = a
        # posting payload per codec: raw arrays for "none", the packed
        # sidecars otherwise (fences are NOT stored — load_index rebuilds
        # them from the packed metadata / raw ids)
        posting = {"doc_ids": index.doc_ids, "values": index.values,
                   "packed_words": index.packed_words,
                   "tile_bits": index.tile_bits,
                   "tile_base": index.tile_base,
                   "tile_word_off": index.tile_word_off,
                   "values_q": index.values_q,
                   "value_scale": index.value_scale}
        shard = lambda k: dict(
            {"term_offsets": index.term_offsets[k]},
            **{n: a[k] for n, a in posting.items() if a is not None})
    elif isinstance(index, SegmentInvertedIndex):
        kind, n_shards = "segment", 1
        common = {}
        shard = lambda k: {"term_offsets": index.term_offsets,
                           "doc_ids": index.doc_ids,
                           "values": index.values}
    else:
        raise TypeError(f"cannot save index of type {type(index).__name__}")
    common.update(idf=index.idf, doc_len=index.doc_len,
                  seg_len=index.seg_len)
    manifest = {
        "kind": kind, "n_shards": int(n_shards),
        "n_docs": int(index.n_docs), "vocab_size": int(index.vocab_size),
        "n_b": int(index.n_b), "functions": list(index.functions),
        "time": time.time(),
    }
    codec = getattr(index, "codec", "none")
    if codec != "none":
        manifest.update(codec=codec, codec_tile=int(index.codec_tile),
                        max_tile_words=int(index.max_tile_words),
                        codec_spans=[int(s) for s in index.codec_spans])
    # device->host gather on the caller thread (mirrors save_checkpoint:
    # the background thread only ever does file I/O + the publish swap)
    shard_arrays = [{n: np.asarray(a) for n, a in shard(k).items()}
                    for k in range(n_shards)]
    common_arrays = {n: np.asarray(a) for n, a in common.items()}

    def write():
        try:
            with obs.span("ckpt.save_index"):
                tmp = index_dir.rstrip("/") + f".tmp{os.getpid()}"
                os.makedirs(tmp, exist_ok=True)
                for k, arrs in enumerate(shard_arrays):
                    np.savez(os.path.join(tmp, f"shard_{k:05d}.npz"),
                             **arrs)
                np.savez(os.path.join(tmp, "common.npz"), **common_arrays)
                with open(os.path.join(tmp, _INDEX_MANIFEST), "w") as f:
                    json.dump(manifest, f)
                if os.path.exists(index_dir):
                    # never rmtree the live index before publishing: move
                    # it aside first, so a writer preempted mid-overwrite
                    # leaves the previous index recoverable at <dir>.old*
                    # (load_index falls back to it) instead of destroyed.
                    # NOTE directory swap cannot be a single atomic op
                    # portably — a reader racing the two os.replace calls
                    # can momentarily miss index_dir; overwrite a live
                    # serving path only behind the .old fallback or
                    # publish to a fresh dir.
                    import glob
                    import shutil
                    old = index_dir.rstrip("/") + f".old{os.getpid()}"
                    if os.path.exists(old):
                        shutil.rmtree(old)
                    os.replace(index_dir, old)
                    os.replace(tmp, index_dir)
                    # a successful publish supersedes every stranded
                    # leftover — including .old/.tmp dirs from OTHER
                    # (preempted) pids, which would otherwise accumulate
                    # and confuse future recovery
                    for stale in glob.glob(
                            index_dir.rstrip("/") + ".old*") + \
                            glob.glob(index_dir.rstrip("/") + ".tmp*"):
                        shutil.rmtree(stale, ignore_errors=True)
                else:
                    os.replace(tmp, index_dir)      # atomic publish
            obs.counter("seine_index_saves_total",
                        "index dir publishes").inc()
        except BaseException as e:
            obs.counter("seine_ckpt_write_errors_total",
                        "failed (a)sync ckpt/index writes").inc()
            _log.error("index save failed", path=index_dir, err=repr(e))
            raise

    if async_write:
        _spawn_async(write)
    else:
        write()
    return index_dir


def load_index_shard(index_dir: str, k: int) -> Dict[str, np.ndarray]:
    """One shard's local CSR arrays (what a single pod restores)."""
    with np.load(os.path.join(index_dir, f"shard_{k:05d}.npz")) as z:
        return {n: z[n] for n in z.files}


def load_index(index_dir: str) -> Any:
    """Restore the index saved by :func:`save_index` (round-trips to the
    same arrays bit-for-bit; tests/test_build_pipeline.py holds it).

    If ``index_dir`` is missing/unpublished but a ``<dir>.old<pid>`` left
    by a writer preempted mid-overwrite exists, that previous index is
    restored instead — the overwrite crash window loses the half-written
    update, never the published index.
    """
    from ..core.index import SegmentInvertedIndex
    from ..dist.partition import PartitionedIndex

    if not os.path.exists(os.path.join(index_dir, _INDEX_MANIFEST)):
        import glob
        stranded = glob.glob(index_dir.rstrip("/") + ".old*")
        if stranded:
            # newest by mtime, NOT lexicographic — pids don't sort by age
            index_dir = max(stranded, key=os.path.getmtime)
    with open(os.path.join(index_dir, _INDEX_MANIFEST)) as f:
        m = json.load(f)
    with np.load(os.path.join(index_dir, "common.npz")) as z:
        common = {n: z[n] for n in z.files}
    static = dict(n_docs=m["n_docs"], vocab_size=m["vocab_size"],
                  n_b=m["n_b"], functions=tuple(m["functions"]))
    from ..core.index import build_fences
    if m["kind"] == "segment":
        s = load_index_shard(index_dir, 0)
        doc_ids = jnp.asarray(s["doc_ids"])
        return SegmentInvertedIndex(
            term_offsets=jnp.asarray(s["term_offsets"]),
            doc_ids=doc_ids,
            values=jnp.asarray(s["values"]),
            fences=build_fences(doc_ids),
            idf=jnp.asarray(common["idf"]),
            doc_len=jnp.asarray(common["doc_len"]),
            seg_len=jnp.asarray(common["seg_len"]), **static)
    shards = [load_index_shard(index_dir, k) for k in range(m["n_shards"])]
    opt = lambda n: (jnp.asarray(common[n]) if n in common else None)
    stack = lambda n: (jnp.asarray(np.stack([s[n] for s in shards]))
                       if n in shards[0] else None)
    codec = m.get("codec", "none")     # legacy manifests: uncompressed
    if codec == "none":
        doc_ids = stack("doc_ids")
        posting = dict(doc_ids=doc_ids, values=stack("values"),
                       fences=build_fences(doc_ids))
    else:
        # packed shards: ids/values stay in their compressed form; the
        # fence rows are not stored — decode them from the tile metadata
        # (bitwise what build_fences produced on the raw ids)
        from ..core.codec import fences_from_packed
        posting = dict(
            codec=codec, codec_tile=int(m["codec_tile"]),
            max_tile_words=int(m["max_tile_words"]),
            codec_spans=tuple(m.get("codec_spans", (0, 0))),
            doc_ids=None, values=stack("values"),
            packed_words=stack("packed_words"),
            tile_bits=stack("tile_bits"), tile_base=stack("tile_base"),
            tile_word_off=stack("tile_word_off"),
            values_q=stack("values_q"), value_scale=stack("value_scale"))
        nmax = (posting["values"] if posting["values"] is not None
                else posting["values_q"]).shape[1]
        posting["fences"] = jnp.asarray(fences_from_packed(
            np.stack([s["tile_bits"] for s in shards]),
            np.stack([s["tile_base"] for s in shards]),
            np.stack([s["tile_word_off"] for s in shards]),
            np.stack([s["packed_words"] for s in shards]),
            tile=int(m["codec_tile"]), n=int(nmax)))
    return PartitionedIndex(
        term_offsets=jnp.asarray(
            np.stack([s["term_offsets"] for s in shards])),
        term_to_shard=jnp.asarray(common["term_to_shard"]),
        range_lo=jnp.asarray(common["range_lo"]),
        idf=jnp.asarray(common["idf"]),
        doc_len=jnp.asarray(common["doc_len"]),
        seg_len=jnp.asarray(common["seg_len"]),
        range_hi=opt("range_hi"),
        split_term=opt("split_term"), split_doc=opt("split_doc"),
        n_shards=m["n_shards"], **static, **posting)


def restore_checkpoint(ckpt_dir: str, target: Any, *,
                       step: Optional[int] = None,
                       shardings: Any = None) -> Tuple[Any, Dict]:
    """Restore into the structure of `target`.

    `shardings`: optional pytree of NamedSharding matching `target` — arrays
    are placed directly onto the (possibly different-shaped) mesh, which is
    the reshard-on-load path for elastic restarts.
    Returns (tree, manifest)."""
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"ckpt_{step:010d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    flat_t = _flatten_with_paths(target)
    shard_flat = None
    if shardings is not None:
        shard_flat = [s for _, s in _flatten_with_paths(shardings)]
    leaves = []
    for i, (name, leaf) in enumerate(flat_t):
        if name not in data:
            raise KeyError(f"checkpoint missing leaf {name}")
        arr = data[name]
        if tuple(arr.shape) != tuple(np.shape(leaf)):
            raise ValueError(f"shape mismatch for {name}: "
                             f"{arr.shape} vs {np.shape(leaf)}")
        if shard_flat is not None:
            leaves.append(jax.device_put(arr, shard_flat[i]))
        else:
            leaves.append(jnp.asarray(arr, dtype=leaf.dtype
                                      if hasattr(leaf, "dtype") else None))
    treedef = jax.tree_util.tree_structure(target)
    return jax.tree_util.tree_unflatten(treedef, leaves), manifest
