"""Sequence-parallel decode attention (distributed flash-decoding).

SEINE's online phase stays cheap only while lookups stay local; the one
query-time component with a long axis is the LM-provider's decode over a
long KV cache.  Sharding the cache on the sequence axis (dist.sharding.
lm_cache_spec) makes each device attend over its local KV slice; the slices
are then merged with the standard online-softmax (log-sum-exp) identity —
the exact math of the flash_attn kernel's chunk scan (kernels/flash_attn),
applied across devices instead of across chunks:

    m*   = max_i m_i
    l*   = sum_i l_i · exp(m_i − m*)
    acc* = sum_i acc_i · exp(m_i − m*)
    out  = acc* / l*

so the sharded result is bit-for-bit the reference attention semantics
(oracle: models.layers.naive_attention; tested in tests/test_extensions.py).
"""
from __future__ import annotations

import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def local_decode_stats(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       valid: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-shard online-softmax statistics for single-token GQA decode.

    q: (B, Hq, hd); k, v: (B, S_loc, Hkv, hd) — this shard's KV slice;
    valid: (B, S_loc) mask of live cache positions on this shard.
    Returns (m, l, acc): running max (B, Hq) — -inf where the shard holds
    no valid position — normaliser (B, Hq) and weighted value sum
    (B, Hq, hd), all float32.
    """
    B, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32)) \
        / math.sqrt(hd)                                    # (B, Hkv, G, S)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1)                                     # (B, Hkv, G)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])                     # masked -> 0
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return (m.reshape(B, Hq), l.reshape(B, Hq),
            acc.reshape(B, Hq, hd))


def combine_decode_stats(m: jnp.ndarray, l: jnp.ndarray, acc: jnp.ndarray
                         ) -> jnp.ndarray:
    """Merge per-shard stats stacked on a leading shard axis.

    m, l: (n_shards, B, Hq); acc: (n_shards, B, Hq, hd) -> out (B, Hq, hd).
    The log-sum-exp merge above; shards with no valid positions (m = -inf)
    contribute zero weight.
    """
    m_glob = m.max(axis=0)
    m_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_glob = (l * corr).sum(axis=0)
    acc_glob = (acc * corr[..., None]).sum(axis=0)
    return acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]


def sp_decode_attention(mesh: Mesh, axis: str) -> Callable:
    """Build the sharded decode-attention step for ``mesh``.

    Returns ``fn(q, k, v, lengths) -> (B, Hq, hd)`` where k/v are sharded
    on their sequence dim over mesh axis ``axis`` and ``lengths`` (B,)
    gives each row's valid cache length.  Inside the shard_map each device
    computes stats over its slice, all-gathers the (tiny) stats, and merges
    — one collective of O(B·Hq·hd) instead of moving the KV cache.
    """
    def local(q, k, v, lengths):
        S_loc = k.shape[1]
        shard = jax.lax.axis_index(axis)
        pos = shard * S_loc + jnp.arange(S_loc)
        valid = pos[None, :] < lengths[:, None]
        m, l, acc = local_decode_stats(q, k, v, valid)
        return combine_decode_stats(jax.lax.all_gather(m, axis),
                                    jax.lax.all_gather(l, axis),
                                    jax.lax.all_gather(acc, axis))

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(), P(None, axis), P(None, axis), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)
