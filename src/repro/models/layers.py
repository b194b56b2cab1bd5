"""Shared neural-net building blocks (pure-functional, pytree params).

No flax/optax in the offline container; params are plain dicts of jnp arrays,
init functions take explicit PRNG keys, forward functions are pure.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def maybe_constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """with_sharding_constraint iff the named mesh axes exist in context.

    Keeps models mesh-agnostic: under the production mesh big intermediates
    (MoE dispatch buffers, GNN edge messages) get pinned to the intended
    layout instead of letting SPMD replicate them; on a single device it is
    a no-op. The pseudo-axis "__data__" expands to every batch-parallel
    axis present ("pod", "data").
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = tuple(mesh.axis_names)
    if not names:
        return x

    def resolve(a):
        if a is None:
            return None
        if a == "__data__":
            present = tuple(n for n in ("pod", "data") if n in names)
            return present or None
        if a == "__all__":
            return names or None
        return a if a in names else None

    spec = list(resolve(a) for a in axes)
    # divisibility guard: shrink an axis tuple greedily (drop the leftmost
    # axis first — 'pod' before 'data'/'model') until it divides the dim;
    # drop entirely only if nothing divides.
    for i, s in enumerate(spec):
        if s is None:
            continue
        axes_list = list(s if isinstance(s, tuple) else (s,))
        while axes_list:
            n = 1
            for a in axes_list:
                n *= mesh.shape[a]
            if x.shape[i] % n == 0:
                break
            axes_list.pop(0)
        spec[i] = tuple(axes_list) if axes_list else None
    if all(s is None for s in spec):
        return x
    from jax.sharding import PartitionSpec as _P
    return jax.lax.with_sharding_constraint(x, _P(*spec))


def maybe_replicate(x: jnp.ndarray) -> jnp.ndarray:
    """Force-gather to replicated iff a mesh is in context.

    Used inside the layer-scan body under the FSDP strategy: constraining
    the SLICED layer weights to replicated places the all-gather inside the
    loop (it depends on the slice index, so XLA cannot hoist it), giving
    true per-layer gather/release instead of a whole-model gather."""
    if not tuple(jax.sharding.get_abstract_mesh().axis_names):
        return x
    from jax.sharding import PartitionSpec as _P
    return jax.lax.with_sharding_constraint(x, _P())


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale).astype(dtype)


def embed_init(key, n: int, d: int, dtype=jnp.float32, scale: float = 0.02):
    return (jax.random.normal(key, (n, d), dtype=jnp.float32) * scale).astype(dtype)


def mlp_init(key, dims: Tuple[int, ...], dtype=jnp.float32) -> Params:
    """Plain MLP param stack: dims = (d0, d1, ..., dn)."""
    keys = jax.random.split(key, len(dims) - 1)
    return {
        "w": [dense_init(keys[i], dims[i], dims[i + 1], dtype) for i in range(len(dims) - 1)],
        "b": [jnp.zeros((dims[i + 1],), dtype) for i in range(len(dims) - 1)],
    }


def mlp_apply(p: Params, x: jnp.ndarray, act=jax.nn.relu, final_act=None) -> jnp.ndarray:
    n = len(p["w"])
    for i in range(n):
        x = x @ p["w"][i] + p["b"][i]
        if i < n - 1:
            x = act(x)
        elif final_act is not None:
            x = final_act(x)
    return x


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked online-softmax (flash-style in pure JAX).
#
# The naive (B,H,S,S) score tensor at S=32k would be ~GBs/device; we instead
# scan over KV chunks maintaining running (max, denom, weighted-sum) — the
# same math as FlashAttention, which keeps compile-time memory analysis
# honest and is the dry-run stand-in for kernels/flash_attn.
# ---------------------------------------------------------------------------

def gqa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool, q_offset: int = 0,
                  chunk: int = 1024, kv_valid_len: Optional[jnp.ndarray] = None
                  ) -> jnp.ndarray:
    """Grouped-query attention.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D), Hq % Hkv == 0.
    q_offset: absolute position of q[0] (for causal masking in prefill chunks
    or decode). kv_valid_len: (B,) optional valid kv length (decode w/ cache).
    Returns (B, Sq, Hq, D).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    scale = 1.0 / math.sqrt(D)

    n_chunks = max(1, -(-Skv // chunk))
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, Hkv, D)
    vc = v.reshape(B, n_chunks, chunk, Hkv, D)

    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, c_idx = inp
        kv_pos = c_idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb.astype(jnp.float32)) * scale
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        mask &= (kv_pos < Skv)[None, :]
        if kv_valid_len is not None:
            s = jnp.where((kv_pos[None, :] < kv_valid_len[:, None])
                          [:, None, None, None, :] & mask[None, None, None],
                          s, -jnp.inf)
        else:
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    if n_chunks == 1:
        (m, l, acc), _ = body((m0, l0, a0), (kc[:, 0], vc[:, 0], jnp.asarray(0)))
    else:
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def naive_attention(q, k, v, *, causal: bool, q_offset: int = 0):
    """O(S^2)-memory reference attention (oracle for tests)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        qp = q_offset + jnp.arange(Sq)
        kp = jnp.arange(Skv)
        s = jnp.where((qp[:, None] >= kp[None, :])[None, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)
