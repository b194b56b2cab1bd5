"""Decoder-only transformer LM (dense GQA + MoE variants).

Functional implementation designed for pjit/SPMD at pod scale:

* layers are parameter-stacked and iterated with ``lax.scan`` (small HLO,
  fast multi-pod compiles) with a configurable remat policy;
* attention is chunked online-softmax (flash-style) so the dry-run memory
  analysis reflects the production kernel (kernels/flash_attn is the TPU
  Pallas version of the same math);
* cross-entropy is computed in sequence chunks against the (possibly
  vocab-sharded) unembedding so full (B,S,V) logits never materialise;
* MoE uses capacity-based scatter dispatch (Switch/GShard semantics with
  per-group capacity) — data movement instead of dense one-hot einsums, so
  HLO FLOPs match the true active-parameter cost;
* decode keeps a (L, B, S, Hkv, hd) KV cache; long-context decode shards the
  cache on the sequence axis (SP) and XLA SPMD turns the softmax reductions
  into all-reduces (distributed flash-decoding).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import TransformerConfig
from .layers import apply_rope, dense_init, embed_init, gqa_attention, rms_norm

Params = Dict[str, Any]


def _dt(cfg: TransformerConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    dt = _dt(cfg)
    L, D, hd = cfg.n_layers, cfg.d_model, cfg.head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 16)

    def stack(initfn, k, *shape_args):
        kk = jax.random.split(k, L)
        return jnp.stack([initfn(kk[i], *shape_args) for i in range(L)])

    layers: Params = {
        "ln1": jnp.ones((L, D), dt),
        "ln2": jnp.ones((L, D), dt),
        "wq": stack(lambda k: dense_init(k, D, Hq * hd, dt), ks[0]),
        "wk": stack(lambda k: dense_init(k, D, Hkv * hd, dt), ks[1]),
        "wv": stack(lambda k: dense_init(k, D, Hkv * hd, dt), ks[2]),
        "wo": stack(lambda k: dense_init(k, Hq * hd, D, dt,
                                         scale=1.0 / math.sqrt(Hq * hd * L)), ks[3]),
    }
    if cfg.moe is None:
        F = cfg.d_ff
        layers.update({
            "w_gate": stack(lambda k: dense_init(k, D, F, dt), ks[4]),
            "w_up": stack(lambda k: dense_init(k, D, F, dt), ks[5]),
            "w_down": stack(lambda k: dense_init(k, F, D, dt,
                                                 scale=1.0 / math.sqrt(F * L)), ks[6]),
        })
    else:
        E, Fe = cfg.moe.n_experts, cfg.moe.d_expert

        def einit(k, din, dout, scale=None):
            kk = jax.random.split(k, E)
            return jnp.stack([dense_init(kk[i], din, dout, dt, scale) for i in range(E)])

        layers.update({
            "router": stack(lambda k: dense_init(k, D, E, jnp.float32), ks[4]),
            "we_gate": stack(lambda k: einit(k, D, Fe), ks[5]),
            "we_up": stack(lambda k: einit(k, D, Fe), ks[6]),
            "we_down": stack(lambda k: einit(k, Fe, D, 1.0 / math.sqrt(Fe * L)), ks[7]),
        })
        if cfg.moe.n_shared_experts:
            Fs = cfg.moe.n_shared_experts * Fe
            layers.update({
                "ws_gate": stack(lambda k: dense_init(k, D, Fs, dt), ks[8]),
                "ws_up": stack(lambda k: dense_init(k, D, Fs, dt), ks[9]),
                "ws_down": stack(lambda k: dense_init(k, Fs, D, dt,
                                                      scale=1.0 / math.sqrt(Fs * L)), ks[10]),
            })
    params: Params = {
        "embed": embed_init(ks[11], cfg.vocab_size, D, dt),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[12], D, cfg.vocab_size, dt)
    return params


def unembed_matrix(cfg: TransformerConfig, params: Params) -> jnp.ndarray:
    return params["embed"].T if cfg.tie_embeddings else params["unembed"]


# ---------------------------------------------------------------------------
# MoE dispatch (capacity-based scatter; Switch/GShard token-drop semantics)
# ---------------------------------------------------------------------------

def moe_capacity(m_tokens: int, k: int, n_experts: int, cf: float = 1.25) -> int:
    return max(1, int(math.ceil(m_tokens * k / n_experts * cf)))


from .layers import maybe_constrain as _constrain  # noqa: E402


def moe_ffn(x: jnp.ndarray, lp: Params, cfg: TransformerConfig,
            capacity_factor: Optional[float] = None,
            batch_axes: str = "__data__"
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (G, M, D) token groups. Returns (out, aux_loss).

    batch_axes: which pseudo mesh axes carry the token groups ("__data__"
    under the TP strategy, "__all__" under FSDP when experts cannot use
    the model axis) — must match the sharding of the incoming activations
    or SPMD replicates the (G,E,C,D) dispatch buffers."""
    G, M, D = x.shape
    E, K = cfg.moe.n_experts, cfg.moe.top_k
    if capacity_factor is None:
        capacity_factor = cfg.moe.capacity_factor
    C = moe_capacity(M, K, E, capacity_factor)
    dt = x.dtype

    logits = jnp.einsum("gmd,de->gme", x.astype(jnp.float32), lp["router"])
    probs = jax.nn.softmax(logits, axis=-1)                       # (G,M,E)
    topv, topi = jax.lax.top_k(probs, K)                          # (G,M,K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss.
    me = probs.mean(axis=(0, 1))                                  # (E,)
    ce_frac = jax.nn.one_hot(topi[..., 0], E).mean(axis=(0, 1))
    aux = cfg.moe.router_aux_coef * E * jnp.sum(me * ce_frac)

    # position of each (token, slot) within its expert, per group.
    # Sort-based ranking (MaxText-style): the (G, M*K, E) one-hot cumsum
    # would be TBs at pod scale; argsort by expert id + rank-within-run is
    # O(G * MK log MK) ints and yields identical (token-order-stable) slots.
    eid_flat = topi.reshape(G, M * K)
    order = jnp.argsort(eid_flat, axis=1, stable=True)            # (G,MK)
    sorted_e = jnp.take_along_axis(eid_flat, order, axis=1)
    idx = jnp.broadcast_to(jnp.arange(M * K)[None], (G, M * K))
    new_run = jnp.concatenate(
        [jnp.ones((G, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    run_start = jax.lax.cummax(jnp.where(new_run, idx, 0), axis=1)
    rank_sorted = idx - run_start                                  # (G,MK)
    pos_flat = jnp.zeros((G, M * K), jnp.int32).at[
        jnp.arange(G)[:, None], order].set(rank_sorted.astype(jnp.int32))
    pos_sel = pos_flat.reshape(G, M, K)

    tok_idx = jnp.broadcast_to(jnp.arange(M)[None, :, None], (G, M, K))
    src = _constrain(
        jnp.take_along_axis(x, tok_idx.reshape(G, M * K)[..., None], axis=1),
        batch_axes, None, None)

    # dispatch-buffer layout: token groups stay data-parallel, experts go
    # EP — without these constraints SPMD replicates (G,E,C,D) on every
    # chip. When E does not divide the model axis (granite: 40 experts /
    # tp16) the capacity dim carries the model sharding instead
    # (TP-within-expert layout). The zero buffer is pinned BEFORE the
    # scatter so the scatter itself is partitioned.
    _msize = dict(jax.sharding.get_abstract_mesh().shape).get("model", 1)
    if E % max(_msize, 1) == 0 and batch_axes == "__data__":
        _spec = ("__data__", "model", None, None)     # EP layout
    elif batch_axes == "__data__":
        _spec = ("__data__", None, "model", None)     # TP-within-expert
    else:
        _spec = ("__all__", None, None, None)         # FSDP: batch-parallel

    def pin(t):
        return _constrain(t, *_spec)

    eidf = topi.reshape(G, M * K)
    posf = pos_sel.reshape(G, M * K)
    buf0 = pin(jnp.zeros((G, E, C, D), dt))

    def scatter_one(buf_g, xsrc, eid, p):
        return buf_g.at[eid, p].set(xsrc, mode="drop")

    buf = pin(jax.vmap(scatter_one)(buf0, src, eidf, posf))       # (G,E,C,D)
    # expert SwiGLU (experts sharded on the model axis -> EP)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, lp["we_gate"])) \
        * jnp.einsum("gecd,edf->gecf", buf, lp["we_up"])
    h = pin(h)
    y = jnp.einsum("gecf,efd->gecd", h, lp["we_down"])            # (G,E,C,D)
    y = pin(y)

    def gather_one(yb, eid, p):
        out = yb.at[eid.clip(0, E - 1), p].get(mode="fill", fill_value=0)
        return out  # (M*K, D)

    back = jax.vmap(gather_one)(y, eidf, posf)                    # (G,M*K,D)
    back = back.reshape(G, M, K, D) * topv[..., None].astype(dt)
    out = back.sum(axis=2)

    if cfg.moe.n_shared_experts:
        hs = jax.nn.silu(jnp.einsum("gmd,df->gmf", x, lp["ws_gate"])) \
            * jnp.einsum("gmd,df->gmf", x, lp["ws_up"])
        out = out + jnp.einsum("gmf,fd->gmd", hs, lp["ws_down"])
    return out, aux


def dense_ffn(x: jnp.ndarray, lp: Params) -> jnp.ndarray:
    h = jax.nn.silu(jnp.einsum("...d,df->...f", x, lp["w_gate"])) \
        * jnp.einsum("...d,df->...f", x, lp["w_up"])
    return jnp.einsum("...f,fd->...d", h, lp["w_down"])


# ---------------------------------------------------------------------------
# transformer block
# ---------------------------------------------------------------------------

def block(x: jnp.ndarray, lp: Params, cfg: TransformerConfig, *,
          positions: jnp.ndarray, attn_chunk: int = 1024,
          moe_batch_axes: str = "__data__"
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One pre-norm block. x: (B, S, D). Returns (x, moe_aux)."""
    B, S, D = x.shape
    hd, Hq, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads

    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dk->bsk", h, lp["wq"]).reshape(B, S, Hq, hd)
    k = jnp.einsum("bsd,dk->bsk", h, lp["wk"]).reshape(B, S, Hkv, hd)
    v = jnp.einsum("bsd,dk->bsk", h, lp["wv"]).reshape(B, S, Hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = gqa_attention(q, k, v, causal=True, chunk=attn_chunk)
    x = x + jnp.einsum("bsk,kd->bsd", o.reshape(B, S, Hq * hd), lp["wo"])

    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.moe is None:
        y = dense_ffn(h, lp)
        aux = jnp.zeros((), jnp.float32)
    else:
        y, aux = moe_ffn(h, lp, cfg, batch_axes=moe_batch_axes)
    return x + y, aux


def forward(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig, *,
            attn_chunk: int = 1024, remat: bool = True,
            scan_layers: bool = True,
            gather_layer_weights: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S) -> final hidden (B, S, D), total moe aux loss.

    gather_layer_weights: FSDP mode — layer weights live sharded across the
    whole mesh and are all-gathered per scan iteration (layers.maybe_replicate).
    """
    B, S = tokens.shape
    x = params["embed"].at[tokens].get(mode="clip")               # (B,S,D)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(carry, lp):
        x, aux = carry
        if gather_layer_weights:
            from .layers import maybe_replicate
            # expert weights stay EP-sharded; gathering them per layer
            # moves E x more bytes than the tokens they process.
            lp = {k: (v if k.startswith("we_")
                      else jax.tree.map(maybe_replicate, v))
                  for k, v in lp.items()}
        x, a = block(x, lp, cfg, positions=positions, attn_chunk=attn_chunk,
                     moe_batch_axes=("__all__" if gather_layer_weights
                                     else "__data__"))
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    aux0 = jnp.zeros((), jnp.float32)
    if scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
    else:
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            (x, aux), _ = body((x, aux0), lp)
            aux0 = aux
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------

def chunked_ce_loss(hidden: jnp.ndarray, labels: jnp.ndarray,
                    unembed: jnp.ndarray, n_chunks: int = 8) -> jnp.ndarray:
    """Cross-entropy without materialising (B,S,V) logits.

    hidden: (B,S,D); labels: (B,S) with -1 = ignore; unembed: (D,V).
    Scans over sequence chunks; inside a chunk the (B,c,V) logits live only
    transiently (and V may be sharded -> vocab-parallel CE).
    """
    B, S, D = hidden.shape
    n_chunks = min(n_chunks, S)
    while S % n_chunks:
        n_chunks -= 1
    c = S // n_chunks
    hc = hidden.reshape(B, n_chunks, c, D).swapaxes(0, 1)         # (n,B,c,D)
    lc = labels.reshape(B, n_chunks, c).swapaxes(0, 1)

    def body(carry, inp):
        tot, cnt = carry
        h, l = inp
        # bf16 operands, f32 accumulation: no f32 copy of the (D,V)
        # unembedding is materialised/gathered per chunk (§Perf iter C2)
        logits = jax.lax.dot_general(
            h, unembed, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l.clip(0)[..., None], axis=-1)[..., 0]
        valid = (l >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((lse - gold) * valid)
        cnt = cnt + valid.sum()
        return (tot, cnt), None

    if n_chunks == 1:
        (tot, cnt), _ = body((0.0, 0.0), (hc[0], lc[0]))
    else:
        (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params: Params, batch: Dict[str, jnp.ndarray], cfg: TransformerConfig,
            *, attn_chunk: int = 1024, ce_chunks: int = 8,
            remat: bool = True, scan_layers: bool = True,
            gather_layer_weights: bool = False) -> jnp.ndarray:
    hidden, aux = forward(params, batch["tokens"], cfg, attn_chunk=attn_chunk,
                          remat=remat, scan_layers=scan_layers,
                          gather_layer_weights=gather_layer_weights)
    ce = chunked_ce_loss(hidden, batch["labels"], unembed_matrix(cfg, params),
                         n_chunks=ce_chunks)
    return ce + aux


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jnp.ndarray  # (L, B, S, Hkv, hd)
    v: jnp.ndarray  # (L, B, S, Hkv, hd)
    length: jnp.ndarray  # (B,) valid lengths


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None) -> KVCache:
    dt = dtype or _dt(cfg)
    sh = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(sh, dt), jnp.zeros(sh, dt),
                   jnp.zeros((batch,), jnp.int32))


def decode_step(params: Params, cache: KVCache, tokens: jnp.ndarray,
                cfg: TransformerConfig) -> Tuple[jnp.ndarray, KVCache]:
    """One autoregressive step. tokens: (B,) -> logits (B, V), new cache.

    The cache sequence axis may be sharded (SP); attention reductions over it
    become all-reduces under SPMD (distributed flash-decoding schedule).
    """
    B = tokens.shape[0]
    D, hd, Hq, Hkv = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    x = params["embed"].at[tokens].get(mode="clip")[:, None]      # (B,1,D)
    pos = cache.length[:, None]                                    # (B,1)

    def body(x, inp):
        lp, kc, vc = inp
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dk->bsk", h, lp["wq"]).reshape(B, 1, Hq, hd)
        k = jnp.einsum("bsd,dk->bsk", h, lp["wk"]).reshape(B, 1, Hkv, hd)
        v = jnp.einsum("bsd,dk->bsk", h, lp["wv"]).reshape(B, 1, Hkv, hd)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        # write the new KV at position `length` (dynamic per-batch scatter)
        bidx = jnp.arange(B)
        kc = kc.at[bidx, cache.length].set(k[:, 0])
        vc = vc.at[bidx, cache.length].set(v[:, 0])
        o = gqa_attention(q, kc, vc, causal=False,
                          chunk=min(kc.shape[1], 4096),
                          kv_valid_len=cache.length + 1)
        x = x + jnp.einsum("bsk,kd->bsd", o.reshape(B, 1, Hq * hd), lp["wo"])
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is None:
            y = dense_ffn(h, lp)
        else:
            y, _ = moe_ffn(h.reshape(B, 1, D), lp, cfg)
            y = y.reshape(B, 1, D)
        return x + y, (kc, vc)

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache.k, cache.v))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        unembed_matrix(cfg, params).astype(jnp.float32))
    return logits[:, 0], KVCache(nk, nv, cache.length + 1)


def prefill(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig, *,
            attn_chunk: int = 1024) -> jnp.ndarray:
    """Full-prompt forward; returns next-token logits (B, V)."""
    hidden, _ = forward(params, tokens, cfg, attn_chunk=attn_chunk)
    last = hidden[:, -1]
    return jnp.einsum("bd,dv->bv", last.astype(jnp.float32),
                      unembed_matrix(cfg, params).astype(jnp.float32))
