"""The plain reference: SEINE's interaction matrix straight from the text.

For a query and a block of candidate documents it computes M_{q,d}
``(B, Q, n_b, n_f)`` from the documents' tokens and segments, the
embedding table, the idf and the interaction weights, by the definitions
of the paper's nine atomic functions (section 2.3) and of the builder's
contract: a (term, doc) pair holds values only where the term occurs in
the doc (tf > sigma) and is among the doc's ``max_uniq`` smallest vocabulary
slots; every other pair, and every padded query slot, is zero.

It imports nothing of the program and takes nothing the program made: the
static table and the contextual embeddings come from the configuration's
provider file (``bench/providers/<provider>.py``, its ``reference``), from
the benchmark's own weights, and tokens, segments, idf and interaction
weights are the benchmark's own.  Each function is written in its plain
form (segment means before projections, distances as sums of squared
differences), not in the factored forms the builder uses.

Every contraction goes through :func:`mm`, so the control can run the same
code with each matrix product at ``high`` (three bfloat16 passes, emulated
so that it means the same on any backend) instead of ``highest``, and a
provider that states bfloat16 can run its part at :data:`BELOW_BF16`.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the one precision below a bfloat16 model: float8 e4m3 operands
BELOW_BF16 = "fp8_e4m3"


def _bf16_parts(x):
    """x = hi + lo + rest, hi and lo rounded to bfloat16 (8 exponent, 7
    mantissa bits).  ``reduce_precision`` is kept by the compiler, where a
    cast to bfloat16 and back may be folded away as excess precision."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                        mantissa_bits=7)


def _e4m3(x):
    """x rounded to float8 e4m3 (4 exponent, 3 mantissa bits), by IEEE
    rules: the largest finite value is 240, and beyond it lies inf."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def mm(spec: str, a, b, precision: str = "highest"):
    """``einsum(spec, a, b)`` in float32 at ``highest``; at ``high``, the
    bfloat16 three-pass product ``hi*hi + hi*lo + lo*hi``; or at
    :data:`BELOW_BF16` (``"fp8_e4m3"``), the product at ``highest`` of
    operands rounded to float8 e4m3.

    ``fp8_e4m3`` is the control of a model that states bfloat16 weights and
    activations with float32 accumulation: it has to lie below that
    precision.  Rounding each product's result to bfloat16 would not: a
    bfloat16 deployment already rounds its results so."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == BELOW_BF16:
        return jnp.einsum(spec, _e4m3(a), _e4m3(b), precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _bf16_parts(a)
    bh, bl = _bf16_parts(b)
    return (jnp.einsum(spec, ah, bh, precision=HIGHEST)
            + jnp.einsum(spec, ah, bl, precision=HIGHEST)
            + jnp.einsum(spec, al, bh, precision=HIGHEST))


def _unit(x):
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)),
                           1e-9)


def interactions(tokens, segs, query, table, ctx, idf, ip, *, n_b: int,
                 functions: Sequence[str], sigma: float, max_uniq: int,
                 precision: str = "highest"):
    """tokens/segs ``(B, L)``, query ``(Q,)`` -> M ``(B, Q, n_b, n_f)``.

    ``table`` ``(|v|, D)`` is the provider's static table, ``ctx``
    ``(B, L, D)`` its contextual embeddings of the documents' tokens (zero
    at padding)."""
    mmp = functools.partial(mm, precision=precision)
    valid = tokens >= 0
    qv = query >= 0
    vf = valid.astype(jnp.float32)
    S = jax.nn.one_hot(jnp.where(valid, segs, n_b), n_b,
                       dtype=jnp.float32)                   # (B, L, n_b)
    E = table[tokens.clip(0)] * vf[..., None]               # (B, L, D)
    eq = table[query.clip(0)] * qv[:, None]                 # (Q, D)
    match = ((query[None, :, None] == tokens[:, None, :])
             & valid[:, None, :] & qv[None, :, None]).astype(jnp.float32)
    tf = mmp("bql,bls->bqs", match, S)                      # (B, Q, n_b)
    cnt = S.sum(1)                                          # (B, n_b)
    in_seg = S > 0                                          # (B, L, n_b)

    def seg_max(x):       # x (B, Q, L) -> max over each segment's tokens
        x = jnp.where(in_seg[:, None], x[..., None], -jnp.inf)
        return x.max(2)                                     # (B, Q, n_b)

    occ = match[..., None] * S[:, None]                     # (B, Q, L, n_b)
    occ_mean = occ / jnp.maximum(tf, 1.0)[:, :, None, :]
    out = []
    for fn in functions:
        if fn == "tf":
            out.append(tf)
        elif fn == "idf_indicator":
            out.append(jnp.broadcast_to(
                (idf[query.clip(0)] * qv)[None, :, None] * 1.0, tf.shape)
                * (tf > 0))
        elif fn == "dot":
            out.append(mmp("qd,bsd->bqs", eq,
                           mmp("bls,bld->bsd", S, E)))
        elif fn == "cosine":
            un = _unit(E) * vf[..., None]
            out.append(mmp("qd,bsd->bqs", _unit(eq),
                           mmp("bls,bld->bsd", S, un)) * qv[None, :, None])
        elif fn == "gauss_max":
            d2 = jnp.sum((eq[None, :, None, :] - E[:, None, :, :]) ** 2, -1)
            out.append(jnp.exp(seg_max(-d2)))
        elif fn == "linear_agg":
            mean_ctx = mmp("bqls,bld->bqsd", occ_mean, ctx)
            out.append(mmp("bqsd,d->bqs", mean_ctx, ip["a"]) + ip["b"])
        elif fn == "max_op":
            f = jnp.log(jax.nn.softplus(ctx) + 1e-9)
            v = seg_max(mmp("qd,bld->bql", eq, f))
            out.append(jnp.where(jnp.isfinite(v), v, 0.0))
        elif fn == "mlp_emb":
            mean_ctx = mmp("bqls,bld->bqsd", occ_mean, ctx)
            (w1, w2), (b1, b2) = ip["mlp"]["w"], ip["mlp"]["b"]
            h = jax.nn.relu(mmp("bqsd,dk->bqsk", mean_ctx, w1) + b1)
            out.append(mmp("bqsk,ko->bqso", h, w2)[..., 0] + b2[0])
        elif fn == "log_cond_prob":
            ctx_mean = mmp("bls,bld->bsd", S, ctx) \
                / jnp.maximum(cnt, 1.0)[..., None]
            logits = mmp("bsd,vd->bsv", ctx_mean, table)
            logp = logits - jax.nn.logsumexp(logits, -1, keepdims=True)
            g = jnp.take(logp, query.clip(0), axis=2)       # (B, n_b, Q)
            out.append(jnp.swapaxes(g, 1, 2) * qv[None, :, None])
        else:
            raise ValueError(f"unknown atomic function {fn!r}")
    vals = jnp.stack(out, -1) * qv[None, :, None, None]

    # the builder's contract: which (term, doc) pairs hold values
    srt = jnp.sort(jnp.where(valid, tokens, jnp.iinfo(jnp.int32).max), 1)
    first = (srt < jnp.iinfo(jnp.int32).max) & jnp.concatenate(
        [jnp.ones_like(srt[:, :1], bool), srt[:, 1:] != srt[:, :-1]], 1)
    rank = ((srt[:, None, :] < query[None, :, None])
            & first[:, None, :]).sum(-1)                    # (B, Q)
    stored = qv[None] & (tf.sum(-1) > sigma) & (rank < max_uniq)
    return vals * stored[..., None, None]


def doc_meta(tokens, segs, n_b: int):
    """Per-doc (doc_len (B,), seg_len (B, n_b)) in valid tokens."""
    valid = tokens >= 0
    seg_len = jax.nn.one_hot(jnp.where(valid, segs, n_b), n_b,
                             dtype=jnp.float32).sum(1)
    return valid.sum(1).astype(jnp.float32), seg_len


@functools.partial(jax.jit, static_argnames=(
    "n_b", "functions", "sigma", "max_uniq", "precision", "score_fn",
    "embed"))
def _block(tokens, segs, query, weights, idf, ip, params, *, n_b, functions,
           sigma, max_uniq, precision, score_fn, embed):
    table, ctx = embed(weights, tokens, segs, precision)
    m = interactions(tokens, segs, query, table, ctx, idf, ip, n_b=n_b,
                     functions=functions, sigma=sigma, max_uniq=max_uniq,
                     precision=precision)
    doc_len, seg_len = doc_meta(tokens, segs, n_b)
    s = score_fn(params, m, query >= 0, doc_len, seg_len, functions,
                 functools.partial(mm, precision=precision))
    return m, s


def score_requests(requests, tokens, segs, embed, weights, idf, ip, params,
                   score_fn, *, n_b, functions, sigma, max_uniq,
                   precision="highest", block=64):
    """Reference ``(M, scores)`` per ``(query, doc_ids)`` request, computed
    in blocks of ``block`` docs padded to one shape.  Host arrays out.

    ``embed(weights, tokens, segs, precision) -> (table, ctx)`` is the
    provider's reference, ``weights`` the provider's arrays."""
    functions = tuple(functions)
    weights = jax.device_put(weights)
    out = []
    for q, docs in requests:
        ms, ss = [], []
        for s in range(0, len(docs), block):
            d = docs[s:s + block]
            n = len(d)
            d = np.concatenate([d, np.full(block - n, d[0], d.dtype)])
            m, sc = _block(jnp.asarray(tokens[d]), jnp.asarray(segs[d]),
                           jnp.asarray(q), weights, idf, ip, params,
                           n_b=n_b, functions=functions, sigma=float(sigma),
                           max_uniq=int(max_uniq), precision=precision,
                           score_fn=score_fn, embed=embed)
            ms.append(np.asarray(m)[:n])
            ss.append(np.asarray(sc)[:n])
        out.append((np.concatenate(ms), np.concatenate(ss)))
    return out
