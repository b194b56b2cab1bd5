"""The provider seam: the hash provider's reference, moved into
``bench/providers/hash.py``, gives the plain reference the same M and
scores, bit for bit, as the inline computation it replaced; the table it
draws is the program's; and a configuration names a provider file that
exists.

``_frozen_block`` is a frozen copy of the reference's block before the
seam (its contextual embeddings inline, ``ALPHA`` = 0.25, the table drawn
eagerly from the seed).  It stays as it is: it is what the seam is held
to.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import corpus as corpus_mod
from bench import harness as H
from bench import reference as ref

SIZE = dict(n_docs=96, n_queries=8, max_len=160)


def _frozen_interactions(tokens, segs, query, table, idf, ip, *, n_b,
                         functions, sigma, max_uniq, precision):
    mmp = functools.partial(ref.mm, precision=precision)
    valid = tokens >= 0
    qv = query >= 0
    vf = valid.astype(jnp.float32)
    S = jax.nn.one_hot(jnp.where(valid, segs, n_b), n_b,
                       dtype=jnp.float32)
    E = table[tokens.clip(0)] * vf[..., None]
    eq = table[query.clip(0)] * qv[:, None]
    match = ((query[None, :, None] == tokens[:, None, :])
             & valid[:, None, :] & qv[None, :, None]).astype(jnp.float32)
    tf = mmp("bql,bls->bqs", match, S)
    cnt = S.sum(1)

    seg_mean = mmp("bls,bld->bsd", S, E) / jnp.maximum(cnt, 1.0)[..., None]
    ctx = (E + 0.25 * mmp("bls,bsd->bld", S, seg_mean)) * vf[..., None]
    in_seg = S > 0

    def seg_max(x):
        x = jnp.where(in_seg[:, None], x[..., None], -jnp.inf)
        return x.max(2)

    occ = match[..., None] * S[:, None]
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
            un = ref._unit(E) * vf[..., None]
            out.append(mmp("qd,bsd->bqs", ref._unit(eq),
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
            g = jnp.take(logp, query.clip(0), axis=2)
            out.append(jnp.swapaxes(g, 1, 2) * qv[None, :, None])
    vals = jnp.stack(out, -1) * qv[None, :, None, None]

    srt = jnp.sort(jnp.where(valid, tokens, jnp.iinfo(jnp.int32).max), 1)
    first = (srt < jnp.iinfo(jnp.int32).max) & jnp.concatenate(
        [jnp.ones_like(srt[:, :1], bool), srt[:, 1:] != srt[:, :-1]], 1)
    rank = ((srt[:, None, :] < query[None, :, None])
            & first[:, None, :]).sum(-1)
    stored = qv[None] & (tf.sum(-1) > sigma) & (rank < max_uniq)
    return vals * stored[..., None, None]


@functools.partial(jax.jit, static_argnames=(
    "n_b", "functions", "sigma", "max_uniq", "precision", "score_fn"))
def _frozen_block(tokens, segs, query, table, idf, ip, params, *, n_b,
                  functions, sigma, max_uniq, precision, score_fn):
    m = _frozen_interactions(tokens, segs, query, table, idf, ip, n_b=n_b,
                             functions=functions, sigma=sigma,
                             max_uniq=max_uniq, precision=precision)
    doc_len, seg_len = ref.doc_meta(tokens, segs, n_b)
    s = score_fn(params, m, query >= 0, doc_len, seg_len, functions,
                 functools.partial(ref.mm, precision=precision))
    return m, s


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.fixture(scope="module", params=["mq2007-knrm.rerank1k",
                                        "mq2008-deeptilebars.letor"])
def world(request):
    cfg = dict(H.load_cell(request.param).config, **SIZE)
    seed = 2**33 + 29
    c = corpus_mod.generate(cfg, seed)
    ip, params = H.make_weights(cfg, seed)
    return cfg, seed, c, ip, params


@pytest.mark.parametrize("precision", ["highest", "high", ref.BELOW_BF16])
def test_hash_reference_is_bitwise_the_inline_one(world, precision):
    """M and scores through ``bench/providers/hash.py`` equal the frozen
    inline computation bit for bit, at ``highest``, at ``high`` (the
    control's precision) and below bfloat16."""
    cfg, seed, c, ip, params = world
    hp = H.provider("hash")
    w = hp.weights(cfg, seed, c.vocab_size)
    table = jax.random.normal(jax.random.key(H.key_seed(seed, 10)),
                              (c.vocab_size, cfg["embed_dim"]),
                              dtype=jnp.float32) / jnp.sqrt(cfg["embed_dim"])
    score_fn = H.ranker(cfg["ranker"]).score
    funcs = tuple(cfg["functions"])
    kw = dict(n_b=cfg["n_segments"], functions=funcs,
              sigma=float(cfg["sigma_index"]), max_uniq=128,
              precision=precision, score_fn=score_fn)
    q = c.queries[0]
    docs = np.arange(24, dtype=np.int32)
    idf = jnp.asarray(c.idf)
    m0, s0 = _frozen_block(jnp.asarray(c.tokens[docs]),
                           jnp.asarray(c.segs[docs]), jnp.asarray(q), table,
                           idf, ip, params, **kw)

    def embed(weights, tokens, segs, prec):
        return hp.reference(weights, tokens, segs, cfg, prec)

    (m1, s1), = ref.score_requests(
        [(q, docs)], c.tokens, c.segs, embed, w, idf, ip, params, score_fn,
        n_b=kw["n_b"], functions=funcs, sigma=kw["sigma"], max_uniq=128,
        precision=precision, block=24)
    assert np.array_equal(_bits(m0), _bits(m1))
    assert np.array_equal(_bits(s0), _bits(s1))
    assert np.abs(np.asarray(m1)).max() > 0


def test_hash_table_is_the_programs(world):
    """The reference's table is drawn on its own, in one jitted call, and
    is bit for bit the table ``HashProvider`` draws from the same seed."""
    from repro.core import HashProvider

    cfg, seed, c, _, _ = world
    hp = H.provider("hash")
    w = hp.weights(cfg, seed, c.vocab_size)
    prog = hp.program(cfg, w, c.vocab_size)
    assert isinstance(prog, HashProvider)
    assert int(w["seed"]) == H.key_seed(seed, hp.STREAM)
    assert np.array_equal(_bits(prog.table()), _bits(w["table"]))
    ctx_prog = prog.contextualize(jnp.asarray(c.tokens[0]),
                                  jnp.asarray(c.segs[0]))
    _, ctx_ref = hp.reference(w, jnp.asarray(c.tokens[:1]),
                              jnp.asarray(c.segs[:1]), cfg)
    np.testing.assert_allclose(np.asarray(ctx_ref[0]),
                               np.asarray(ctx_prog), rtol=1e-5, atol=1e-6)


def test_mm_below_bf16_is_highest_on_e4m3_operands():
    """``mm`` at ``BELOW_BF16`` is the ``highest`` product of operands
    rounded to float8 e4m3, and lies further from ``highest`` than one
    bfloat16 pass does (operands rounded to bfloat16): so it is below a
    model that states bfloat16."""
    k = jax.random.split(jax.random.key(2**31 + 7), 2)
    a = jax.random.normal(k[0], (64, 256), jnp.float32)
    b = jax.random.normal(k[1], (256, 32), jnp.float32)
    got = ref.mm("ij,jk->ik", a, b, ref.BELOW_BF16)

    def rounded(x, e, m):
        return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)

    want = jnp.einsum("ij,jk->ik", rounded(a, 4, 3), rounded(b, 4, 3),
                      precision=jax.lax.Precision.HIGHEST)
    assert np.array_equal(_bits(got), _bits(want))
    exact = ref.mm("ij,jk->ik", a, b)
    one_pass = jnp.einsum("ij,jk->ik", rounded(a, 8, 7), rounded(b, 8, 7),
                          precision=jax.lax.Precision.HIGHEST)
    gap_fp8 = float(jnp.sqrt(jnp.mean((got - exact) ** 2)))
    gap_bf16 = float(jnp.sqrt(jnp.mean((one_pass - exact) ** 2)))
    assert gap_fp8 > 4 * gap_bf16 > 0
    with pytest.raises(ValueError):
        ref.mm("ij,jk->ik", a, b, "fp8")


def test_provider_references_import_nothing_of_the_program():
    """``bench/reference.py`` and every provider file import nothing of
    ``repro`` outside a provider's ``program``."""
    import ast
    import os

    paths = [os.path.join(H.BENCH, "reference.py")] + [
        os.path.join(H.BENCH, "providers", f)
        for f in os.listdir(os.path.join(H.BENCH, "providers"))
        if f.endswith(".py")]
    for path in paths:
        with open(path) as f:
            tree = ast.parse(f.read())
        for top in tree.body:
            if isinstance(top, ast.FunctionDef) and top.name == "program":
                continue
            for node in ast.walk(top):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    names = [node.module or ""]
                else:
                    continue
                assert all(n.split(".")[0] != "repro" for n in names), \
                    (path, getattr(top, "name", None))
