"""Plain DeepTileBars (Tang & Yang, AAAI 2019) over SEINE's tf,
idf_indicator and gauss_max functions.

Each query term's row of the (Q, n_b) tile bar has three channels: tf over
the segment's length, the indicative idf, and the Gaussian-kernel best
match.  Convolutions of widths 1-5 (8 filters each, zero past the last
segment) run along the segments; each is ReLU'd, masked to non-empty
segments, and max- and mean-pooled.  The 80 features are averaged over the
real query terms and scored by an 80-32-1 ReLU MLP.
"""
import jax
import jax.numpy as jnp

WIDTHS = (1, 2, 3, 4, 5)
N_FILT = 8
CHANNELS = ("tf", "idf_indicator", "gauss_max")


def init(key, n_b: int):
    """Weights in the layout the program's deeptilebars scorer takes: a
    width-w filter bank is ``(len(CHANNELS) * w, N_FILT)`` with row
    ``c * w + i`` the weight of channel c at offset i."""
    ks = jax.random.split(key, 2 * len(WIDTHS) + 4)
    c = len(CHANNELS)
    convs = [{"w": jax.random.normal(ks[2 * j], (w * c, N_FILT))
              / jnp.sqrt(w * c),
              "b": 0.1 * jax.random.normal(ks[2 * j + 1], (N_FILT,))}
             for j, w in enumerate(WIDTHS)]
    d = 2 * len(WIDTHS) * N_FILT
    mlp = {"w": [jax.random.normal(ks[-4], (d, 32)) / jnp.sqrt(d),
                 jax.random.normal(ks[-3], (32, 1)) / jnp.sqrt(32.0)],
           "b": [0.1 * jax.random.normal(ks[-2], (32,)),
                 0.1 * jax.random.normal(ks[-1], (1,))]}
    return {"convs": convs, "mlp": mlp}


def score(params, m, q_valid, doc_len, seg_len, functions, mm):
    """m (B, Q, n_b, n_f) -> scores (B,)."""
    n_b = m.shape[2]
    img = jnp.stack([m[..., functions.index(c)] for c in CHANNELS], -1)
    img = img.at[..., 0].divide(jnp.maximum(seg_len, 1.0)[:, None, :])
    seg_mask = (seg_len > 0).astype(jnp.float32)[:, None, :, None]
    n_seg = jnp.maximum(seg_mask.sum(2), 1.0)
    feats = []
    for w, p in zip(WIDTHS, params["convs"]):
        x = jnp.pad(img, ((0, 0), (0, 0), (0, w - 1), (0, 0)))
        filt = p["w"].reshape(len(CHANNELS), w, N_FILT)
        h = sum(mm("bqsc,cf->bqsf", x[:, :, i:i + n_b], filt[:, i])
                for i in range(w))
        h = jax.nn.relu(h + p["b"]) * seg_mask
        feats += [h.max(2), h.sum(2) / n_seg]
    f = jnp.concatenate(feats, -1) * q_valid[None, :, None]
    pooled = f.sum(1) / jnp.maximum(q_valid.sum(), 1.0)
    (w1, w2), (b1, b2) = params["mlp"]["w"], params["mlp"]["b"]
    h = jax.nn.relu(mm("bd,dk->bk", pooled, w1) + b1)
    return mm("bk,ko->bo", h, w2)[:, 0] + b2[0]
