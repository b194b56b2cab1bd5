"""Plain KNRM (Xiong et al., SIGIR 2017) over SEINE's cosine function.

The stored cosine of a (term, segment) pair is a sum over the segment's
tokens; divided by the segment's length it is the mean match signal in
[-1, 1].  Eleven RBF kernels (mu 1.0 with sigma 0.001 for exact matches,
then mu 0.9 .. -0.9 with sigma 0.1) soft-count it per segment, the counts
are log-pooled over segments and summed over query terms, and one linear
layer scores the eleven features.
"""
import jax
import jax.numpy as jnp

MUS = (1.0, 0.9, 0.7, 0.5, 0.3, 0.1, -0.1, -0.3, -0.5, -0.7, -0.9)
SIGMAS = (0.001,) + (0.1,) * 10


def init(key, n_b: int):
    """Weights in the layout the program's knrm scorer takes."""
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (len(MUS), 1)) / jnp.sqrt(len(MUS)),
            "b": 0.1 * jax.random.normal(k2, (1,))}


def score(params, m, q_valid, doc_len, seg_len, functions, mm):
    """m (B, Q, n_b, n_f) -> scores (B,)."""
    cos = m[..., functions.index("cosine")]
    seg_mask = (seg_len > 0).astype(jnp.float32)[:, None, :, None]
    x = jnp.clip(cos / jnp.maximum(seg_len, 1.0)[:, None, :], -1.0, 1.0)
    mu, sigma = jnp.asarray(MUS), jnp.asarray(SIGMAS)
    k = jnp.exp(-0.5 * ((x[..., None] - mu) / sigma) ** 2) * seg_mask
    phi = jnp.log1p(k.sum(2)) * q_valid[None, :, None]      # (B, Q, K)
    return mm("bk,ko->bo", phi.sum(1), params["w"])[:, 0] + params["b"][0]
