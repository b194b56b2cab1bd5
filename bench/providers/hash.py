"""The hash provider: a static table drawn from a seed, and contextual
embeddings that add ALPHA x the mean of the token's segment.

    table  = normal(seed, (|v|, embed_dim)) / sqrt(embed_dim)
    ctx(t) = (e(t) + ALPHA * mean of e over t's segment) for a real token

A provider file gives the harness three things, found by the
configuration's ``"provider"`` name:

* :func:`weights` -- the provider's arrays, drawn on the device from the
  run's seed in one jitted call;
* :func:`program` -- the program's provider object for ``IndexBuilder``,
  the only part of the file that imports the program;
* :func:`reference` -- the plain ``(table, ctx)`` of a block of documents,
  in ``jax.numpy`` with every product through ``bench.reference.mm``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 0.25   # ctx(t) = e(t) + ALPHA x mean of its segment
STREAM = 10    # the table's key stream of the run's seed


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, shape, scale):
    # ``scale`` is an argument, not a constant: XLA turns a division by a
    # constant into a product with its reciprocal, which rounds otherwise
    # than ``HashProvider``'s eager draw
    return jax.random.normal(key, shape, dtype=jnp.float32) / scale


def hash_table(seed: int, vocab_size: int, embed_dim: int):
    """The table ``HashProvider`` draws for the 32-bit ``seed``, bit for
    bit."""
    return _draw(jax.random.key(seed), (vocab_size, embed_dim),
                 jnp.sqrt(embed_dim))


def weights(config: dict, seed: int, vocab_size: int) -> dict:
    """``seed``: the key the program's provider draws its own table from;
    ``table``: the same draw, made here for the reference and kept on the
    host until it runs, so that it holds no device memory while the
    program does."""
    from bench.harness import key_seed

    s = key_seed(seed, STREAM)
    return {"seed": np.uint32(s),
            "table": np.asarray(hash_table(s, vocab_size,
                                           config["embed_dim"]))}


def program(config: dict, weights: dict, vocab_size: int):
    from repro.core import HashProvider

    return HashProvider(vocab_size, config["embed_dim"],
                        seed=int(weights["seed"]))


def reference(weights: dict, tokens, segs, config: dict,
              precision: str = "highest"):
    """tokens/segs ``(B, L)`` -> ``(table (|v|, D), ctx (B, L, D))``."""
    from bench.reference import mm

    n_b = config["n_segments"]
    table = weights["table"]
    valid = tokens >= 0
    vf = valid.astype(jnp.float32)
    S = jax.nn.one_hot(jnp.where(valid, segs, n_b), n_b,
                       dtype=jnp.float32)                   # (B, L, n_b)
    E = table[tokens.clip(0)] * vf[..., None]               # (B, L, D)
    cnt = S.sum(1)                                          # (B, n_b)
    seg_mean = mm("bls,bld->bsd", S, E, precision) \
        / jnp.maximum(cnt, 1.0)[..., None]
    ctx = (E + ALPHA * mm("bls,bsd->bld", S, seg_mean, precision)) \
        * vf[..., None]
    return table, ctx
