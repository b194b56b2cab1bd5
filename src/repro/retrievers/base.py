"""Retriever interface.

Every retriever is a pure scorer over the q-d interaction matrix
M_{q,d} (B, Q, n_b, n_f) — whether M came from a SEINE index lookup, from
the No-Index on-the-fly path, or from an SNRM latent interaction is
invisible to it. That separation of indexing method from retrieval method
is the paper's experimental design (§3.1) and our registry mirrors it.

QMeta carries per-query/per-doc side info every scorer may need.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclass
class QMeta:
    q_mask: jnp.ndarray    # (Q,) 1.0 for real query terms
    q_idf: jnp.ndarray     # (Q,)
    doc_len: jnp.ndarray   # (B,)
    seg_len: jnp.ndarray   # (B, n_b)
    avg_dl: jnp.ndarray    # ()


@dataclass(frozen=True)
class RetrieverSpec:
    """A registered scorer.

    ``needs`` is a contract, not a note: ``SeineEngine.score`` gathers
    only these atomic functions from the index and passes ``score`` an M
    whose last axis holds just them, with the matching narrowed
    ``functions`` tuple.  A scorer must read M only through
    ``fidx(functions, name)`` and only for names in ``needs``; reading
    any other function raises at trace time.
    """
    name: str
    init: Callable[..., Any]          # (key, n_b, functions) -> params
    score: Callable[..., jnp.ndarray]  # (params, M, meta, functions) -> (B,)
    needs: Tuple[str, ...]            # atomic functions consumed


_REGISTRY: Dict[str, RetrieverSpec] = {}


def register(spec: RetrieverSpec) -> RetrieverSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_retriever(name: str) -> RetrieverSpec:
    if name not in _REGISTRY:
        raise KeyError(f"unknown retriever {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_retrievers() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def fidx(functions: Sequence[str], name: str) -> int:
    return tuple(functions).index(name)


def hinge_pair_loss(score_fn, params, m_pos, m_neg, meta_pos, meta_neg,
                    functions) -> jnp.ndarray:
    """Pairwise hinge (the LETOR training objective used for all rankers)."""
    sp = score_fn(params, m_pos, meta_pos, functions)
    sn = score_fn(params, m_neg, meta_neg, functions)
    return jnp.maximum(0.0, 1.0 - sp + sn).mean()
