"""Work counted by contract, for the roofline shares.

``qd_matrix``'s contract is "return M (B, Q, n_b, n_f) for the query's
terms and the candidates".  The least traffic that takes is, for each real
pair (a query term >= 0 times an unpadded candidate), reading its stored
``n_b x n_f`` values and writing them to M as float32.  Id tiles, fences,
padded slots and padded candidates are not counted: they depend on how the
lookup is implemented, and this count must not.  So whatever kernel,
layout or codec serves the lookup, its share stays at or under 100%.

An encoder's work is counted the same way: its provider file's
``flops(config, n_tokens)`` for each real document of a checked request,
at that document's own length (``RunView.probe_doc_tokens``), never for a
padded candidate or a padded position.  A model-FLOP share divides that by
its device time times ``peaks["bf16_flops_per_s"]`` and carries ``mfu`` in
its name.
"""
from typing import Optional

import numpy as np

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def lookup_bytes(requests, n_b: int, n_f: int, value_dtype: str) -> int:
    """``requests``: ``[(query_terms, n_candidates)]``."""
    per_pair = n_b * n_f * (DTYPE_BYTES[value_dtype] + 4)
    pairs = sum(int((np.asarray(q) >= 0).sum()) * int(n) for q, n in requests)
    return pairs * per_pair


def encoder_flops(provider, config: dict, doc_tokens) -> Optional[int]:
    """The operations a provider needs to encode the real documents of the
    checked requests: its ``flops(config, n_tokens)`` summed document by
    document, since attention grows with a document's own length.
    ``doc_tokens``: per request, the real token count of each real
    candidate.  ``None`` where the provider names no ``flops`` (``hash``
    computes no model)."""
    flops = getattr(provider, "flops", None)
    if flops is None:
        return None
    return sum(flops(config, int(n)) for req in doc_tokens for n in req)
