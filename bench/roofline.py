"""Work counted by contract, for the roofline shares.

``qd_matrix``'s contract is "return M (B, Q, n_b, n_f) for the query's
terms and the candidates".  The least traffic that takes is, for each real
pair (a query term >= 0 times an unpadded candidate), reading its stored
``n_b x n_f`` values and writing them to M as float32.  Id tiles, fences,
padded slots and padded candidates are not counted: they depend on how the
lookup is implemented, and this count must not.  So whatever kernel,
layout or codec serves the lookup, its share stays at or under 100%.
"""
import numpy as np

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def lookup_bytes(requests, n_b: int, n_f: int, value_dtype: str) -> int:
    """``requests``: ``[(query_terms, n_candidates)]``."""
    per_pair = n_b * n_f * (DTYPE_BYTES[value_dtype] + 4)
    pairs = sum(int((np.asarray(q) >= 0).sum()) * int(n) for q, n in requests)
    return pairs * per_pair
