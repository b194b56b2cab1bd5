"""Ahead-of-time compiles of the serving kernels for a TPU v5e.

No chip is attached here: the TPU compiler compiles for a described
``v5e:2x2`` topology, which refuses what a chip would refuse at its first
call — block shapes off the (8, 128) tiling, DMA slices off a memref's
tiling, SMEM overflow.  Shapes are the paper's own cell at full scale:
SEINE_LETOR widths (n_b = 20, 9 interaction functions) over MQ2007's
65,323 docs, ~14.4M postings (~10.4 GB of f32 values), Q = 8 query terms
x B = 2,048 candidates.  Each compile also checks that ``values`` keeps a
compact device layout (the argument bytes stay near the logical bytes)
and that no per-call copy of it appears in the program's temporaries.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library at a time.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.index import POSTING_TILE, fence_count
from repro.kernels.csr_lookup import ops

NNZ = 14_400_000      # postings of the SEINE_LETOR build at 65,323 docs
VOCAB = 11_600        # the generator's raw-token ceiling
N_B, N_F = 20, 9
Q, B = 8, 2048
RETRIEVE_BLOCK = 1024
MAX_TILE_WORDS = 256  # a 32-bit packed tile: the widest decode window


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # compiles for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _index_shapes(one_chip, k, codec):
    """ShapeDtypeStructs of a K-shard PartitionedIndex at MQ2007 scale."""
    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nmax = -(-NNZ // k)
    f = fence_count(nmax, POSTING_TILE)
    vmax = -(-VOCAB // k)
    idx = dict(term_offsets=s((k, vmax + 1)), fences=s((k, f)),
               term_to_shard=None if k == 1 else s((VOCAB,)),
               range_lo=None if k == 1 else s((k,)),
               range_hi=None if k == 1 else s((k,)),
               split_term=None if k == 1 else s((k,)),
               split_doc=None if k == 1 else s((k,)))
    if codec == "none":
        idx.update(doc_ids=s((k, nmax)),
                   values=s((k, nmax, N_B, N_F), jnp.float32),
                   packed=None, value_scale=None)
    else:
        words = nmax // 2 + MAX_TILE_WORDS         # ~16-bit tiles
        idx.update(doc_ids=None, values=s((k, nmax, N_B, N_F), jnp.int8),
                   packed=(s((k, words)), s((k, f)), s((k, f)),
                           s((k, f + 1))),
                   value_scale=s((k, vmax), jnp.float32))
    return idx, s


def _check_compiled(compiled, values, kernel=True):
    text = compiled.as_text()
    if kernel:
        assert "tpu_custom_call" in text, "the Pallas kernel was not lowered"
    mem = compiled.memory_analysis()
    logical = values.size * values.dtype.itemsize
    # f32 (K, N, 20, 9) lays out with the posting axis minor (1.0x); int8
    # at K = 1 pads 20 -> 24 on its sublane axis (1.2x).  A (24, 128)-
    # tiled operand layout would be 17x.
    assert mem.argument_size_in_bytes < 1.3 * logical
    # any copy of values would be at least its logical size
    assert mem.temp_size_in_bytes < 0.5 * logical, "values copied per call"


@pytest.mark.parametrize("codec", ["none", "packed-q8"])
@pytest.mark.parametrize("k", [1, 4])
def test_lookup_compiles_for_v5e(one_chip, k, codec):
    idx, s = _index_shapes(one_chip, k, codec)
    lookup = ops.csr_lookup.lower(
        idx["term_offsets"], idx["doc_ids"], idx["values"],
        idx["term_to_shard"], idx["range_lo"], s((Q,)), s((B,)),
        fences=idx["fences"], split_term=idx["split_term"],
        split_doc=idx["split_doc"], interpret=False, codec=codec,
        packed=idx["packed"], value_scale=idx["value_scale"],
        max_tile_words=MAX_TILE_WORDS if idx["packed"] else 0)
    _check_compiled(lookup.compile(), idx["values"])


def test_narrowed_lookup_compiles_for_v5e(one_chip):
    """The served lookup of a ranker that reads one function (KNRM's
    ``cosine``, ``cols=(3,)``): ``values`` is still read in place, and
    every element gather takes 20 x 1 elements per pair; no gather of
    whole 20 x 9 rows is left."""
    idx, s = _index_shapes(one_chip, 1, "none")
    compiled = ops.csr_lookup.lower(
        idx["term_offsets"], idx["doc_ids"], idx["values"], None, None,
        s((Q,)), s((B,)), fences=idx["fences"], interpret=False,
        cols=(3,)).compile()
    _check_compiled(compiled, idx["values"])
    text = compiled.as_text()
    gathers = [tuple(int(x) for x in dims.split(","))
               for dims in re.findall(r"= f32\[([\d,]+)\]\S* gather\(",
                                      text)]
    assert gathers, "no values gather in the program"
    for shape in gathers:
        assert shape[-1] == N_B or shape[-2:] == (N_B, 1), shape
    full_rows = {dims for dims in re.findall(r"\[([\d,]+)\]", text)
                 if dims.endswith(f",{N_B},{N_F}")}
    assert full_rows == {f"1,{NNZ},{N_B},{N_F}"}, full_rows   # values only


@pytest.mark.parametrize("codec", ["none", "packed-q8"])
@pytest.mark.parametrize("k", [1, 4])
def test_retrieve_block_compiles_for_v5e(one_chip, k, codec):
    """The kernel path of one first-stage doc block: ``csr_retrieve_block``
    dispatches to the jnp ref off-TPU, so its window paths are compiled
    directly."""
    idx, s = _index_shapes(one_chip, k, codec)
    t = POSTING_TILE
    args = (idx["term_offsets"], idx["term_to_shard"], idx["range_lo"],
            idx["range_hi"], s((Q,)), s(()))

    if codec == "none":
        def block(term_offsets, t2s, range_lo, range_hi, q, blo, doc_ids,
                  values):
            return ops._retrieve_block_windows(
                term_offsets, ops._id_rows(doc_ids, t), values, t2s,
                range_lo, range_hi, q, blo, RETRIEVE_BLOCK, t, False)
        extra = (idx["doc_ids"], idx["values"])
    else:
        def block(term_offsets, t2s, range_lo, range_hi, q, blo, packed,
                  fences, values, scale):
            return ops._retrieve_block_windows_packed(
                term_offsets, packed, ops._word_rows(packed, MAX_TILE_WORDS),
                fences, values, scale, t2s, range_lo, range_hi, q, blo,
                RETRIEVE_BLOCK, t, MAX_TILE_WORDS, False)
        extra = (idx["packed"], idx["fences"], idx["values"],
                 idx["value_scale"])
    compiled = jax.jit(block).lower(*args, *extra).compile()
    _check_compiled(compiled, idx["values"])


@pytest.mark.parametrize("k", [1, 4])
def test_jnp_lookups_compile_for_v5e(one_chip, k):
    """The plain-jnp lookups the chip run checks the kernel against: the
    routed reference (``csr_lookup``'s lowering off-TPU) and the SPMD
    partial-sum expression (``impl="jnp"``) at the same real size."""
    from repro.dist.partition import PartitionedIndex
    from repro.kernels.csr_lookup.ref import csr_lookup_ref

    idx, s = _index_shapes(one_chip, k, "none")
    ref = jax.jit(csr_lookup_ref).lower(
        idx["term_offsets"], idx["doc_ids"], idx["values"],
        idx["term_to_shard"], idx["range_lo"], s((Q,)), s((B,)),
        idx["split_term"], idx["split_doc"]).compile()
    _check_compiled(ref, idx["values"], kernel=False)
    pidx = PartitionedIndex(
        term_offsets=idx["term_offsets"], doc_ids=idx["doc_ids"],
        values=idx["values"], term_to_shard=s((VOCAB,)),
        range_lo=s((k,)), range_hi=s((k,)), idf=s((VOCAB,), jnp.float32),
        doc_len=s((65_323,), jnp.float32),
        seg_len=s((65_323, N_B), jnp.float32), n_docs=65_323,
        vocab_size=VOCAB, n_b=N_B, n_shards=k, functions=("f",) * N_F)
    partial = jax.jit(lambda p, q, d: p.qd_matrix(q, d, impl="jnp")).lower(
        pidx, s((Q,)), s((B,))).compile()
    _check_compiled(partial, idx["values"], kernel=False)
