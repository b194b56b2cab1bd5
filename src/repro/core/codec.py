"""Tile-compressed posting codec: FOR/bit-packed doc ids + int8 values.

The fused serving kernels are HBM-bandwidth-bound — every posting tile
tours HBM->VMEM as raw int32 doc ids and float32 interaction values — so
compressing what each shard *stores* is raw speed on the hot path, not
just capacity (ROADMAP item 3).  The tile is the natural decode unit:
the two-level bisect already resolves every probe to one
``POSTING_TILE``-wide tile via the uncompressed fence row (a ready-made
skip pointer), so the kernel only ever needs to decode the tile it DMA'd.

Doc ids — per-tile frame-of-reference (FOR), not delta coding: a tile
can span posting-list boundaries, so ids within it are NOT monotone and
deltas could be negative.  Instead each tile stores

  base   = min(tile)                      (int32, the frame)
  bits   c in {0, 4, 8, 16, 32}           per-tile width class
  words  ceil(tile * c / 32) packed int32 (c=0: none; c=32: raw ids)

Width classes are divisors of 32 so no packed value ever straddles a
word: decode of one element is a shift+mask of one word — two scalar
VMEM loads per bisect probe, the same op class as the uncompressed
kernel's tile reads.  Lossless by construction: ``unpack(pack(x)) == x``
bitwise for every int32 row (c=32 stores raw ids, so even adversarial
spans round-trip).  Tiles are laid out contiguously with a per-tile word
offset table; the kernel DMAs a fixed ``max_tile_words`` window from
``tile_word_off[jt]`` (rows are padded by one window so the DMA never
runs out of bounds; the garbage tail is never decoded).

Interaction values — symmetric int8 with one scale per (shard, local
term) row, mirroring ``dist.compression.quantize_int8`` (max-abs / 127,
min-clamped): a term's postings share dynamic range (same idf regime),
per-term scales keep the quantisation error proportional to each term's
own magnitude.  Quantised values are gated on effectiveness deltas
(benchmarks/bench_compressed.py), never bitwise — ids stay exact in
every codec mode.

Codec axis (threaded through build -> partition -> kernels -> ckpt ->
engine): ``"none"`` (raw), ``"packed"`` (FOR ids, f32 values),
``"packed-q8"`` (FOR ids, int8 values + per-term scales).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .index import fence_count

CODECS = ("none", "packed", "packed-q8")
WIDTH_CLASSES = (0, 4, 8, 16, 32)
INT32_MAX = np.iinfo(np.int32).max


def validate_codec(codec: Optional[str]) -> str:
    """Normalize ``codec`` (None -> "none") and reject unknown names."""
    c = codec or "none"
    if c not in CODECS:
        raise ValueError(f"unknown codec {c!r}; supported: {CODECS}")
    return c


class PackedIds(NamedTuple):
    """Bit-packed doc ids for K stacked shard rows.

    ``packed_words (K, W)`` int32 — tile j of row k occupies words
    ``[tile_word_off[k, j], tile_word_off[k, j+1])``; every row is padded
    by ``max_tile_words`` zero words so a fixed-size window DMA from any
    real tile's offset stays in bounds.  ``tile_bits``/``tile_base``
    ``(K, F)`` int32, ``tile_word_off (K, F+1)`` int32 with
    ``F = fence_count(Nmax, tile)``.  ``max_tile_words`` is the static
    per-tile DMA window (>= the widest tile's word count, >= 1)."""
    packed_words: np.ndarray
    tile_bits: np.ndarray
    tile_base: np.ndarray
    tile_word_off: np.ndarray
    max_tile_words: int
    tile: int
    n: int                      # unpacked row length (Nmax)

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in (self.packed_words, self.tile_bits,
                             self.tile_base, self.tile_word_off))


def _width_classes(span: np.ndarray) -> np.ndarray:
    """Smallest width class in {0,4,8,16,32} holding ``span`` (max-min)."""
    bits = np.full(span.shape, 32, np.int32)
    for c in (16, 8, 4):
        bits[span < (1 << c)] = c
    bits[span == 0] = 0
    return bits


def pack_row(row: np.ndarray, tile: int
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pack one (n,) int32 row -> (words, bits, base, word_off).

    Positions [0, n) round-trip exactly; the tile-pad tail [n, F*tile)
    is filled with the row's last value before packing so a short tail
    never forces a 32-bit tile (decoders mask positions >= n to the
    int32-max sentinel themselves, matching the uncompressed tile pad).
    """
    if tile % 8:
        raise ValueError(f"codec tile must be a multiple of 8 (so every "
                         f"width class tiles a 32-bit word), got {tile}")
    row = np.ascontiguousarray(np.asarray(row, np.int32))
    n = row.shape[0]
    f = fence_count(n, tile)
    padded = np.empty(f * tile, np.int32)
    padded[:n] = row
    padded[n:] = row[-1] if n else 0
    tiles = padded.reshape(f, tile)
    base = tiles.min(axis=1)
    span = tiles.max(axis=1).astype(np.int64) - base.astype(np.int64)
    bits = _width_classes(span)
    wpt = (bits.astype(np.int64) * tile) // 32
    word_off = np.zeros(f + 1, np.int64)
    np.cumsum(wpt, out=word_off[1:])
    words = np.zeros(int(word_off[-1]), np.uint32)
    for c in (4, 8, 16):
        sel = np.flatnonzero(bits == c)
        if sel.size:
            rel = (tiles[sel].astype(np.int64)
                   - base[sel, None]).astype(np.uint32)
            vpw = 32 // c
            grouped = rel.reshape(sel.size, tile // vpw, vpw)
            shifts = (np.arange(vpw, dtype=np.uint32) * c)[None, None, :]
            packed = np.bitwise_or.reduce(grouped << shifts, axis=-1)
            idx = word_off[sel, None] + np.arange(tile // vpw)[None, :]
            words[idx.reshape(-1)] = packed.reshape(-1)
    sel = np.flatnonzero(bits == 32)
    if sel.size:
        idx = word_off[sel, None] + np.arange(tile)[None, :]
        words[idx.reshape(-1)] = tiles[sel].reshape(-1).view(np.uint32)
    return (words.view(np.int32), bits, base.astype(np.int32),
            word_off.astype(np.int32))


def pack_doc_ids(doc_ids: np.ndarray, tile: int) -> PackedIds:
    """Pack stacked shard rows (K, Nmax) int32 into one PackedIds.

    Rows pack independently (shards are the unit of placement and
    checkpointing); word buffers pad to a common width plus one
    ``max_tile_words`` DMA window of zeros.
    """
    doc_ids = np.asarray(doc_ids, np.int32)
    if doc_ids.ndim != 2:
        raise ValueError(f"expected stacked (K, Nmax) doc ids, got shape "
                         f"{doc_ids.shape}")
    k, n = doc_ids.shape
    rows = [pack_row(doc_ids[i], tile) for i in range(k)]
    # floor of 8 words (32 B) keeps the fixed-size tile DMA above the
    # transfer-efficiency floor even when every tile packs to width 0/4
    mw = max(8, max(int(np.diff(wo).max(initial=0))
                    for _, _, _, wo in rows))
    w = max(int(r[0].shape[0]) for r in rows) + mw
    words = np.zeros((k, w), np.int32)
    f = fence_count(n, tile)
    bits = np.zeros((k, f), np.int32)
    base = np.zeros((k, f), np.int32)
    woff = np.zeros((k, f + 1), np.int32)
    for i, (rw, rb, rbase, rwo) in enumerate(rows):
        words[i, :rw.shape[0]] = rw
        bits[i] = rb
        base[i] = rbase
        woff[i] = rwo
    return PackedIds(words, bits, base, woff, mw, int(tile), int(n))


def unpack_row(words: np.ndarray, bits: np.ndarray, base: np.ndarray,
               word_off: np.ndarray, *, tile: int, n: int) -> np.ndarray:
    """Exact inverse of :func:`pack_row` over positions [0, n)."""
    f = bits.shape[0]
    words = np.asarray(words).view(np.uint32)
    out = np.empty((f, tile), np.int32)
    for c in WIDTH_CLASSES:
        sel = np.flatnonzero(bits == c)
        if not sel.size:
            continue
        if c == 0:
            out[sel] = base[sel, None]
        elif c == 32:
            idx = word_off[sel, None].astype(np.int64) + np.arange(tile)
            out[sel] = words[idx.reshape(-1)].reshape(
                sel.size, tile).view(np.int32)
        else:
            vpw = 32 // c
            idx = (word_off[sel, None].astype(np.int64)
                   + np.arange(tile // vpw)[None, :])
            w = words[idx.reshape(-1)].reshape(sel.size, tile // vpw, 1)
            shifts = (np.arange(vpw, dtype=np.uint32) * c)[None, None, :]
            rel = (w >> shifts) & np.uint32((1 << c) - 1)
            out[sel] = (base[sel, None]
                        + rel.reshape(sel.size, tile).astype(np.int64)
                        ).astype(np.int32)
    return out.reshape(-1)[:n]


def unpack_doc_ids(p: PackedIds) -> np.ndarray:
    """(K, Nmax) int32 — bitwise inverse of :func:`pack_doc_ids`."""
    k = p.packed_words.shape[0]
    return np.stack([
        unpack_row(p.packed_words[i], p.tile_bits[i], p.tile_base[i],
                   p.tile_word_off[i], tile=p.tile, n=p.n)
        for i in range(k)])


def fences_from_packed(tile_bits: np.ndarray, tile_base: np.ndarray,
                       tile_word_off: np.ndarray, packed_words: np.ndarray,
                       *, tile: int, n: int) -> np.ndarray:
    """Rebuild the (K, F) fence rows from packed metadata alone.

    Fence j is the decoded id at position ``j * tile`` (relative offset 0
    inside its tile: word ``tile_word_off[j]``, shift 0), or the int32
    max sentinel once ``j * tile`` passes the unpacked length — exactly
    what ``core.index.build_fences`` produces on the raw array, so
    checkpoints need not store fences at all.
    """
    k, f = tile_bits.shape
    wo = np.minimum(tile_word_off[:, :f], packed_words.shape[1] - 1)
    w0 = np.take_along_axis(packed_words, wo, axis=1).view(np.uint32)
    mask = np.uint32(1) << np.minimum(tile_bits, 16).astype(np.uint32)
    rel = (w0 & (mask - np.uint32(1))).astype(np.int64)
    dec = np.where(tile_bits == 32, w0.view(np.int32),
                   (tile_base.astype(np.int64) + rel).astype(np.int32))
    live = (np.arange(f) * tile)[None, :] < n
    return np.where(live, dec, INT32_MAX).astype(np.int32)


# ---------------------------------------------------------------------------
# value quantisation (per-term int8 scales)
# ---------------------------------------------------------------------------

def quantize_values(values: np.ndarray, term_offsets: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(K, Nmax, n_b, n_f) f32 + (K, Vmax+1) offsets ->
    (values_q int8, value_scale (K, Vmax) f32).

    One symmetric scale per (shard, local term) row —
    ``max |v| / 127`` over the term's postings, min-clamped exactly like
    ``dist.compression.quantize_int8`` — so dequantisation error stays
    bounded by ``scale / 2`` per entry, proportional to the term's own
    magnitude.  Padding postings hold zeros and quantise to zero under
    any scale; empty terms keep the clamp floor (their scale is never
    applied to a found pair).  The elementwise passes run over
    ``chunk`` postings at a time, so host temporaries stay a fixed size
    instead of several copies of an index-sized f32 array.
    """
    values = np.asarray(values, np.float32)
    offs = np.asarray(term_offsets, np.int64)
    k, nmax = values.shape[:2]
    vmax = offs.shape[1] - 1
    chunk = 1 << 18
    amax = np.empty((k, nmax), np.float32)
    for s in range(0, nmax, chunk):
        amax[:, s:s + chunk] = np.abs(values[:, s:s + chunk]).max(axis=(2, 3))
    peak = np.zeros((k, vmax), np.float32)
    pos_scale = np.empty((k, nmax), np.float32)
    for i in range(k):
        counts = np.diff(np.clip(offs[i], 0, nmax))
        term_of = np.repeat(np.arange(vmax), counts)         # (nnz_i,)
        np.maximum.at(peak[i], term_of, amax[i, :term_of.shape[0]])
        scale_i = np.maximum(peak[i], 1e-12) / 127.0
        pos_scale[i] = 1.0                                   # pad rows
        pos_scale[i, :term_of.shape[0]] = scale_i[term_of]
    q = np.empty(values.shape, np.int8)
    for s in range(0, nmax, chunk):
        q[:, s:s + chunk] = np.clip(np.round(
            values[:, s:s + chunk] / pos_scale[:, s:s + chunk, None, None]),
            -127, 127)
    return q, (np.maximum(peak, 1e-12) / 127.0).astype(np.float32)


# ---------------------------------------------------------------------------
# jnp random-access decode (the reference lowering the kernels are held to)
# ---------------------------------------------------------------------------

def unpack_at(packed_words: jnp.ndarray, tile_bits: jnp.ndarray,
              tile_base: jnp.ndarray, tile_word_off: jnp.ndarray,
              k: jnp.ndarray, pos: jnp.ndarray, *, tile: int
              ) -> jnp.ndarray:
    """Decode shard-local positions: ids[k, pos] without materialising
    the unpacked rows.  ``k``/``pos`` broadcastable int32; positions are
    clipped into the packed tile range (callers mask out-of-range reads
    exactly like ``.get(mode="clip")`` gathers on the raw array).
    """
    f = tile_bits.shape[1]
    j = jnp.clip(pos // tile, 0, f - 1)
    r = jnp.clip(pos - j * tile, 0, tile - 1)
    c = tile_bits.at[k, j].get(mode="clip")
    tb = tile_base.at[k, j].get(mode="clip")
    wo = tile_word_off.at[k, j].get(mode="clip")
    bitpos = r * c
    w = packed_words.at[k, wo + bitpos // 32].get(mode="clip")
    rel = jax.lax.shift_right_logical(w, jnp.bitwise_and(bitpos, 31)) \
        & ((1 << jnp.minimum(c, 16)) - 1)
    return jnp.where(c == 32, w, tb + rel)


def unpack_flat(packed_words: jnp.ndarray, tile_bits: jnp.ndarray,
                tile_base: jnp.ndarray, tile_word_off: jnp.ndarray,
                flat_pos: jnp.ndarray, *, tile: int, nmax: int
                ) -> jnp.ndarray:
    """Decode positions in the flat ``(K * Nmax,)`` view the jnp lookup
    reference bisects over (``doc_ids.reshape(K * N)`` semantics)."""
    n_flat = packed_words.shape[0] * nmax
    p = jnp.clip(flat_pos, 0, max(n_flat - 1, 0))
    k = p // nmax
    return unpack_at(packed_words, tile_bits, tile_base, tile_word_off,
                     k, p - k * nmax, tile=tile)


__all__ = ["CODECS", "WIDTH_CLASSES", "INT32_MAX", "PackedIds",
           "validate_codec", "pack_row", "pack_doc_ids", "unpack_row",
           "unpack_doc_ids", "fences_from_packed", "quantize_values",
           "unpack_at", "unpack_flat"]
