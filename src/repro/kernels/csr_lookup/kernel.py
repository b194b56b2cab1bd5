"""csr_lookup — the fused SEINE serving lookup as Pallas TPU kernels.

SEINE's query phase is Eq. 4: M_{q,d}[i] = values[owner(q_i), pos(q_i, d)]
— pure random access into the term-partitioned CSR.  The kernels here
resolve the POSITION half of that expression; the values half is one
XLA gather in ``ops`` over the compact ``values`` array.  The split is
what lets the index fill a chip: XLA keeps ``(K, Nmax, n_b, n_f)`` f32
in a compact layout (the posting axis minor), while a Mosaic operand
must be (8, 128)-tiled on its last two dims — ``(n_b, n_f) = (20, 9)``
padded to (24, 128) is 17x the logical bytes, and a per-call relayout
copy of an index that fills two thirds of HBM cannot exist.  No kernel
in this module ever sees ``values``.

:func:`csr_lookup_pallas` — one grid cell per (query term, candidate):

  * routing — per-(term, hop) lane tables ``(k, lo, hi)`` and the
    candidate doc ids ride the SCALAR PREFETCH stream.  ``hop`` counts
    the doc-range sub-shard splits of the term at or below the candidate
    (``split_term``/``split_doc``, (S,) tables, also prefetched), which
    is exactly ``ref.route_pairs``; indexes without splits pass one
    never-matching entry and every pair takes hop 0 (``ref.route_terms``);
  * a TWO-LEVEL branchless bisect — level 1 over the owner's FENCE row
    (every T-th doc id; the whole ``(K, F)`` table sits in SMEM, where
    dynamic scalar reads are native) finds the single T-wide posting
    tile that can hold the target, level 2 DMAs that tile HBM->SMEM and
    bisects inside it.  Both levels run the integer ops of
    ``core.index._bisect`` and the split is exact (the target position
    is unique), so positions are bitwise-equal to the jnp refs;
  * the packed codec (``core.codec``) adds a decode between the tile DMA
    and each probe: the tile's FOR base and word offset come from two
    more SMEM tables, its bit width from the word-offset difference;
  * the output is the pair's flat position ``k * Nmax + pos``, or -1
    when the pair is absent (the sigma=0 exact zero) — ownership is
    exclusive per (term, doc-range), so one write per cell, no partials.

:func:`window_rows_pallas` — the first-stage retrieval gather: grid cell
(lane, window) DMAs a run of whole HBM rows straight into its output
block; ``ops`` slices the window out and merges it with the shared
segment scatter.

HBM layout of what the kernels DMA: an int32 id (or packed-word) buffer
viewed as ``(R, 1, LANES)`` rows.  A DMA may only slice a tiled
dimension along its tiling, so the (1, 128)-tiled minor pair stays whole
and every dynamic slice lands on the untiled leading axis — rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import bisect_steps

LANES = 128   # words per DMA row of the id / packed-word buffers


def window_rows(width: int, align: int) -> int:
    """Static count of ``LANES``-word rows that cover any ``width``-word
    window whose flat start is a multiple of ``align``."""
    g = math.gcd(int(align), LANES)
    return -(-(LANES - g + int(width)) // LANES)


def as_rows(flat: jnp.ndarray, extra_rows: int) -> jnp.ndarray:
    """(n,) int32 -> (R, 1, LANES) DMA rows, zero-padded by at least
    ``extra_rows`` whole rows so a ``window_rows``-row DMA starting at
    any row holding a live word stays in bounds."""
    n = flat.shape[0]
    r = -(-n // LANES) + int(extra_rows)
    return jnp.pad(flat, (0, r * LANES - n)).reshape(r, 1, LANES)


def _make_lookup_kernel(*, tile: int, stride: int, n_pos: int, n_rows: int,
                        n_fence_iter: int, n_tile_iter: int,
                        packed: bool, mw: int):
    def _kernel(lk_ref, llo_ref, lhi_ref, qw_ref, st_ref, sd_ref, docs_ref,
                fence_ref, *rest):
        if packed:
            base_ref, woff_ref, rows_ref, out_ref, buf, sem = rest
        else:
            rows_ref, out_ref, buf, sem = rest
        i = pl.program_id(0)                 # query term
        j = pl.program_id(1)                 # candidate
        d = docs_ref[j]
        w = qw_ref[i]
        hop = jnp.int32(0)
        for s in range(st_ref.shape[0]):     # sub-shard splits at or below d
            hop = hop + ((st_ref[s] == w) & (sd_ref[s] <= d)).astype(
                jnp.int32)
        hop = jnp.minimum(hop, lk_ref.shape[1] - 1)
        k, lo0, hi0 = lk_ref[i, hop], llo_ref[i, hop], lhi_ref[i, hop]
        n_fence = fence_ref.shape[1]

        # level 1 — fence bisect, clamped to the tiles intersecting
        # [lo, hi): first fence index jf in (j_lo, j_hi] with
        # fences[jf] >= d (j_hi + 1 when none).  Restricted to the range
        # the fences are sorted (a posting range never crosses a list
        # boundary), so for every tile strictly before jf the whole tile
        # is < d and the answer lies in tile jf - 1 — or at its right
        # boundary, fence jf itself.
        j_lo = lo0 // tile
        j_hi = jnp.maximum((hi0 - 1) // tile, j_lo)

        def fence_body(_, state):
            flo, fhi = state
            mid = (flo + fhi) // 2
            v = fence_ref[k, jnp.clip(mid, 0, n_fence - 1)]
            go_right = (v < d) & (flo < fhi)
            return (jnp.where(go_right, mid + 1, flo),
                    jnp.where(go_right, fhi, mid))

        jf, _ = jax.lax.fori_loop(0, n_fence_iter, fence_body,
                                  (j_lo + 1, j_hi + 1))
        # clamp keeps the tile DMA in bounds when lo == hi == n_fence*tile
        # (empty range pinned at a tile-aligned shard end); the window
        # below degenerates to empty there, so the clamp never changes a
        # findable result
        jt = jnp.clip(jf - 1, 0, n_fence - 1)
        base = jt * tile

        if packed:
            tb = base_ref[k, jt]
            wo = woff_ref[k, jt]
            c = (woff_ref[k, jt + 1] - wo) * 32 // tile   # bits per id
            mask = (1 << jnp.minimum(c, 16)) - 1
            start = k * stride + wo
        else:
            start = k * stride + base
        # DMA the rows holding the winning tile HBM -> SMEM
        row0 = start // LANES
        off = start - row0 * LANES
        cp = pltpu.make_async_copy(rows_ref.at[pl.ds(row0, n_rows)], buf,
                                   sem)
        cp.start()
        cp.wait()

        def word(x):                         # x-th word of the tile
            f = off + x
            return buf[f // LANES, 0, f % LANES]

        def probe(p):                        # doc id at position p
            r = jnp.clip(p - base, 0, tile - 1)
            if not packed:
                return word(r)
            bp = r * c
            wv = word(jnp.clip(bp // 32, 0, mw - 1))
            rel = jax.lax.shift_right_logical(
                wv, jnp.bitwise_and(bp, 31)) & mask
            return jnp.where(c == 32, wv, tb + rel)

        # level 2 — the in-tile bisect over the window [w_lo, w_hi):
        # same ops as core.index._bisect, only bit_length(tile) steps
        w_lo = jnp.maximum(base, lo0)
        w_hi = jnp.minimum(base + tile, hi0)

        def tile_body(_, state):
            lo, hi = state
            mid = (lo + hi) // 2
            go_right = (probe(mid) < d) & (lo < hi)
            return (jnp.where(go_right, mid + 1, lo),
                    jnp.where(go_right, hi, mid))

        pos, _ = jax.lax.fori_loop(0, n_tile_iter, tile_body, (w_lo, w_hi))
        # the hit value: inside the DMA'd tile, or — when the bisect ran
        # off the window's right edge at a tile boundary still inside
        # [lo, hi) — the next tile's first element, which IS fence jt+1
        v_fence = fence_ref[k, jnp.clip(jt + 1, 0, n_fence - 1)]
        v_at = jnp.where(pos < w_hi, probe(pos), v_fence)
        found = (pos < hi0) & (v_at == d)
        out_ref[i, j] = jnp.where(found, k * n_pos + pos, -1)

    return _kernel


def csr_lookup_pallas(lane_k: jnp.ndarray, lane_lo: jnp.ndarray,
                      lane_hi: jnp.ndarray, query_terms: jnp.ndarray,
                      split_term: jnp.ndarray, split_doc: jnp.ndarray,
                      doc_targets: jnp.ndarray, fences: jnp.ndarray,
                      rows: jnp.ndarray, *, tile: int, stride: int,
                      n_pos: int, packed_meta=None, max_tile_words: int = 0,
                      interpret: bool = False) -> jnp.ndarray:
    """Positions of query_terms (Q,) x doc_targets (B,) -> (Q, B) int32.

    ``lane_k/lo/hi`` (Q, H) — the route of each term at each sub-shard
    hop (``ops.route_hops``); ``split_term``/``split_doc`` (S,) — the
    sub-shard split tables (one ``-1`` entry when there are none);
    ``fences`` (K, F) int32.  ``rows`` is the (R, 1, LANES) DMA view of
    the flat id buffer, where shard k's ids start at word
    ``k * stride`` (tile-padded, so ``stride = F * tile``) — or, with
    ``packed_meta = (tile_base (K, F), tile_word_off (K, F+1))``, of the
    flat packed-word buffer (``stride`` = words per shard row,
    ``max_tile_words`` the per-tile decode window).  Entry (q, b) is
    ``k * n_pos + pos`` for a stored pair, -1 otherwise.
    """
    q_n = lane_k.shape[0]
    b_n = doc_targets.shape[0]
    n_fence = fences.shape[1]
    packed = packed_meta is not None
    n_rows = (window_rows(max_tile_words, 1) if packed
              else window_rows(tile, tile))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tables = (fences,) + (tuple(packed_meta) if packed else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,    # lane k/lo/hi, terms, splits, docs
        grid=(q_n, b_n),
        in_specs=[smem] * len(tables) + [
            pl.BlockSpec(memory_space=pl.ANY)],   # id rows stay in HBM
        out_specs=smem,
        scratch_shapes=[
            pltpu.SMEM((n_rows, 1, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        _make_lookup_kernel(tile=tile, stride=stride, n_pos=n_pos,
                            n_rows=n_rows,
                            n_fence_iter=bisect_steps(n_fence),
                            n_tile_iter=bisect_steps(tile), packed=packed,
                            mw=max_tile_words),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_n, b_n), jnp.int32),
        interpret=interpret,
    )(lane_k, lane_lo, lane_hi, query_terms, split_term, split_doc,
      doc_targets, *tables, rows)


def _window_rows_kernel(row0_ref, rows_ref, out_ref, sem):
    lane = pl.program_id(0)
    w = pl.program_id(1)
    n_rows = out_ref.shape[2]
    cp = pltpu.make_async_copy(
        rows_ref.at[pl.ds(row0_ref[lane, w], n_rows)], out_ref.at[0, 0],
        sem)
    cp.start()
    cp.wait()


def window_rows_pallas(row0: jnp.ndarray, rows: jnp.ndarray, *,
                       n_rows: int, interpret: bool = False) -> jnp.ndarray:
    """Posting-window gather for first-stage retrieval.

    Where the lookup resolves one (term, doc) pair per grid cell,
    retrieval walks whole posting ranges: grid cell (l, w) DMAs rows
    ``[row0[l, w], row0[l, w] + n_rows)`` of ``rows`` (R, 1, LANES)
    HBM -> VMEM straight into its output block — one dynamic copy per
    cell, no compute.  The window slice and the segment-sum merge
    (``ref.merge_windows``) happen outside: the merge is a scatter,
    which the VPU has no efficient primitive for, while the gather is
    pure DMA the kernel overlaps across grid cells.
    Returns (L, n_win, n_rows, 1, LANES) int32.
    """
    n_lanes, n_win = row0.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_lanes, n_win),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, n_rows, 1, LANES),
                               lambda l, w, r: (l, w, 0, 0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _window_rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_lanes, n_win, n_rows, 1, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(row0.astype(jnp.int32), rows)
