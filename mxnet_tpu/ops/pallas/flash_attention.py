"""Flash attention Pallas kernel (fwd + bwd, causal, O(S) memory).

Reference analog: upstream MXNet has NO fused attention op (SURVEY
§5.7) — BERT-era attention is composed from batch_dot+softmax
(src/operator/tensor/dot-inl.h + nn/softmax.cc), materializing the
(S, S) score matrix in HBM. This kernel is the TPU-first replacement:
blockwise online-softmax with the query block resident in VMEM, scores
never leaving the chip.

Also exports ``flash_attention_with_lse`` returning the per-row
log-sum-exp — the combiner state blockwise/ring schemes need. Note:
parallel/ring_attention.py currently folds chunks with a pure-jnp
online-softmax (differentiable through lax.scan) rather than this
forward-only kernel; this entry point serves external combiners and
golden tests.

Shapes: q, k (B, H, Sq / Skv, D), v (B, H, Skv, Dv): q.k's width D and
v's Dv are read apart, so latent attention (q.k 192 padded to 256 by the
op, v 128) runs p.v, o, do and dv at 128 columns; Dv == D is the one-width
call every other attention layer makes. ``q_offset`` is the
global position of q row 0 relative to k row 0 (ring attention passes
the rotating chunk offset; 0 for vanilla causal). The same
Sq != Skv + offset geometry is what the decode engine's CHUNKED
PREFILL steps (serving/decode_model.py ``prefill_chunk``) produce —
a small q block at global position ``start`` attending to the paged
KV written so far. That path runs the composed jnp attention over
gathered cache pages today (small Sq keeps the score block trivially
VMEM-resident), but the masking convention is deliberately identical
(``col <= q_offset + row``) so the chunk loop can be pointed at this
kernel without changing results.

Variable-length batches ARE handled natively: ``kv_lens`` (B,) int32
gives each example's valid key/value length. The per-example length
rides in SMEM; score columns at or beyond it are masked in both the
forward and the fused backward, and (q, k) tiles that start past the
length are SKIPPED entirely (no MXU work — short rows in a padded
batch cost proportionally less). Rows whose query position is padding
produce zeros through the l==0 guard; with the loss masking padded
positions (cotangent zero there), their dk/dv contributions vanish
identically, so gradients match the composed masked softmax exactly.

Arbitrary ADDITIVE masks (relative-position biases etc.) are not
expressible as lengths — the op layer falls back to the jnp composed
path for those.

SEQUENCE PACKING is handled natively too: ``segment_ids`` (B, S) int32
gives each token's segment (sequence) id within its packed row
(io/packing.py emits them; 0 marks padding slots). Attention is
block-diagonal — a (q, k) pair contributes only when the two tokens
share a segment id — so multiple short sequences ride one row with
exactly zero cross-sequence attention, forward and backward. The ids
ride in VMEM in the lane/sublane-broadcast layout Mosaic compares
cheaply (q ids replicated across 128 lanes, kv ids across 8 sublanes —
the jax.experimental flash reference's SegmentIds idiom), and a
per-block id-range summary (min/max per q/k tile) rides in SMEM so a
(q-block, kv-block) pair whose id ranges are disjoint is SKIPPED
whole (no MXU work) — sound for arbitrary ids since disjoint ranges
cannot share a value, and tight when the packer lays segments out
contiguously (monotonic ids). Combine with ``kv_lens`` (the packed
row's used length) so tail padding is masked and padding rows emit
exact zeros through the l==0 guard; packed outputs and gradients then
match each sequence run unpacked, bit-for-bit in block-free cases and
within fp tolerance otherwise. Packing requires Sq == Skv (self
attention; the KV-cache decode path has no packed analog here).

A causal WINDOW (``window=W``, static): token t sees keys j with
0 <= t - j < W, itself and the W - 1 before it (sliding-window layers).
The kv tile is then at most as wide as the window, and tiles outside
the band cost nothing: the forward and the split backward walk the band's
tiles alone (THE WALK, below), and the fused backward (at most two kv
tiles: rows no longer than two windows) walks its whole (kv, q) grid and
skips by ``_block_visible``.

GROUPED key/value heads: k and v may have fewer heads than q (Hk
divides H); query head h reads key/value head h // (H / Hk) through
the index maps, k and v are not repeated in HBM. Forward and dq walk
the B*H query rows; the dkv and fused backwards walk the B*Hk
key/value rows and, inside each, the group's query heads one after the
other, so that dk and dv are summed over the group in VMEM.

A PAIR MASK THAT IS DATA (``pair_mask``, (B, Sq, Skv) int8, an operand and
not a static argument): query t sees key s only where the mask is nonzero,
besides what ``causal`` allows; one mask serves all the heads of a batch
entry (sparse attention whose keys a learned indexer selects: the mask is
computed from the data, a step at a time). Two operands: the mask itself,
read a (block_q, block_k) tile at a time through the same grids (1 MB at
512 x 2048, beside a tile's 0.5 GFLOP), and its TILE SUMMARY, (B, nq, nk)
int32 in SMEM, nonzero where a tile has any live pair, from which
``_block_visible`` skips a dead tile whole. Every arm honours both: the
forward and the dq kernel walk (q rows, q tiles, kv tiles) and read tile
(i, j) of batch entry row // H; the dkv and fused kernels walk (kv rows, kv
tiles, the group's query heads x q tiles) and read tile (i, j) of batch
entry row // Hk, the same tile for each of the group's heads. Inside a live
tile the mask joins the causal one (``_pair_mask``'s ``smask``). It goes with
neither a window, segments nor ``kv_lens``; with the operand absent the
kernels trace what they traced before it existed
(``tests/test_keye_vl2_blocks.py``).

THREE KINDS OF TILE under the static masks (causal, window, the kv tile's
padding, block diffusion), decided from the call's own masks and shapes at
trace time (`_tile_lives`): a DEAD tile is not walked (below); a tile they
leave WHOLLY live runs its whole width with no static mask built (a pair
mask, kv lengths and segments are data, and still apply); a PARTLY live one
runs only its chunks of ``block_q`` columns (512 of a 2048-wide kv tile)
from the first that holds a live pair to the last, as one update at a
static width (a body for each count of chunks, one of which runs), its mask
built at that width: on the causal diagonal and in a noisy row's own
diffusion blocks most chunks are empty (`_plan_tiles`, `_bodies`). The
products of the live pairs are the ones the whole tile computed. A call
with no wholly live tile and no chunk to skip (no static mask; a window's
tiles, one chunk wide) traces the one masked body it always traced; one
with a plan calls its kernel through ``jax.jit``, so that a model's
identical layer calls trace the several bodies once.

THE WALK: where the static masks leave a tile dead, the forward, dq and
dkv kernels walk a table of the live steps (`_plan_tiles`' walk,
`_walk_table` at the file's end) instead of the whole (q tiles x kv tiles)
rectangle, so that a dead tile costs neither a grid step nor the fetch of
its blocks. The table is three int32 vectors, scalar-prefetched: step t's
outer and inner tile, which the index maps read where the rectangle's grid
gave its two inner program ids, and flags marking the first and last step
of an outer tile and a live one. Its order is the rectangle's, dead steps
left out (q tiles, each's live kv tiles ascending; for dkv kv tiles,
each's group of query heads one after the other, each's live q tiles
ascending), so every sum runs in the rectangle's order. An outer tile with
no live step keeps one step, which only initialises and writes (o = 0 and
lse = -inf, or dk = dv = 0), and fetches the inner blocks the step before
fetched. On a walked step the static masks' visibility is the table's;
the data's (kv lengths, segments, the pair mask's tile summary) is tested
in the kernel as before. The table is the same for every row: static
masks depend on neither the head nor the batch entry. A call whose static
masks leave every tile live, and the fused backward, walk the rectangle.

Trace-time tallies (`profiler.counters()`): every forward or backward
call adds the tiles of its (q tiles x kv tiles) rectangle to
``flash_tiles`` and those its static masks (causal, window) leave live
to ``flash_tiles_live``, the chunks of those live tiles to ``flash_chunks``
and those it computes to ``flash_chunks_run``, and the (q tile, kv tile)
steps that one of its kernels launches (the table's or the rectangle's)
to ``flash_grid_steps``; it counts once in ``flash_calls``, and in
``flash_two_width_calls`` too where v is narrower than q.k.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import profiler as _profiler
from ._util import resolve_interpret, x32

_NEG_INF = -1e30
# segment-id VMEM layout (the jax flash reference's SegmentIds idiom):
# q ids broadcast across the 128 lanes, kv ids across 8 sublanes, so the
# (block_q, block_k) equality mask is a repeat + a sublane-broadcast
# compare — both native Mosaic moves, no transposes
_SEG_LANES = 128
_SEG_SUBLANES = 8
# tile-padding sentinels: q pad rows and kv pad cols must never match
# each other (or any real id ≥ 0), so they get DISTINCT negatives
_SEG_PAD_Q = -2
_SEG_PAD_KV = -3
# The backward kernels work on f32 (block_q, block_k) tiles (s, p, dp,
# ds) of 4 MiB each at the default 512x2048 tiling, against Mosaic's
# default scoped-VMEM limit of 16 MiB. The un-segmented backward
# compiles under that limit; with the segment mask's repeated id tile
# the v5e compiler refused the packed S=2048 backward by 76 KiB
# (16.07 MiB needed). Interpret mode never checks VMEM, so the limit is
# stated: twice the default, a quarter of a v5e core's 128 MiB.
_BWD_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=32 * 1024 * 1024)


def _dot_precision(dtype):
    """Explicit per-dot precision: Mosaic rejects the process-wide
    'high' matmul precision that __init__.py sets for f32 numerics
    parity. Kernel blocks are f32-cast copies of the caller's data, so
    for bf16 models a DEFAULT (single-pass bf16) dot is lossless; true
    f32 inputs get HIGHEST (exact f32 via MXU passes)."""
    return (lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else lax.Precision.DEFAULT)


def _segment_mask(qseg_ref, kseg_ref, block_k, cols=slice(None)):
    """(block_q, block_k) same-segment mask from the broadcast-layout id
    tiles: q ids (block_q, 128) repeated across lane groups, kv ids one
    sublane row (1, block_k) broadcast down the sublanes; ``cols``: the
    kv tile's ``block_k`` columns that the mask covers."""
    qs = qseg_ref[0]
    if block_k > _SEG_LANES:
        qs = pltpu.repeat(qs, block_k // _SEG_LANES, axis=1)
    elif block_k < _SEG_LANES:  # never hit: block_k is a 128-multiple
        qs = qs[:, :block_k]
    return qs == kseg_ref[0][:1, cols]


def _seg_range(qrng_ref, krng_ref, i, j, n_heads):
    """The (i, j) pair's segment-id range summaries (4 SMEM scalars) —
    None refs mean no segment masking."""
    if qrng_ref is None:
        return None
    b = pl.program_id(0) // np.int32(n_heads)
    return (qrng_ref[0, b, i], qrng_ref[1, b, i],
            krng_ref[0, b, j], krng_ref[1, b, j])


def _split_rest(rest, dynamic_seg, dynamic_mask):
    """A kernel's trailing refs: (the four segment refs or Nones, the pair
    mask's tile and tile summary or Nones, the outputs and scratch)."""
    seg, pm = (None,) * 4, (None,) * 2
    if dynamic_seg:
        seg, rest = rest[:4], rest[4:]
    if dynamic_mask:
        pm, rest = rest[:2], rest[2:]
    return seg, pm, rest


def _tile_live(live_ref, i, j, rows):
    """The pair mask's summary of tile (i, j) (an SMEM scalar, nonzero where
    any pair of the tile is live); ``rows``: grid rows a batch entry. None
    refs mean no pair mask."""
    if live_ref is None:
        return None
    return live_ref[pl.program_id(0) // np.int32(rows), i, j]


def _tile_mask(pm_ref, smask, cols=slice(None)):
    """The boolean mask of a tile's columns ``cols``: the pair mask's where
    there is one (it never goes with segments), else ``smask``."""
    if pm_ref is None:
        return smask
    return pm_ref[0, :, cols].astype(jnp.int32) != 0


def _pair_mask(i, j, causal, q_offset, kv_len, block_q, block_k,
               kvl=None, smask=None, window=None, bdiff=None, span=None):
    """Validity mask for the (i, j) score block, or None when every
    position is statically visible (no kv padding, not causal, no
    per-example length, no segments) — the common dense shape skips the
    iota/where entirely. ``kvl`` is the traced per-example valid kv
    length (SMEM scalar); it subsumes the static tail-pad mask since
    kvl <= kv_len. ``smask`` is the precomputed (block_q, block_k)
    same-segment mask (packing). ``span`` (col0, width): the mask of the
    tile's ``width`` keys from key col0 alone (col0 None: the tile's
    first)."""
    col0, width = span or (None, block_k)

    def cols():
        return (j * block_k if col0 is None else col0) + \
            lax.broadcasted_iota(jnp.int32, (block_q, width), 1)
    mask = smask
    if kvl is not None:
        lm = cols() < kvl
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    elif kv_len % block_k != 0:  # padded tail block exists
        lm = cols() < kv_len
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    if causal:
        col = cols()
        row = i * block_q + q_offset + lax.broadcasted_iota(
            jnp.int32, (block_q, width), 0)
        cm = col <= row
        if window is not None:   # itself and the window - 1 before it
            cm = jnp.logical_and(cm, row - col < window)
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    if bdiff is not None:    # (half, block): `_bdiff_mask`, at the file's end
        bm = _bdiff_mask(i, j, block_q, block_k, *bdiff, span=span)
        mask = bm if mask is None else jnp.logical_and(mask, bm)
    return mask


def _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                   segrng=None, window=None, live=None, bdiff=None,
                   step=None):
    """Whether the (i, j) tile has ANY live score: causal skip, the
    window skip (the tile's last key is older than the first row's
    window), the per-example length skip (tiles starting at/after kvl
    are dead — the variable-length fast path's whole-tile saving), and
    the packed segment-range skip (disjoint id ranges cannot share a
    segment, so cross-sequence tiles cost no MXU work), and the pair mask's
    tile summary (``live``: 0 where the mask leaves no pair of the tile).
    ``step``: a walked step's live flag (THE WALK), which stands for
    the static masks' tests (causal, window, block diffusion); None on the
    rectangle's grid, which tests them here."""
    if step is None:
        q_last = (i + 1) * block_q - 1 + q_offset
        vis = jnp.logical_or(not causal, j * block_k <= q_last)
        if window is not None:
            vis = jnp.logical_and(vis, (j + 1) * block_k - 1
                                  >= i * block_q + q_offset - (window - 1))
    else:
        vis = step
    if kvl is not None:
        vis = jnp.logical_and(vis, j * block_k < kvl)
    if segrng is not None:
        qmin, qmax, kmin, kmax = segrng
        vis = jnp.logical_and(vis, jnp.logical_and(qmin <= kmax,
                                                   kmin <= qmax))
    if live is not None:
        vis = jnp.logical_and(vis, live != 0)
    if bdiff is not None and step is None:
        vis = jnp.logical_and(vis, _bdiff_live(
            i * block_q, (i + 1) * block_q - 1, j * block_k,
            (j + 1) * block_k - 1, *bdiff))
    return vis


def _chunk_cols(block_q, block_k):
    """Columns of a chunk: a partly live kv tile runs the chunks of
    ``block_q`` columns (the diagonal's and a diffusion block's step) that
    hold its live pairs, where they cut the tile into lane-aligned parts,
    else its whole width (None)."""
    if block_k > block_q and block_k % block_q == 0 and block_q % 128 == 0:
        return block_q
    return None


def _span_live(r0, r1, c0, c1, causal, q_offset, kv_len, window, bdiff,
               xp=jnp):
    """Whether rows [r0, r1] x keys [c0, c1] (inclusive) hold a pair that the
    static masks leave live: causal, the window, the kv tile's padding and
    block diffusion. jnp scalars in a kernel, numpy arrays at trace time."""
    live = c0 < kv_len
    if causal:
        live = xp.logical_and(live, c0 <= r1 + q_offset)
    if window is not None:
        live = xp.logical_and(live, c1 >= r0 + q_offset - (window - 1))
    if bdiff is not None:
        live = xp.logical_and(live, _bdiff_live(r0, r1, c0, c1, *bdiff, xp=xp))
    return live


def _span_whole(r0, r1, c0, c1, causal, q_offset, kv_len, window, bdiff,
                xp=jnp):
    """Whether the static masks leave EVERY pair of rows [r0, r1] x keys
    [c0, c1] live (`_span_live`'s twin, from the same corners)."""
    whole = c1 < kv_len
    if causal:
        whole = xp.logical_and(whole, c1 <= r0 + q_offset)
    if window is not None:
        whole = xp.logical_and(whole, r1 + q_offset - c0 < window)
    if bdiff is not None:
        whole = xp.logical_and(whole, _bdiff_whole(r0, r1, c0, c1, *bdiff,
                                                   xp=xp))
    return whole


def _plan_tiles(rows, causal, window, q_offset, kv_len, block_q, block_k, nq,
                nk, bdiff=None, group=1):
    """Trace-time: the tallies of a call, its plan and its walk, from what the
    static masks leave live (`_tile_lives`). ``flash_tiles`` += the tiles of
    its score rectangles, ``flash_tiles_live`` += those the static masks leave
    live; ``flash_chunks`` += the chunks of the live tiles,
    ``flash_chunks_run`` += those the kernels compute: every chunk of a tile
    the static masks leave wholly live, a partly live one's from its first
    live chunk to its last.

    The plan (a static kernel argument, `_bodies`): None where no live tile
    is wholly live under a static mask and no chunk can be skipped (the
    kernels trace the one masked body they always traced), else (whether
    wholly live tiles exist, the chunk's columns or None). Its live chunks
    are contiguous in every tile of the cells' calls.

    The walk (THE WALK, `_walk_table`): None where the static masks leave
    every tile live (the kernels walk the rectangle), else the tables of the
    forward's and dq's walk over q tiles and of dkv's over kv tiles, with the
    ``group`` query heads of a key/value head each."""
    statics = (causal, q_offset, kv_len, window, bdiff)
    chunk = _chunk_cols(block_q, block_k)
    lives = _tile_lives(statics, block_q, block_k, nq, nk)
    n = lives.shape[-1]
    r0 = np.arange(nq)[:, None] * block_q
    # the chunks a tile runs, its first live one to its last; a tile is live
    # where any of its chunks is
    at = np.arange(n)
    ran = np.maximum(np.where(lives, at, -1).max(-1)
                     - np.where(lives, at, n).min(-1) + 1, 0)
    live = ran > 0
    if bdiff is not None:
        _tally_bdiff(rows * nq * nk, rows * int(live.sum()))
    _profiler.count("flash_tiles", rows * nq * nk)
    _profiler.count("flash_tiles_live", rows * int(live.sum()))
    c0 = np.arange(nk)[None, :] * block_k
    whole = live & _span_whole(r0, r0 + block_q - 1, c0, c0 + block_k - 1,
                               *statics, xp=np)
    _profiler.count("flash_chunks", rows * n * int(live.sum()))
    _profiler.count("flash_chunks_run",
                    rows * int(np.where(whole, n, ran)[live].sum()))
    walk = None if live.all() else (_walk_table(live),
                                    _walk_table(live.T, group))
    masked = causal or window is not None or bdiff is not None \
        or kv_len % block_k != 0
    wholes = bool(masked and whole.any())
    skips = bool((ran[live & ~whole] < n).any())
    if not (wholes or skips):
        return None, walk
    return (wholes, chunk if skips else None), walk


def _bodies(visible, i, j, plan, block_q, block_k, statics):
    """The bodies a kernel traces for tile (i, j), as (condition, span) pairs,
    of which one runs for a visible tile (a walked one, `_plan_tiles`' walk,
    or on the rectangle's grid one that `_block_visible` passes). A span ``(col0,
    width, cols, static)`` is the tile's key rows ``cols``, keys col0 .. col0 +
    width - 1 (col0 None: from the tile's first), under every mask (``static``)
    or under what is data alone. Without a plan, one body: the tile under every
    mask. With one (`_plan_tiles`), a tile that the static masks leave wholly
    live runs its whole width under the data's masks alone, and a partly live
    one its chunks from the first live one to the last, at a static width (a
    body for each count of chunks; where no chunk can be skipped, the whole
    width). Under causal masks alone the live chunks start the tile, so the
    span's columns are static (and the data's masks, which go with no window or
    block diffusion, slice statically); else the span starts at a dynamic
    chunk. ``statics``: `_span_live`'s masks."""
    full = (None, block_k, slice(None), True)
    if plan is None:
        return [(visible, full)]
    wholes, chunk = plan
    r0, c0 = i * block_q, j * block_k
    r1 = r0 + block_q - 1
    out, partial = [], visible
    if wholes:
        whole = _span_whole(r0, r1, c0, c0 + block_k - 1, *statics)
        out.append((jnp.logical_and(visible, whole),
                    (None, block_k, slice(None), False)))
        partial = jnp.logical_and(visible, jnp.logical_not(whole))
    if chunk is None:
        return out + [(partial, full)]
    n = block_k // chunk
    prefix = statics[3] is None and statics[4] is None   # no window, bdiff
    first, last = (0 if prefix else np.int32(n)), np.int32(-1)
    for c in range(n):
        live = _span_live(r0, r1, c0 + c * chunk, c0 + (c + 1) * chunk - 1,
                          *statics)
        if not prefix:
            first = jnp.where(live, jnp.minimum(first, c), first)
        last = jnp.where(live, c, last)
    start = None if prefix else pl.multiple_of(first * chunk, chunk)
    for w in range(1, n + 1):
        span = (None, w * chunk, slice(0, w * chunk), True) if prefix else \
            (c0 + start, w * chunk, pl.ds(start, w * chunk), True)
        out.append((jnp.logical_and(partial, last - first == w - 1), span))
    return out


def _masker(i, j, kvl, qseg_ref, kseg_ref, pm_ref, causal, q_offset, kv_len,
            block_q, block_k, window, bdiff):
    """``mask(col0, width, cols, static)``: the mask of a span of tile
    (i, j) (`_bodies`), or None: the data's masks (kv lengths, segments, the
    pair mask) on the tile's columns ``cols`` and, where ``static``, the
    static ones."""
    def mask(col0, width, cols, static):
        smask = _tile_mask(pm_ref, None if qseg_ref is None else
                           _segment_mask(qseg_ref, kseg_ref, width, cols),
                           cols)
        if not static:
            return _pair_mask(i, j, False, 0, block_k, block_q, block_k, kvl,
                              smask)
        return _pair_mask(i, j, causal, q_offset, kv_len, block_q, block_k,
                          kvl, smask, window, bdiff, (col0, width))
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, kvl_ref, *rest,
                sm_scale, causal, q_offset, kv_len, block_q, block_k,
                precision, dynamic_kv, dynamic_seg, n_heads,
                window=None, dynamic_mask=False, bdiff=None, plan=None,
                walk=None):
    ((qseg_ref, kseg_ref, qrng_ref, krng_ref), (pm_ref, live_ref),
     (o_ref, lse_ref, acc_sc, m_sc, l_sc)) = _split_rest(
         rest, dynamic_seg, dynamic_mask)
    i, j, first, last, step = _grid_step(walk)
    kvl = kvl_ref[pl.program_id(0)] if dynamic_kv else None

    @pl.when(first())
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # skip: causal invisibility, a tile outside the window's band or past
    # the example's kv length, a packed tile whose segment-id ranges are
    # disjoint, or one in which the pair mask leaves nothing
    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window, _tile_live(live_ref, i, j, n_heads),
                             bdiff, step)
    mask_of = _masker(i, j, kvl, qseg_ref, kseg_ref, pm_ref, causal, q_offset,
                      kv_len, block_q, block_k, window, bdiff)

    def run(col0, width, cols, static):
        # one online-softmax update over the span's keys. q arrives
        # pre-scaled by sm_scale (host side) so no per-pair (block_q,
        # block_k) elementwise scale runs on the VPU
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, cols].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

        mask = mask_of(col0, width, cols, static)
        if mask is not None:
            s = jnp.where(mask, s, np.float32(_NEG_INF))

        m_prev = m_sc[:]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # rows with no visible key yet keep m_cur at the -1e30 sentinel;
        # exp(s - m_cur) would be exp(0)=1 there, polluting l/acc with an
        # average of V. Force p (and alpha) to 0 until a real score lands.
        seen = m_cur > np.float32(_NEG_INF / 2)
        alpha = jnp.where(seen, alpha, np.float32(0.0))
        p = jnp.where(seen, jnp.exp(s - m_cur), np.float32(0.0))
        l_sc[:] = l_sc[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, cols].astype(jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        m_sc[:] = m_cur

    for cond, span in _bodies(visible, i, j, plan, block_q, block_k,
                              (causal, q_offset, kv_len, window, bdiff)):
        pl.when(cond)(functools.partial(run, *span))

    @pl.when(last())
    def _():
        l = l_sc[:]
        l_safe = jnp.where(l == np.float32(0.0), np.float32(1.0), l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == np.float32(0.0), np.float32(_NEG_INF),
                        m_sc[:] + jnp.log(l_safe))
        lse_ref[0] = lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   kvl_ref, *rest,
                   sm_scale, causal, q_offset, kv_len, block_q, block_k,
                   precision, dynamic_kv, dynamic_seg, n_heads,
                   window=None, group=1, steps=None,
                   dynamic_mask=False, bdiff=None, plan=None, walk=None):
    ((qseg_ref, kseg_ref, qrng_ref, krng_ref), (pm_ref, live_ref),
     (dq_ref, dq_sc)) = _split_rest(rest, dynamic_seg, dynamic_mask)
    i, j, first, last, step = _grid_step(walk)
    kvl = kvl_ref[pl.program_id(0)] if dynamic_kv else None

    @pl.when(first())
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window, _tile_live(live_ref, i, j, n_heads),
                             bdiff, step)
    mask_of = _masker(i, j, kvl, qseg_ref, kseg_ref, pm_ref, causal, q_offset,
                      kv_len, block_q, block_k, window, bdiff)

    def run(col0, width, cols, static):
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, cols].astype(jnp.float32)
        v = v_ref[0, cols].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        mask = mask_of(col0, width, cols, static)
        p = jnp.exp(s - lse) if mask is None \
            else jnp.where(mask, jnp.exp(s - lse), np.float32(0.0))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - delta)
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    for cond, span in _bodies(visible, i, j, plan, block_q, block_k,
                              (causal, q_offset, kv_len, window, bdiff)):
        pl.when(cond)(functools.partial(run, *span))

    @pl.when(last())
    def _():
        # dq is wrt the ORIGINAL q: rescale once on the small (bq, d)
        # block (q was pre-scaled; ds here is wrt unscaled scores)
        dq_ref[0] = (dq_sc[:] * np.float32(sm_scale)).astype(dq_ref.dtype)


def _inner_q_tile(t, group, steps):
    """The q tile of inner step ``t`` of the (kv rows, kv tiles, q steps)
    grids: with grouped heads the axis runs the group's query heads one
    after the other, ``steps`` tiles each."""
    return t % steps if group > 1 else t


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    kvl_ref, *rest,
                    sm_scale, causal, q_offset, kv_len, block_q, block_k,
                    precision, dynamic_kv, dynamic_seg, n_heads,
                    window=None, group=1, steps=None,
                    dynamic_mask=False, bdiff=None, plan=None, walk=None):
    # grid: (B*Hk, nk, group * q steps) — q is the inner (sequential) axis
    ((qseg_ref, kseg_ref, qrng_ref, krng_ref), (pm_ref, live_ref),
     (dk_ref, dv_ref, dk_sc, dv_sc)) = _split_rest(
         rest, dynamic_seg, dynamic_mask)
    j, t, first, last, step = _grid_step(walk)
    # any query head of the row's batch entry: the lengths are per example
    kvl = kvl_ref[pl.program_id(0) * group if group > 1
                  else pl.program_id(0)] if dynamic_kv else None
    i = _inner_q_tile(t, group, steps)

    @pl.when(first())
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window,
                             _tile_live(live_ref, i, j, n_heads // group),
                             bdiff, step)
    mask_of = _masker(i, j, kvl, qseg_ref, kseg_ref, pm_ref, causal, q_offset,
                      kv_len, block_q, block_k, window, bdiff)

    def run(col0, width, cols, static):
        # adds into the span's rows of dk and dv
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, cols].astype(jnp.float32)
        v = v_ref[0, cols].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        mask = mask_of(col0, width, cols, static)
        p = jnp.exp(s - lse) if mask is None \
            else jnp.where(mask, jnp.exp(s - lse), np.float32(0.0))

        dv_sc[cols] = dv_sc[cols] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - delta)
        dk_sc[cols] = dk_sc[cols] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    for cond, span in _bodies(visible, i, j, plan, block_q, block_k,
                              (causal, q_offset, kv_len, window, bdiff)):
        pl.when(cond)(functools.partial(run, *span))

    @pl.when(last())
    def _():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      kvl_ref, *rest,
                      sm_scale, causal, q_offset, kv_len, block_q, block_k,
                      precision, dynamic_kv, dynamic_seg, n_heads,
                      window=None, group=1, steps=None,
                      dynamic_mask=False, bdiff=None, plan=None):
    """One-pass backward: dq, dk, dv from a SINGLE traversal of the
    (q block, k block) grid — the score matrix s and dp are computed
    once per pair instead of once in a dq kernel and again in a dkv
    kernel (VERDICT r2 #2: 7 block-matmuls per pair drop to 5, and
    q/do/lse/delta stream through VMEM once, not twice).

    Grid (BH, nk, nq): k outer so dk/dv accumulate in VMEM scratch;
    each (j, i) step owns a distinct dq partial block (no output
    revisiting, so no read-modify-write hazard with Pallas's input
    prefetch pipeline) and the per-k-block partials are summed by XLA
    outside the kernel.
    """
    ((qseg_ref, kseg_ref, qrng_ref, krng_ref), (pm_ref, live_ref),
     (dq_ref, dk_ref, dv_ref, dk_sc, dv_sc)) = _split_rest(
         rest, dynamic_seg, dynamic_mask)
    j, t = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    # any query head of the row's batch entry: the lengths are per example
    kvl = kvl_ref[pl.program_id(0) * group if group > 1
                  else pl.program_id(0)] if dynamic_kv else None
    i = _inner_q_tile(t, group, steps)

    @pl.when(t == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window,
                             _tile_live(live_ref, i, j, n_heads // group),
                             bdiff)
    mask_of = _masker(i, j, kvl, qseg_ref, kseg_ref, pm_ref, causal, q_offset,
                      kv_len, block_q, block_k, window, bdiff)

    def run(col0, width, cols, static):
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, cols].astype(jnp.float32)
        v = v_ref[0, cols].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        mask = mask_of(col0, width, cols, static)
        p = jnp.exp(s - lse) if mask is None \
            else jnp.where(mask, jnp.exp(s - lse), np.float32(0.0))

        dv_sc[cols] = dv_sc[cols] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - delta)
        dk_sc[cols] = dk_sc[cols] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dq_ref[0, 0] = (jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * np.float32(sm_scale)).astype(dq_ref.dtype)

    for cond, span in _bodies(visible, i, j, plan, block_q, block_k,
                              (causal, q_offset, kv_len, window, bdiff)):
        pl.when(cond)(functools.partial(run, *span))

    @pl.when(jnp.logical_not(visible))
    def _():
        # skipped pair (causal or past-kv-length): this step still owns
        # its dq partial block — zero it (output buffers start
        # uninitialized)
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(t == nq - 1)
    def _():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _pad_len(s, block):
    return ((s + block - 1) // block) * block


def _pad0(x, pad):
    """jnp.pad with a fill constant pinned to x's dtype: a bare python
    0 is weakly typed, and mixing the x32 trace region with an x64
    caller jit makes two differently-typed lowerings of jnp.pad's
    private helper collide on some jax versions (symbolic-executor
    graphs trace these pads under x64)."""
    return jnp.pad(x, pad, constant_values=np.zeros((), x.dtype))


# Tile caps: 512-wide q tiles with the kv tile as large as fits (cap
# 2048), so that up to S=2048 the kv grid is one block: k/v stay
# resident, the one-pass backward needs no dq partial-sum, and the
# (q, do, lse, delta) reloads amortize across the whole row. VMEM: the
# f32 score block is bq*bk*4 = 4 MB at 512x2048 (d<=128 keeps operand
# blocks ~1 MB), inside the ~16 MB budget. Under a static mask a wide kv
# tile costs no more than its live part: a dead tile is not walked
# (THE WALK) and a partly live one runs its live chunks alone
# (`_plan_tiles`). The caps are what every ledger run has used (S=512
# and S=8192); whether 512x2048 is right at S=2048 packed is not measured
# (ROADMAP.md Queue 2 workload 3).
_BLOCK_Q_CAP = 512
_BLOCK_K_CAP = 2048


def _pick_blocks(sq, skv, window=None):
    bq = min(_BLOCK_Q_CAP, _pad_len(sq, 8))
    bk = min(_BLOCK_K_CAP, _pad_len(skv, 128))
    if window is not None:
        # a kv tile wider than the window is mostly masked: at most the
        # window, so that a band crosses two tiles and the rest is skipped
        bk = min(bk, _pad_len(window, 128))
    return bq, bk


def _check_heads(q, k, v, window, causal, segment_ids, pair_mask=None,
                 kv_lens=None):
    """The group (query heads a key/value head) of a call, checked."""
    h, hk = q.shape[1], k.shape[1]
    if v.shape[1] != hk or h % hk:
        raise ValueError(
            f"key/value heads ({hk}, {v.shape[1]}) must agree and divide "
            f"the query heads ({h})")
    if window is not None and (not causal or window < 1):
        raise ValueError("window needs causal=True and window >= 1")
    if segment_ids is not None and (window is not None or h != hk):
        raise ValueError("segment_ids (packing) goes with neither a window "
                         "nor grouped key/value heads")
    if pair_mask is not None:
        if window is not None or segment_ids is not None or kv_lens is not None:
            raise ValueError("pair_mask goes with neither a window, "
                             "segment_ids nor kv_lens")
        want = (q.shape[0], q.shape[2], k.shape[2])
        if pair_mask.shape != want or pair_mask.dtype != jnp.int8:
            raise ValueError(f"pair_mask must be int8 {want} (batch, queries, "
                             f"keys), got {pair_mask.dtype} {pair_mask.shape}")
    return h // hk


def _q_map(group, steps):
    """The q-side index map of the (kv rows, kv tiles, q steps) grids."""
    if group == 1:
        return lambda b_, j, i: (b_, i, 0)
    g32, s32 = np.int32(group), np.int32(steps)

    def qmap(b_, j, t):
        i = _inner_q_tile(t, group, s32)
        return (b_ * g32 + t // s32, i, 0)
    return qmap


def _kv_map(group):
    """The kv-side index map of the (q rows, q tiles, kv steps) grids."""
    if group == 1:
        return lambda b_, i, j: (b_, j, 0)
    g32 = np.int32(group)
    return lambda b_, i, j: (b_ // g32, j, 0)


def _expand_kv_lens(kv_lens, b, h):
    """(B,) per-example lengths -> (B*H,) int32 whole-array SMEM
    operand (kernels index it by program_id(0); Mosaic requires either
    tile-aligned blocks or the full array, so the full tiny vector it
    is)."""
    return jnp.broadcast_to(
        kv_lens.astype(jnp.int32).reshape(b, 1), (b, h)).reshape(b * h)


def _prep_segments(segment_ids, b, sq, skv, sq_p, skv_p, block_q, block_k):
    """Host-side packed-attention operands from (B, S) segment ids:

    - qseg (B, sq_p, 128): ids broadcast across lanes (q side);
    - kseg (B, 8, skv_p): ids broadcast across sublanes (kv side);
    - qrng (2, B, nq) / krng (2, B, nk): per-tile id min/max (SMEM)
      driving the whole-block disjoint-range skip.

    Tile padding uses distinct negative sentinels per side so padded q
    rows can never match padded kv cols. Arrays are per-BATCH (not
    per-head); kernels index them with program_id(0) // n_heads."""
    seg = segment_ids.astype(jnp.int32)
    qseg = seg if sq_p == sq else jnp.pad(
        seg, ((0, 0), (0, sq_p - sq)),
        constant_values=np.int32(_SEG_PAD_Q))
    kseg = seg if skv_p == skv else jnp.pad(
        seg, ((0, 0), (0, skv_p - skv)),
        constant_values=np.int32(_SEG_PAD_KV))
    nq, nk = sq_p // block_q, skv_p // block_k
    qt = qseg.reshape(b, nq, block_q)
    kt = kseg.reshape(b, nk, block_k)
    qrng = jnp.stack([qt.min(-1), qt.max(-1)])
    krng = jnp.stack([kt.min(-1), kt.max(-1)])
    qseg = lax.broadcast_in_dim(qseg, (b, sq_p, _SEG_LANES), (0, 1))
    kseg = lax.broadcast_in_dim(kseg, (b, _SEG_SUBLANES, skv_p), (0, 2))
    return qseg, kseg, qrng, krng


def _seg_specs(block_q, block_k, n_heads, transposed_grid):
    """BlockSpecs for the four segment operands. ``transposed_grid``:
    the dkv/fused backward runs (BH, nk, nq), the fwd/dq grids run
    (BH, nq, nk) — the index maps pick the right program axes."""
    h32 = np.int32(n_heads)  # i32 divisor: index maps must stay i32
    if transposed_grid:
        qmap = lambda b_, j, i: (b_ // h32, i, 0)  # noqa: E731
        kmap = lambda b_, j, i: (b_ // h32, 0, j)  # noqa: E731
    else:
        qmap = lambda b_, i, j: (b_ // h32, i, 0)  # noqa: E731
        kmap = lambda b_, i, j: (b_ // h32, 0, j)  # noqa: E731
    return [
        pl.BlockSpec((1, block_q, _SEG_LANES), qmap,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, _SEG_SUBLANES, block_k), kmap,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]


def _prep_pair_mask(pair_mask, sq_p, skv_p, block_q, block_k):
    """The pair mask's two operands: the (B, sq_p, skv_p) int8 mask (tile
    padding dead) and its (B, nq, nk) int32 tile summary for SMEM, nonzero
    where a tile has a live pair."""
    b, sq, skv = pair_mask.shape
    if (sq_p, skv_p) != (sq, skv):
        pair_mask = _pad0(pair_mask, ((0, 0), (0, sq_p - sq), (0, skv_p - skv)))
    tiles = pair_mask.reshape(b, sq_p // block_q, block_q,
                              skv_p // block_k, block_k)
    return [pair_mask, jnp.max(tiles, axis=(2, 4)).astype(jnp.int32)]


def _mask_specs(block_q, block_k, rows, transposed_grid, group=1, steps=None):
    """BlockSpecs of the pair mask's operands; ``rows``: grid rows a batch
    entry. The (kv rows, kv tiles, q steps) grids (``transposed_grid``) run a
    group's query heads one after the other, ``steps`` q tiles each."""
    r32 = np.int32(rows)
    if not transposed_grid:
        tile = lambda b_, i, j: (b_ // r32, i, j)  # noqa: E731
    elif group == 1:
        tile = lambda b_, j, i: (b_ // r32, i, j)  # noqa: E731
    else:
        s32 = np.int32(steps)
        tile = lambda b_, j, t: (b_ // r32, t % s32, j)  # noqa: E731
    return [pl.BlockSpec((1, block_q, block_k), tile, memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM)]


@x32
def _flash_fwd(q, k, v, sm_scale, causal, q_offset, interpret,
               block_q=None, block_k=None, kv_lens=None,
               segment_ids=None, window=None, pair_mask=None, bdiff=None):
    b, h, sq, d = q.shape
    skv, dv = k.shape[2], v.shape[-1]     # q.k's width d, v's own dv
    if segment_ids is not None and sq != skv:
        raise ValueError(
            f"segment_ids (packing) requires self-attention shapes, got "
            f"sq={sq} != skv={skv}")
    group = _check_heads(q, k, v, window, causal, segment_ids, pair_mask,
                         kv_lens)
    hk = h // group
    bq0, bk0 = _pick_blocks(sq, skv, window)
    block_q = block_q or bq0
    block_k = block_k or bk0
    sq_p, skv_p = _pad_len(sq, block_q), _pad_len(skv, block_k)

    # pre-scale q so the kernels never run the (block_q, block_k)
    # elementwise *sm_scale (dq is rescaled on its small output block)
    qf = (q * sm_scale).astype(q.dtype).reshape(b * h, sq, d)
    kf = k.reshape(b * hk, skv, d)
    vf = v.reshape(b * hk, skv, dv)
    if sq_p != sq:
        qf = _pad0(qf, ((0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        kf = _pad0(kf, ((0, 0), (0, skv_p - skv), (0, 0)))
        vf = _pad0(vf, ((0, 0), (0, skv_p - skv), (0, 0)))

    bh = b * h
    dynamic_kv = kv_lens is not None
    dynamic_seg = segment_ids is not None
    kvlf = _expand_kv_lens(kv_lens, b, h) if dynamic_kv \
        else jnp.full((bh,), skv, jnp.int32)
    nq, nk = sq_p // block_q, skv_p // block_k
    plan, walk = _plan_tiles(bh, causal, window, q_offset, skv, block_q,
                             block_k, nq, nk, bdiff)
    _tally_call(d, dv, bh, walk, nq, nk)
    seg_ops = None if segment_ids is None else list(
        _prep_segments(segment_ids, b, sq, skv, sq_p, skv_p, block_q, block_k))
    mask_ops = None if pair_mask is None else _prep_pair_mask(
        pair_mask, sq_p, skv_p, block_q, block_k)
    common = dict(sm_scale=sm_scale, causal=causal, q_offset=q_offset,
                  kv_len=skv, block_q=block_q, block_k=block_k,
                  precision=_dot_precision(q.dtype), dynamic_kv=dynamic_kv,
                  dynamic_seg=dynamic_seg, n_heads=h, window=window,
                  dynamic_mask=pair_mask is not None, bdiff=bdiff, plan=plan)
    call = _flash_fwd_call if plan is None else _flash_fwd_once
    return call(qf, kf, vf, kvlf, seg_ops, mask_ops, (b, h, sq, dv),
                tuple(sorted(common.items())), interpret, q.dtype, group,
                walk and walk[0])


def _flash_fwd_call(qf, kf, vf, kvlf, seg_ops, mask_ops, dims, common,
                    interpret, q_dtype, group, walk=None):
    """The forward's kernel call on its prepared operands; ``common``: the
    kernel's static arguments, as sorted pairs; ``walk``: the table of its
    live steps (`_plan_tiles`), or None to walk the rectangle."""
    b, h, sq, dv = dims
    common = dict(common)
    block_q, block_k = common["block_q"], common["block_k"]
    d = qf.shape[-1]
    bh, sq_p = b * h, qf.shape[1]
    nq, nk = sq_p // block_q, kf.shape[1] // block_k
    kv_map = _kv_map(group)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [qf, kf, vf, kvlf]
    if seg_ops is not None:
        in_specs += _seg_specs(block_q, block_k, h, transposed_grid=False)
        operands += seg_ops
    if mask_ops is not None:
        in_specs += _mask_specs(block_q, block_k, h, transposed_grid=False)
        operands += mask_ops
    o, lse = _tiled_call(
        functools.partial(_fwd_kernel, **common), (bh, nq, nk), walk,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b_, i, j: (b_, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b_, i, j: (b_, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, dv), q_dtype),
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        name="mxtpu_flash_fwd",
        interpret=interpret,
    )(*operands)
    o = o[:, :sq].reshape(b, h, sq, dv)
    lse = lse[:, :sq, 0].reshape(b, h, sq)
    return o, lse


@x32
def _flash_bwd(q, k, v, o, lse, do, sm_scale, causal, q_offset, interpret,
               block_q=None, block_k=None, dlse=None, kv_lens=None,
               segment_ids=None, window=None, pair_mask=None, bdiff=None):
    b, h, sq, d = q.shape
    skv, dv = k.shape[2], v.shape[-1]
    hk = k.shape[1]        # the forward checked the heads
    group = h // hk
    bq0, bk0 = _pick_blocks(sq, skv, window)
    block_q = block_q or bq0
    block_k = block_k or bk0
    sq_p, skv_p = _pad_len(sq, block_q), _pad_len(skv, block_k)
    bh = b * h
    dynamic_kv = kv_lens is not None
    kvlf = _expand_kv_lens(kv_lens, b, h) if dynamic_kv \
        else jnp.full((bh,), skv, jnp.int32)
    seg_ops = None if segment_ids is None else list(
        _prep_segments(segment_ids, b, sq, skv, sq_p, skv_p,
                       block_q, block_k))
    mask_ops = None if pair_mask is None else _prep_pair_mask(
        pair_mask, sq_p, skv_p, block_q, block_k)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, sq, 1)
    if dlse is not None:
        # d lse/d s = p, so the lse cotangent enters ds = p*(dp - delta)
        # as delta_eff = delta - dlse (one extra subtract, no new kernel)
        delta = delta - dlse.astype(jnp.float32).reshape(bh, sq, 1)
    # pre-scaled q (matches forward): s = q'k^T directly; dk = ds^T q'
    # IS the original-k gradient, dq rescales by sm_scale at the write
    qf = (q * sm_scale).astype(q.dtype).reshape(bh, sq, d)
    kf = k.reshape(b * hk, skv, d)
    vf = v.reshape(b * hk, skv, dv)
    dof = do.reshape(bh, sq, dv)
    lsef = lse.reshape(bh, sq, 1)
    if sq_p != sq:
        pad = ((0, 0), (0, sq_p - sq), (0, 0))
        qf, dof = _pad0(qf, pad), _pad0(dof, pad)
        # padded q rows: lse=-inf would give exp(s - -inf)=inf; use +inf
        # so p=exp(-inf)=0 for those rows
        lsef = jnp.pad(lsef, ((0, 0), (0, sq_p - sq), (0, 0)),
                       constant_values=np.float32(np.inf))
        delta = _pad0(delta, ((0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        pad = ((0, 0), (0, skv_p - skv), (0, 0))
        kf, vf = _pad0(kf, pad), _pad0(vf, pad)

    nq, nk = sq_p // block_q, skv_p // block_k
    plan, walk = _plan_tiles(bh, causal, window, q_offset, skv, block_q,
                             block_k, nq, nk, bdiff, group)
    # the fused arm (nk <= 2, below) walks the rectangle: its docstring
    _tally_call(d, dv, bh, walk if nk > 2 else None, nq, nk)
    common = dict(sm_scale=sm_scale, causal=causal, q_offset=q_offset,
                  kv_len=skv, block_q=block_q, block_k=block_k,
                  precision=_dot_precision(q.dtype), dynamic_kv=dynamic_kv,
                  dynamic_seg=seg_ops is not None, n_heads=h,
                  window=window, group=group,
                  dynamic_mask=mask_ops is not None, bdiff=bdiff, plan=plan)

    # the fused pass writes nk f32 dq-partial copies to HBM; past nk=2
    # that memory/write cliff outweighs the recompute saving, so long
    # multi-k-block rows (S > 2*block_k cap) take the split path whose
    # dq accumulates in VMEM scratch
    args = (qf, kf, vf, dof, lsef, delta, kvlf, seg_ops,
            (b, h, sq, skv, d, dv), nq, nk, tuple(sorted(common.items())),
            interpret, k.dtype, v.dtype, q.dtype, group, mask_ops)
    if nk > 2:
        split = _flash_bwd_split if plan is None else _flash_bwd_split_once
        return split(*args, walk=walk)
    return (_flash_bwd_fused if plan is None else _flash_bwd_fused_once)(*args)


def _flash_bwd_fused(qf, kf, vf, dof, lsef, delta, kvlf, seg_ops, dims,
                     nq, nk, common, interpret, k_dtype, v_dtype, q_dtype,
                     group=1, mask_ops=None):
    """Single-pass dq/dk/dv, taken where the kv grid has at most two
    blocks: ``bert_base.train_b64x512`` (S=512, nk=1) runs this kernel;
    ``kimi_linear_48b_a3b.train_8k``'s latent attention (S=8192 at the
    2048 cap, nk=4) runs ``_flash_bwd_split``'s dq/dkv pair.

    It walks the whole (kv tiles x q steps) rectangle under every mask and
    takes no table of live steps (THE WALK), because each of its steps owns a
    dq partial block that it must write, zeros where the tile is dead; no
    cell runs it with a static mask."""
    b, h, sq, skv, d, dv = dims
    bh = b * h
    common = dict(common, steps=nq)
    block_q, block_k = common["block_q"], common["block_k"]
    sq_p, skv_p = nq * block_q, nk * block_k
    # the q side: with grouped heads the inner axis runs the group's query
    # heads one after the other, nq tiles each
    q_map = _q_map(group, nq)
    if group == 1:
        dq_map = lambda b_, j, i: (b_, j, i, 0)  # noqa: E731
    else:
        def dq_map(b_, j, t):
            row, i, _ = q_map(b_, j, t)
            return (row, j, i, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv), lambda b_, j, i: (b_, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, dv), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [qf, kf, vf, dof, lsef, delta, kvlf]
    if seg_ops is not None:
        in_specs += _seg_specs(block_q, block_k, h, transposed_grid=True)
        operands += seg_ops
    if mask_ops is not None:
        in_specs += _mask_specs(block_q, block_k, h // group, True, group, nq)
        operands += mask_ops
    dq_part, dk, dvg = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, **common),
        grid=(bh // group, nk, group * nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), dq_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            # f32 partials: the cross-k-block sum happens outside the
            # kernel in f32, then casts once to the caller dtype
            jax.ShapeDtypeStruct((bh, nk, sq_p, d), jnp.float32),
            jax.ShapeDtypeStruct((bh // group, skv_p, d), k_dtype),
            jax.ShapeDtypeStruct((bh // group, skv_p, dv), v_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=_BWD_COMPILER_PARAMS,
        name="mxtpu_flash_bwd_fused",
        interpret=interpret,
    )(*operands)

    dq = dq_part.sum(axis=1).astype(q_dtype) if nk > 1 \
        else dq_part[:, 0].astype(q_dtype)
    dq = dq[:, :sq].reshape(b, h, sq, d)
    dk = dk[:, :skv].reshape(b, h // group, skv, d)
    dvg = dvg[:, :skv].reshape(b, h // group, skv, dv)
    return dq, dk, dvg


def _flash_bwd_split(qf, kf, vf, dof, lsef, delta, kvlf, seg_ops, dims,
                     nq, nk, common, interpret, k_dtype, v_dtype, q_dtype,
                     group=1, mask_ops=None, walk=None):
    """The dq kernel and the dkv kernel; ``walk``: the tables of their live
    steps (`_plan_tiles`: q tile-major and kv tile-major), or None to walk
    the rectangles."""
    b, h, sq, skv, d, dv = dims
    bh = b * h
    common = dict(common)
    block_q, block_k = common["block_q"], common["block_k"]
    sq_p, skv_p = nq * block_q, nk * block_k
    common = dict(common, steps=nq)
    kv_map = _kv_map(group)
    q_map = _q_map(group, nq)
    walk_dq, walk_dkv = walk or (None, None)

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, dv), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [qf, kf, vf, dof, lsef, delta, kvlf]
    if seg_ops is not None:
        dq_specs += _seg_specs(block_q, block_k, h, transposed_grid=False)
        operands += seg_ops
    if mask_ops is not None:
        dq_specs += _mask_specs(block_q, block_k, h, transposed_grid=False)
        operands += mask_ops
    dq = _tiled_call(
        functools.partial(_bwd_dq_kernel, **common), (bh, nq, nk), walk_dq,
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_BWD_COMPILER_PARAMS,
        name="mxtpu_flash_bwd_dq",
        interpret=interpret,
    )(*operands)

    dkv_specs = [
        pl.BlockSpec((1, block_q, d), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv), lambda b_, j, i: (b_, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, dv), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    if seg_ops is not None:
        dkv_specs += _seg_specs(block_q, block_k, h, transposed_grid=True)
    if mask_ops is not None:
        dkv_specs += _mask_specs(block_q, block_k, h // group, True, group,
                                 nq)
    dk, dvg = _tiled_call(
        functools.partial(_bwd_dkv_kernel, **common),
        (bh // group, nk, group * nq), walk_dkv,
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dv), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh // group, skv_p, d), k_dtype),
            jax.ShapeDtypeStruct((bh // group, skv_p, dv), v_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=_BWD_COMPILER_PARAMS,
        name="mxtpu_flash_bwd_dkv",
        interpret=interpret,
    )(*operands)

    dq = dq[:, :sq].reshape(b, h, sq, d)
    dk = dk[:, :skv].reshape(b, h // group, skv, d)
    dvg = dvg[:, :skv].reshape(b, h // group, skv, dv)
    return dq, dk, dvg


# A call with a plan traces several bodies a kernel (`_bodies`), a cost paid
# again for each identical call of a model's layers, and in every process
# whether the compile cache holds the program or not. Through jax.jit, keyed
# by the static arguments and the operands' shapes, a program traces and
# lowers them once for all its identical calls.
_STATIC = ("dims", "common", "interpret", "q_dtype", "group")
_BWD_STATIC = _STATIC + ("nq", "nk", "k_dtype", "v_dtype")
_flash_fwd_once = jax.jit(_flash_fwd_call, static_argnames=_STATIC + ("walk",))
_flash_bwd_fused_once = jax.jit(_flash_bwd_fused, static_argnames=_BWD_STATIC)
_flash_bwd_split_once = jax.jit(_flash_bwd_split,
                                static_argnames=_BWD_STATIC + ("walk",))


def _int_ct(x):
    """Cotangent for an integer tensor argument (kv_lens, segment_ids):
    None when absent, float0 zeros when present (custom_vjp contract
    for int primals)."""
    if x is None:
        return None
    return np.zeros(x.shape, jax.dtypes.float0)


# The two values of a forward call that the backward reads and cannot
# derive from q, k, v short of running the forward again: the output
# (one activation, the size of q) and the rows' log-sum-exp (1/head_dim
# of that, float32). They carry these `checkpoint_name`s, and a
# rematerialised block keeps values so named (gluon/block.py reads this
# tuple into its default checkpoint policy) instead of rebuilding them
# with a second forward call in its backward. Outside a checkpoint a
# name is the identity and lowers to nothing.
REMAT_KEEP = ("flash_out", "flash_lse")


def _fwd_named(q, k, v, sm_scale, causal, q_offset, interpret, kv_lens,
               segment_ids, window=None, pair_mask=None, bdiff=None):
    """The forward that the primals and the VJPs' forward rules share:
    (out, lse) under their `REMAT_KEEP` names."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    o, lse = _flash_fwd(q, k, v, sm_scale, bool(causal), int(q_offset),
                        resolve_interpret(interpret), kv_lens=kv_lens,
                        segment_ids=segment_ids, window=window,
                        pair_mask=pair_mask, bdiff=bdiff)
    named = []
    for name, x in zip(REMAT_KEEP, (o, lse)):
        _profiler.note_named(name, x)   # trace time: `remat_kept`'s tally
        named.append(checkpoint_name(x, name))
    return tuple(named)


def _flash_vjp_bwd(sm_scale, causal, q_offset, interpret, window, res, do,
                   dlse=None):
    q, k, v, o, lse, kv_lens, segment_ids, pair_mask = res
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, sm_scale, bool(causal),
                            int(q_offset), resolve_interpret(interpret),
                            dlse=dlse, kv_lens=kv_lens,
                            segment_ids=segment_ids, window=window,
                            pair_mask=pair_mask)
    return (dq, dk, dv, _int_ct(kv_lens), _int_ct(segment_ids),
            _int_ct(pair_mask))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 9))
def flash_attention_with_lse(q, k, v, sm_scale=None, causal=False,
                             q_offset=0, interpret=None, kv_lens=None,
                             segment_ids=None, window=None, pair_mask=None):
    """Flash attention returning (out, lse) — DIFFERENTIABLE in both
    outputs (the lse cotangent folds into the backward's delta term).

    lse has shape (B, H, Sq), fp32 — the combiner state blockwise/ring
    schemes need; ring_attention folds per-chunk (out, lse) pairs with
    the log-sum-exp combiner and lets gradients flow through both.
    ``kv_lens`` (B,) int32 masks keys at/after each example's length.
    ``segment_ids`` (B, S) int32 restricts attention to same-segment
    pairs (sequence packing; see the module docstring). ``window``,
    ``pair_mask`` and grouped key/value heads as in :func:`flash_attention`.
    """
    return _fwd_named(q, k, v, sm_scale, causal, q_offset, interpret,
                      kv_lens, segment_ids, window, pair_mask)


def _flash_lse_vjp_fwd(q, k, v, sm_scale, causal, q_offset, interpret,
                       kv_lens=None, segment_ids=None, window=None,
                       pair_mask=None):
    o, lse = _fwd_named(q, k, v, sm_scale, causal, q_offset, interpret,
                        kv_lens, segment_ids, window, pair_mask)
    # the primal output IS the named value: one kept array serves both
    return (o, lse), (q, k, v, o, lse, kv_lens, segment_ids, pair_mask)


def _flash_lse_vjp_bwd(sm_scale, causal, q_offset, interpret, window, res,
                       cts):
    return _flash_vjp_bwd(sm_scale, causal, q_offset, interpret, window, res,
                          *cts)


flash_attention_with_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 9))
def flash_attention(q, k, v, sm_scale=None, causal=False, q_offset=0,
                    interpret=None, kv_lens=None, segment_ids=None,
                    window=None, pair_mask=None):
    """softmax(q k^T * scale [+causal/length/segment mask]) v,
    blockwise in VMEM. ``kv_lens`` (B,) int32 masks keys at/after each
    example's valid length (variable-length batches, e.g. BERT
    padding); ``segment_ids`` (B, S) int32 makes attention
    block-diagonal over packed sequences (see module docstring). v may
    be narrower than q and k (q, k (..., D), v (..., Dv)): the output,
    dv and the p.v products are Dv wide (``mx.nd.flash_attention`` pads
    latent attention's q.k 192 to 256, leaves v at 128 and passes the
    true ``sm_scale``).
    ``window`` (static, with ``causal``): a token sees itself and the
    ``window - 1`` keys before it; tiles outside the band are not walked.
    k and v may have fewer heads than q (q (B, H, S, D), k/v (B, Hk, S, D),
    Hk divides H): query head h reads key/value head h // (H / Hk), and
    dk, dv come back with Hk heads, summed over each group.
    ``pair_mask`` (B, Sq, Skv) int8, DATA and not a static argument: query t
    sees key s only where ``pair_mask[b, t, s]`` is nonzero (and the other
    masks allow it); one mask for all the heads of a batch entry. Tiles in
    which it leaves nothing are skipped, forward and backward."""
    return _fwd_named(q, k, v, sm_scale, causal, q_offset, interpret,
                      kv_lens, segment_ids, window, pair_mask)[0]


def _flash_vjp_fwd(q, k, v, sm_scale, causal, q_offset, interpret,
                   kv_lens=None, segment_ids=None, window=None,
                   pair_mask=None):
    o, lse = _fwd_named(q, k, v, sm_scale, causal, q_offset, interpret,
                        kv_lens, segment_ids, window, pair_mask)
    return o, (q, k, v, o, lse, kv_lens, segment_ids, pair_mask)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# -- paged KV decode path ---------------------------------------------------
# The packed path above requires Sq == Skv (self attention over one
# packed row). Autoregressive DECODE is the opposite shape: one (or a
# small chunk of) query token(s) per sequence against a long per-
# sequence KV history that lives in a PAGED pool (serving/kvcache.py —
# the vLLM layout: fixed-size pages, per-sequence page tables). This
# kernel lifts the restriction for that case: K/V are read THROUGH the
# page table — the table rides as a scalar-prefetch operand so each
# (batch row, head, logical page) grid step DMAs exactly the physical
# page it needs — with per-row ``kv_len`` masking and a whole-page
# skip for table slots at/after each row's length. Forward-only by
# design (decode is inference; the training path keeps the packed
# kernel above).

def _paged_fwd_kernel(tbl_ref, kvl_ref, q_ref, k_ref, v_ref, o_ref,
                      acc_sc, m_sc, l_sc, *, sq, page_size, block_q,
                      precision):
    b, j = pl.program_id(0), pl.program_id(2)
    npages = pl.num_programs(2)
    kvl = kvl_ref[b]

    @pl.when(j == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # whole-page skip: a table slot at/after ceil(kvl / page_size) holds
    # padding (or a recycled page) — no MXU work, no pollution
    @pl.when(j * page_size < kvl)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (page_size, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        col = j * page_size + lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1)
        row = lax.broadcasted_iota(jnp.int32, (block_q, page_size), 0)
        # q chunk row i sits at global position kvl - sq + i (the chunk
        # is the TAIL of the sequence, already written to the pages):
        # causal decode masks cols past that position; col < kvl also
        # bounds q pad rows (block_q >= sq) to written history only
        mask = jnp.logical_and(col <= kvl - np.int32(sq) + row,
                               col < kvl)
        s = jnp.where(mask, s, np.float32(_NEG_INF))
        m_prev = m_sc[:]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        seen = m_cur > np.float32(_NEG_INF / 2)
        alpha = jnp.where(seen, jnp.exp(m_prev - m_cur), np.float32(0.0))
        p = jnp.where(seen, jnp.exp(s - m_cur), np.float32(0.0))
        l_sc[:] = l_sc[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        m_sc[:] = m_cur

    @pl.when(j == npages - 1)
    def _():
        l = l_sc[:]
        l_safe = jnp.where(l == np.float32(0.0), np.float32(1.0), l)
        o_ref[0, 0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)


@x32
def paged_flash_attention(q, k_pages, v_pages, page_table, kv_lens,
                          sm_scale=None, interpret=None):
    """Decode-path flash attention over a paged KV pool.

    Shapes::

        q          (B, H, Sq, D)   the last Sq tokens of each sequence
                                   (Sq=1 steady-state decode; small Sq
                                   for chunked prefill)
        k_pages    (P, H, page_size, D)   the pool (all sequences)
        v_pages    (P, H, page_size, D)
        page_table (B, NP) int32   per-row physical page ids, padded
                                   with any in-range id past the row's
                                   ceil(kv_len / page_size) pages
        kv_lens    (B,) int32      per-row written history length,
                                   INCLUDING the Sq query tokens

    K/V are gathered through the page table inside the kernel (the
    table is a scalar-prefetch operand driving the page DMA index
    map); columns at/after each row's ``kv_len`` are masked and whole
    dead pages are skipped. Causal within the chunk: q row ``i`` sees
    positions ``<= kv_len - Sq + i``. Rows whose ``kv_len`` is 0 emit
    exact zeros. Forward-only (inference); differentiation is
    unsupported by design.
    """
    b, h, sq, d = q.shape
    p_, hk, page_size, dk = k_pages.shape
    if (hk, dk) != (h, d) or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"page pool shape {k_pages.shape}/{v_pages.shape} does not "
            f"match q heads/dim ({h}, {d})")
    if page_table.ndim != 2 or page_table.shape[0] != b:
        raise ValueError(
            f"page_table must be (B={b}, NP), got {page_table.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    npages = page_table.shape[1]
    block_q = _pad_len(sq, 8)
    qf = (q * sm_scale).astype(q.dtype)
    if block_q != sq:
        qf = _pad0(qf, ((0, 0), (0, 0), (0, block_q - sq), (0, 0)))
    kern = functools.partial(
        _paged_fwd_kernel, sq=sq, page_size=page_size, block_q=block_q,
        precision=_dot_precision(q.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, npages),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, j, tbl, kvl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b_, h_, j, tbl, kvl: (tbl[b_, j], h_,
                                                      0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b_, h_, j, tbl, kvl: (tbl[b_, j], h_,
                                                      0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d),
            lambda b_, h_, j, tbl, kvl: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ])
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, block_q, d), q.dtype),
        name="mxtpu_paged_flash_fwd",
        interpret=resolve_interpret(interpret),
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
      qf, k_pages, v_pages)
    return out[:, :, :sq]


def paged_attention_reference(q, k_pages, v_pages, page_table, kv_lens,
                              sm_scale=None):
    """Dense jnp reference for :func:`paged_flash_attention` — the
    golden the kernel tests compare against, and the CPU fallback the
    decode model uses off-TPU. Gathers the table'd pages, masks
    columns past each row's ``kv_len`` (causal within the Sq chunk)
    and runs a plain max-subtracted softmax. Every row's computation
    is independent of the others — the property the join/leave
    solo-parity golden leans on. One head width ``d`` for q, k and v, as
    the paged kernel: unequal q.k and v widths (latent attention) are the
    training kernel's (``flash_attention`` takes v at its own width)."""
    b, h, sq, d = q.shape
    page_size = k_pages.shape[2]
    npages = page_table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    # (B, NP, H, page, D) -> (B, H, NP*page, D)
    k = jnp.moveaxis(k_pages[page_table], 2, 1) \
        .reshape(b, h, npages * page_size, d)
    v = jnp.moveaxis(v_pages[page_table], 2, 1) \
        .reshape(b, h, npages * page_size, d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * sm_scale,
                   k.astype(jnp.float32))
    col = jnp.arange(npages * page_size, dtype=jnp.int32)
    row = jnp.arange(sq, dtype=jnp.int32)
    kvl = kv_lens.astype(jnp.int32)[:, None, None, None]
    mask = jnp.logical_and(
        col[None, None, None, :]
        <= kvl - np.int32(sq) + row[None, None, :, None],
        col[None, None, None, :] < kvl)
    s = jnp.where(mask, s, np.float32(_NEG_INF))
    m = jnp.max(s, axis=-1, keepdims=True)
    seen = m > np.float32(_NEG_INF / 2)
    p = jnp.where(seen, jnp.exp(s - m), np.float32(0.0))
    l = jnp.sum(p, axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, np.float32(1.0), l)
    return (jnp.einsum("bhqk,bhkd->bhqd", p / l,
                       v.astype(jnp.float32))).astype(q.dtype)


def _tally_call(d, dv, rows, walk, nq, nk):
    """Trace-time: ``flash_calls`` += 1 a forward or backward call,
    ``flash_two_width_calls`` += 1 where v's width is narrower than q.k's,
    ``flash_grid_steps`` += the (q tile, kv tile) steps that one of its
    kernels (the forward's or dq's, or the fused one) launches, in
    ``flash_tiles``' unit: ``rows`` x the length of its ``walk`` table, or x
    nq x nk on the rectangle."""
    _profiler.count("flash_calls")
    _profiler.count("flash_grid_steps",
                    rows * (len(walk[0][0]) if walk else nq * nk))
    if dv < d:
        _profiler.count("flash_two_width_calls")


# ----------------------------------------------------------------------
# A STATIC BLOCK-DIFFUSION MASK (BD3-LM, Arriola et al., ICLR 2025;
# ``flash_attention_block_diffusion``; ``bdiff`` = (L, block) in the
# kernels): q, k and v span a row's clean copy and its noised copy, the clean
# L positions first. Inside a live tile the mask is written from indices
# (``_bdiff_mask``); a tile it leaves empty (``_bdiff_live``, from the tile's
# corners) is not walked (THE WALK), or in the fused arm is skipped in
# the kernel, with grouped heads too; no (B, 2L, 2L) operand is read.
# Absent, every other call traces the jaxpr it traced before
# (``tests/test_sdar_blocks.py``). A call also adds to the tallies
# ``bdiff_calls``, ``bdiff_tiles`` and ``bdiff_tiles_live``.
# ----------------------------------------------------------------------
def _blocks_of(x, block):
    """``x // block`` for non-negative integers (a shift where ``block`` is a
    power of two); numpy arrays, traced scalars and vectors alike."""
    if block & (block - 1) == 0:
        return x >> (int(block).bit_length() - 1)
    return x // block


def _bdiff_live(r0, r1, c0, c1, half, block, xp=jnp):
    """Whether rows [r0, r1] x keys [c0, c1] (inclusive; positions of the 2 *
    ``half`` of a clean-first row, past it tile padding) hold a pair the
    block-diffusion mask leaves live: a clean row sees the clean keys of its
    own and earlier blocks; a noisy row the clean keys of earlier blocks and
    the noisy keys of its own block; a clean row no noisy key."""
    n, blk = 2 * half, functools.partial(_blocks_of, block=block)
    r1, c1 = xp.minimum(r1, n - 1), xp.minimum(c1, n - 1)
    clean_r1, clean_c1 = xp.minimum(r1, half - 1), xp.minimum(c1, half - 1)
    clean_keys = c0 <= clean_c1
    clean_rows = (r0 <= clean_r1) & clean_keys & (blk(c0) <= blk(clean_r1))
    noisy_rows = (r1 >= half) & (r0 <= r1)
    a0, a1 = xp.maximum(r0, half) - half, r1 - half
    b0, b1 = xp.maximum(c0, half) - half, c1 - half
    noisy_clean = noisy_rows & clean_keys & (blk(c0) < blk(a1))
    noisy_noisy = (noisy_rows & (c1 >= half) & (c0 <= c1)
                   & (blk(b0) <= blk(a1)) & (blk(a0) <= blk(b1)))
    return clean_rows | noisy_clean | noisy_noisy


def _bdiff_whole(r0, r1, c0, c1, half, block, xp=jnp):
    """Whether the block-diffusion mask leaves EVERY pair of rows [r0, r1] x
    keys [c0, c1] live (`_bdiff_live`'s twin, from the same corners; rows past
    the row's end are tile padding, keys past it are `_span_whole`'s): clean
    rows against clean keys of their own or earlier blocks, noisy rows
    against clean keys of earlier blocks, or noisy rows against the noisy
    keys of the one block that holds them all."""
    blk = functools.partial(_blocks_of, block=block)
    r1 = xp.minimum(r1, 2 * half - 1)
    clean = (r1 < half) & (c1 < half) & (blk(c1) <= blk(r0))
    noisy = r0 >= half
    noisy_clean = noisy & (c1 < half) & (blk(c1) < blk(r0 - half))
    own = (noisy & (c0 >= half) & (blk(c0 - half) == blk(r1 - half))
           & (blk(c1 - half) == blk(r0 - half)))
    return clean | noisy_clean | own


def _bdiff_mask(i, j, block_q, block_k, half, block, span=None):
    """The (block_q, block_k) block-diffusion mask of tile (i, j) (of its
    ``span``, as `_pair_mask`'s), from a column of row positions and a row of
    key positions: a key's code is its block, + ``half`` if it is noisy; a
    row sees the codes up to its last visible clean block and, if noisy, the
    one code of its own noisy block."""
    col0, width = span or (None, block_k)
    row = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    col = (j * block_k if col0 is None else col0) + \
        lax.broadcasted_iota(jnp.int32, (1, width), 1)
    rn = (row >= half).astype(jnp.int32)
    cn = (col >= half).astype(jnp.int32)
    rb = _blocks_of(row - half * rn, block)
    code = _blocks_of(col - half * cn, block) + half * cn
    last_clean = rb - rn
    own = jnp.where(rn != 0, rb + half, -1)
    return jnp.logical_or(code <= last_clean, code == own)


def block_diffusion_mask(n, block):
    """The (n, n) boolean mask the kernels apply, as one array (the op's
    ``jax.numpy`` form): n positions, the clean copy's n / 2 first."""
    return _bdiff_mask(0, 0, n, n, n // 2, int(block))


def _tally_bdiff(tiles, live):
    """Trace-time: ``bdiff_calls`` += 1 a forward or backward call,
    ``bdiff_tiles`` / ``bdiff_tiles_live`` += its tiles and those the mask
    leaves live."""
    _profiler.count("bdiff_calls")
    _profiler.count("bdiff_tiles", tiles)
    _profiler.count("bdiff_tiles_live", live)


def _bdiff_args(q, k, v, block):
    if q.shape[2] != k.shape[2] or q.shape[2] % 2 or int(block) < 1:
        raise ValueError(
            f"block diffusion needs one even length for q and k (a clean and a "
            f"noised copy) and a block of at least 1, got {q.shape[2]}, "
            f"{k.shape[2]} and {block}")
    _check_heads(q, k, v, None, False, None)
    return q.shape[2] // 2, int(block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_block_diffusion(q, k, v, block, sm_scale=None,
                                    interpret=None):
    """softmax(q k^T * scale + M) v over a row's clean copy and its noised
    copy, the clean copy first: q, k (B, H|Hk, 2L, D), v (B, Hk, 2L, Dv), L
    positions each, both copies at positions 0 .. L - 1. ``block`` (static)
    is the diffusion block length; with blk(t) = (t mod L) // block, M lets a
    clean query t see the clean keys s with blk(s) <= blk(t); a noisy query
    the clean keys with blk(s) < blk(t) and the noisy keys with blk(s) =
    blk(t); a clean query no noisy key. From indices in every arm (forward,
    fused backward, dq and dkv), with grouped key/value heads; tiles the mask
    leaves empty are skipped whole. Tallies ``bdiff_calls`` / ``bdiff_tiles``
    / ``bdiff_tiles_live``; the output and log-sum-exp carry the
    `REMAT_KEEP` names."""
    bdiff = _bdiff_args(q, k, v, block)
    return _fwd_named(q, k, v, sm_scale, False, 0, interpret, None, None,
                      bdiff=bdiff)[0]


def _bdiff_vjp_fwd(q, k, v, block, sm_scale, interpret):
    bdiff = _bdiff_args(q, k, v, block)
    o, lse = _fwd_named(q, k, v, sm_scale, False, 0, interpret, None, None,
                        bdiff=bdiff)
    return o, (q, k, v, o, lse)


def _bdiff_vjp_bwd(block, sm_scale, interpret, res, do):
    q, k, v, o, lse = res
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash_bwd(q, k, v, o, lse, do, sm_scale, False, 0,
                      resolve_interpret(interpret),
                      bdiff=(q.shape[2] // 2, int(block)))


flash_attention_block_diffusion.defvjp(_bdiff_vjp_fwd, _bdiff_vjp_bwd)


# ----------------------------------------------------------------------
# THE WALK (the module docstring's): a call whose static masks leave a tile
# dead walks a table of its live steps, scalar-prefetched, in place of the
# (outer tiles x inner steps) rectangle. The tables are numpy at trace time,
# built from the static masks and shapes alone, and reach the jitted call
# wrappers as static arguments (tuples), so identical calls trace once.
# ----------------------------------------------------------------------
_FIRST, _LAST, _LIVE = 1, 2, 4     # a step's flags


def _tile_lives(statics, block_q, block_k, nq, nk):
    """Trace-time: (nq, nk, chunks a tile) bool, whether the static masks
    (`_span_live`'s ``statics``) leave a pair of each chunk of each tile
    live; a tile is live where any of its chunks is (`_chunk_cols`: one chunk
    a tile where tiles do not split)."""
    width = _chunk_cols(block_q, block_k) or block_k
    n = block_k // width
    r0 = np.arange(nq)[:, None] * block_q
    c0 = np.arange(nk * n)[None, :] * width
    return np.broadcast_to(_span_live(r0, r0 + block_q - 1, c0, c0 + width - 1,
                                      *statics, xp=np),
                           (nq, nk * n)).reshape(nq, nk, n)


def _walk_table(live, group=1):
    """The steps of a walk over ``live`` (outer tiles x inner tiles, bool), as
    (outer, inner, flags) tuples of ints: each outer tile's live inner tiles
    ascending, ``group`` times over (the g-th time as g x the inner tiles +
    the tile, as the rectangle's inner axis counts), the first and last step
    of an outer tile flagged, and every step that computes flagged live. An
    outer tile with no live inner tile keeps one step, not live, on the inner
    tile the step before fetched (an anchor: it initialises and writes)."""
    n = live.shape[1]
    outer, inner, flags = [], [], []
    for a, row in enumerate(live):
        steps = [g * n + int(b) for g in range(group)
                 for b in np.flatnonzero(row)]
        on = _LIVE if steps else 0
        steps = steps or [inner[-1] if inner else 0]
        for s, b in enumerate(steps):
            outer.append(a)
            inner.append(b)
            flags.append(on | (_FIRST if s == 0 else 0)
                         | (_LAST if s == len(steps) - 1 else 0))
    return tuple(outer), tuple(inner), tuple(flags)


def _grid_step(walk):
    """A kernel's position: (outer tile, inner step, first(), last(), live),
    from the rectangle's program ids (``walk`` None: ``live`` None, the static
    masks tested in the kernel) or from the table's refs at this step (the
    step's live flag). ``first`` / ``last`` are called where the step's
    scratch is set and its output written, so that a kernel on the rectangle
    traces the operations it traced before the walk, in their order."""
    if walk is None:
        a, b = pl.program_id(1), pl.program_id(2)
        n = pl.num_programs(2)
        return a, b, lambda: b == 0, lambda: b == n - 1, None
    outer, inner, flags = walk
    t = pl.program_id(1)
    f = flags[t]
    return (outer[t], inner[t], lambda: (f & _FIRST) != 0,
            lambda: (f & _LAST) != 0, (f & _LIVE) != 0)


def _on_table(spec):
    """A BlockSpec of the rectangle's (rows, outer, inner) grid, on a walk's
    (rows, step) grid: its index map reads the step's tiles from the table."""
    if spec.index_map is None:           # a whole array (SMEM)
        return spec
    at = spec.index_map
    return dataclasses.replace(
        spec, index_map=lambda r, t, outer, inner, flags: at(r, outer[t],
                                                             inner[t]))


def _walked(kernel, outer_ref, inner_ref, flags_ref, *refs):
    return kernel(*refs, walk=(outer_ref, inner_ref, flags_ref))


def _tiled_call(kernel, grid, walk, *, in_specs, out_specs, scratch_shapes,
                **kw):
    """``pl.pallas_call`` of a flash kernel on the rectangle ``grid`` (rows,
    outer tiles, inner steps), or, given a ``walk`` table, on (rows, its
    steps): the table's three vectors go first, as scalar-prefetch operands,
    and the kernel reads them as ``walk``."""
    if walk is None:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs,
                              scratch_shapes=scratch_shapes, **kw)
    outs = [_on_table(s) for s in out_specs] if isinstance(out_specs, list) \
        else _on_table(out_specs)
    call = pl.pallas_call(
        functools.partial(_walked, kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(grid[0], len(walk[0])),
            in_specs=[_on_table(s) for s in in_specs], out_specs=outs,
            scratch_shapes=scratch_shapes),
        **kw)
    tables = [jnp.asarray(np.asarray(x, np.int32)) for x in walk]
    return lambda *operands: call(*tables, *operands)
