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

Shapes: q (B, H, Sq, D), k/v (B, H, Skv, D). ``q_offset`` is the
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
the band cost nothing: ``_block_visible`` skips them, and the forward
and the split backward do not even walk them. Their grids' inner axis
is narrowed to the most tiles a band crosses (``_band_steps``): step jj
of q tile i visits kv tile ``_kv_tile(i, jj)``, the band's first tile
+ jj (``mxtpu_flash_fwd``, ``mxtpu_flash_bwd_dq``); step ii of kv tile
j visits q tile ``_q_tile(j, ii)`` (``mxtpu_flash_bwd_dkv``). The fused
backward (at most two kv tiles: rows no longer than two windows) walks
its whole (kv, q) grid and skips by ``_block_visible`` alone.

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

Trace-time tallies (`profiler.counters()`): every forward or backward
call adds the tiles of its (q tiles x kv tiles) rectangle to
``flash_tiles`` and those its static masks (causal, window) leave live
to ``flash_tiles_live``.
"""
from __future__ import annotations

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


def _segment_mask(qseg_ref, kseg_ref, block_k):
    """(block_q, block_k) same-segment mask from the broadcast-layout id
    tiles: q ids (block_q, 128) repeated across lane groups, kv ids one
    sublane row (1, block_k) broadcast down the sublanes."""
    qs = qseg_ref[0]
    if block_k > _SEG_LANES:
        qs = pltpu.repeat(qs, block_k // _SEG_LANES, axis=1)
    elif block_k < _SEG_LANES:  # never hit: block_k is a 128-multiple
        qs = qs[:, :block_k]
    return qs == kseg_ref[0][:1, :]


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


def _tile_mask(pm_ref, smask):
    """The (block_q, block_k) boolean mask of a tile: the pair mask's where
    there is one (it never goes with segments), else ``smask``."""
    if pm_ref is None:
        return smask
    return pm_ref[0].astype(jnp.int32) != 0


def _pair_mask(i, j, causal, q_offset, kv_len, block_q, block_k,
               kvl=None, smask=None, window=None):
    """Validity mask for the (i, j) score block, or None when every
    position is statically visible (no kv padding, not causal, no
    per-example length, no segments) — the common dense shape skips the
    iota/where entirely. ``kvl`` is the traced per-example valid kv
    length (SMEM scalar); it subsumes the static tail-pad mask since
    kvl <= kv_len. ``smask`` is the precomputed (block_q, block_k)
    same-segment mask (packing)."""
    mask = smask
    if kvl is not None:
        col = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        lm = col < kvl
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    elif kv_len % block_k != 0:  # padded tail block exists
        col = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        lm = col < kv_len
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    if causal:
        col = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        row = i * block_q + q_offset + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cm = col <= row
        if window is not None:   # itself and the window - 1 before it
            cm = jnp.logical_and(cm, row - col < window)
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    return mask


def _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                   segrng=None, window=None, live=None):
    """Whether the (i, j) tile has ANY live score: causal skip, the
    window skip (the tile's last key is older than the first row's
    window), the per-example length skip (tiles starting at/after kvl
    are dead — the variable-length fast path's whole-tile saving), and
    the packed segment-range skip (disjoint id ranges cannot share a
    segment, so cross-sequence tiles cost no MXU work), and the pair mask's
    tile summary (``live``: 0 where the mask leaves no pair of the tile)."""
    q_last = (i + 1) * block_q - 1 + q_offset
    vis = jnp.logical_or(not causal, j * block_k <= q_last)
    if window is not None:
        vis = jnp.logical_and(
            vis, (j + 1) * block_k - 1 >= i * block_q + q_offset - (window - 1))
    if kvl is not None:
        vis = jnp.logical_and(vis, j * block_k < kvl)
    if segrng is not None:
        qmin, qmax, kmin, kmax = segrng
        vis = jnp.logical_and(vis, jnp.logical_and(qmin <= kmax,
                                                   kmin <= qmax))
    if live is not None:
        vis = jnp.logical_and(vis, live != 0)
    return vis


def _kv_tile(i, jj, window, q_offset, block_q, block_k):
    """The kv tile that step ``jj`` of q tile ``i``'s narrowed walk visits:
    the first tile of the rows' band, + jj."""
    first = jnp.maximum(i * block_q + (q_offset - (window - 1)), 0) // block_k
    return first + jj


def _q_tile(j, ii, q_offset, block_q, block_k):
    """The q tile that step ``ii`` of kv tile ``j``'s narrowed walk visits:
    the first tile whose rows see the tile's keys (causal), + ii."""
    return jnp.maximum(j * block_k - q_offset, 0) // block_q + ii


def _band_steps(window, q_offset, block_q, block_k, nq, nk):
    """(kv tiles a q tile's band crosses at most, q tiles a kv tile's):
    the lengths of the narrowed inner axes, counted at trace time."""
    i, j = np.arange(nq), np.arange(nk)
    first_kv = np.maximum(i * block_q + q_offset - (window - 1), 0) // block_k
    last_kv = np.minimum((i * block_q + block_q - 1 + q_offset) // block_k,
                         nk - 1)
    first_q = np.maximum(j * block_k - q_offset, 0) // block_q
    last_q = np.minimum(
        (j * block_k + block_k - 1 + window - 1 - q_offset) // block_q, nq - 1)
    return (max(1, int((last_kv - first_kv + 1).max())),
            max(1, int((last_q - first_q + 1).max())))


def _tally_tiles(rows, causal, window, q_offset, block_q, block_k, nq, nk):
    """Trace-time: ``flash_tiles`` += the tiles of this call's score
    rectangles, ``flash_tiles_live`` += those the static masks leave live."""
    i, j = np.arange(nq)[:, None], np.arange(nk)[None, :]
    live = np.ones((nq, nk), bool)
    if causal:
        live &= j * block_k <= (i + 1) * block_q - 1 + q_offset
    if window is not None:
        live &= (j + 1) * block_k - 1 >= i * block_q + q_offset - (window - 1)
    _profiler.count("flash_tiles", rows * nq * nk)
    _profiler.count("flash_tiles_live", rows * int(live.sum()))


def _fwd_kernel(q_ref, k_ref, v_ref, kvl_ref, *rest,
                sm_scale, causal, q_offset, kv_len, block_q, block_k,
                precision, dynamic_kv, dynamic_seg, n_heads,
                window=None, narrow=None, dynamic_mask=False):
    ((qseg_ref, kseg_ref, qrng_ref, krng_ref), (pm_ref, live_ref),
     (o_ref, lse_ref, acc_sc, m_sc, l_sc)) = _split_rest(
         rest, dynamic_seg, dynamic_mask)
    i, jj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    kvl = kvl_ref[pl.program_id(0)] if dynamic_kv else None
    # ``narrow`` (a window's walk): step jj visits the band's jj-th tile
    j = _kv_tile(i, jj, window, q_offset, block_q, block_k) \
        if narrow else jj

    @pl.when(jj == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # skip: causal invisibility, a tile outside the window's band or past
    # the example's kv length, a packed tile whose segment-id ranges are
    # disjoint, or one in which the pair mask leaves nothing
    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window, _tile_live(live_ref, i, j, n_heads))
    if narrow:
        visible = jnp.logical_and(visible, j < narrow[1])

    @pl.when(visible)
    def _():
        # q arrives pre-scaled by sm_scale (host side) so no per-pair
        # (block_q, block_k) elementwise scale runs on the VPU
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

        smask = _tile_mask(pm_ref, _segment_mask(qseg_ref, kseg_ref, block_k)
                           if dynamic_seg else None)
        mask = _pair_mask(i, j, causal, q_offset, kv_len, block_q, block_k,
                          kvl, smask, window)
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
        v = v_ref[0].astype(jnp.float32)
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        m_sc[:] = m_cur

    @pl.when(jj == nk - 1)
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
                   window=None, narrow=None, group=1, steps=None,
                   dynamic_mask=False):
    ((qseg_ref, kseg_ref, qrng_ref, krng_ref), (pm_ref, live_ref),
     (dq_ref, dq_sc)) = _split_rest(rest, dynamic_seg, dynamic_mask)
    i, jj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    kvl = kvl_ref[pl.program_id(0)] if dynamic_kv else None
    j = _kv_tile(i, jj, window, q_offset, block_q, block_k) \
        if narrow else jj

    @pl.when(jj == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window, _tile_live(live_ref, i, j, n_heads))
    if narrow:
        visible = jnp.logical_and(visible, j < narrow[1])

    @pl.when(visible)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        smask = _tile_mask(pm_ref, _segment_mask(qseg_ref, kseg_ref, block_k)
                           if dynamic_seg else None)
        mask = _pair_mask(i, j, causal, q_offset, kv_len, block_q, block_k,
                          kvl, smask, window)
        p = jnp.exp(s - lse) if mask is None \
            else jnp.where(mask, jnp.exp(s - lse), np.float32(0.0))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - delta)
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    @pl.when(jj == nk - 1)
    def _():
        # dq is wrt the ORIGINAL q: rescale once on the small (bq, d)
        # block (q was pre-scaled; ds here is wrt unscaled scores)
        dq_ref[0] = (dq_sc[:] * np.float32(sm_scale)).astype(dq_ref.dtype)


def _inner_q_tile(j, t, q_offset, block_q, block_k, narrow, group, steps):
    """The q tile of inner step ``t`` of the (kv rows, kv tiles, q steps)
    grids: with grouped heads the axis runs the group's query heads one
    after the other, ``steps`` tiles each; narrowed, a head's step ii
    visits the ii-th q tile that sees the kv tile."""
    ii = t % steps if group > 1 else t
    return _q_tile(j, ii, q_offset, block_q, block_k) if narrow else ii


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    kvl_ref, *rest,
                    sm_scale, causal, q_offset, kv_len, block_q, block_k,
                    precision, dynamic_kv, dynamic_seg, n_heads,
                    window=None, narrow=None, group=1, steps=None,
                    dynamic_mask=False):
    # grid: (B*Hk, nk, group * q steps) — q is the inner (sequential) axis
    ((qseg_ref, kseg_ref, qrng_ref, krng_ref), (pm_ref, live_ref),
     (dk_ref, dv_ref, dk_sc, dv_sc)) = _split_rest(
         rest, dynamic_seg, dynamic_mask)
    j, t = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    # any query head of the row's batch entry: the lengths are per example
    kvl = kvl_ref[pl.program_id(0) * group if group > 1
                  else pl.program_id(0)] if dynamic_kv else None
    i = _inner_q_tile(j, t, q_offset, block_q, block_k, narrow, group, steps)

    @pl.when(t == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window,
                             _tile_live(live_ref, i, j, n_heads // group))
    if narrow:
        visible = jnp.logical_and(visible, i < narrow[0])

    @pl.when(visible)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        smask = _tile_mask(pm_ref, _segment_mask(qseg_ref, kseg_ref, block_k)
                           if dynamic_seg else None)
        mask = _pair_mask(i, j, causal, q_offset, kv_len, block_q, block_k,
                          kvl, smask, window)
        p = jnp.exp(s - lse) if mask is None \
            else jnp.where(mask, jnp.exp(s - lse), np.float32(0.0))

        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - delta)
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    @pl.when(t == nq - 1)
    def _():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      kvl_ref, *rest,
                      sm_scale, causal, q_offset, kv_len, block_q, block_k,
                      precision, dynamic_kv, dynamic_seg, n_heads,
                      window=None, narrow=None, group=1, steps=None,
                      dynamic_mask=False):
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
    i = _inner_q_tile(j, t, q_offset, block_q, block_k, narrow, group, steps)

    @pl.when(t == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    visible = _block_visible(i, j, causal, q_offset, block_q, block_k, kvl,
                             _seg_range(qrng_ref, krng_ref, i, j, n_heads),
                             window,
                             _tile_live(live_ref, i, j, n_heads // group))

    @pl.when(visible)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        smask = _tile_mask(pm_ref, _segment_mask(qseg_ref, kseg_ref, block_k)
                           if dynamic_seg else None)
        mask = _pair_mask(i, j, causal, q_offset, kv_len, block_q, block_k,
                          kvl, smask, window)
        p = jnp.exp(s - lse) if mask is None \
            else jnp.where(mask, jnp.exp(s - lse), np.float32(0.0))

        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - delta)
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dq_ref[0, 0] = (jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * np.float32(sm_scale)).astype(dq_ref.dtype)

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
# blocks ~1 MB), inside the ~16 MB budget. The caps are what every
# ledger run has used (S=512 and S=8192); whether 512x2048 is right at
# S=2048 packed is not measured (ROADMAP.md Queue 2 workload 3).
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


def _q_map(group, steps, narrow, q_offset, block_q, block_k):
    """The q-side index map of the (kv rows, kv tiles, q steps) grids."""
    if group == 1 and not narrow:
        return lambda b_, j, i: (b_, i, 0)
    g32, s32 = np.int32(group), np.int32(steps)

    def qmap(b_, j, t):
        i = _inner_q_tile(j, t, q_offset, block_q, block_k, narrow, group, s32)
        if narrow:
            i = jnp.minimum(i, np.int32(narrow[0] - 1))
        return (b_ * g32 + t // s32 if group > 1 else b_, i, 0)
    return qmap


def _kv_map(group, window, narrow, q_offset, block_q, block_k):
    """The kv-side index map of the (q rows, q tiles, kv steps) grids."""
    if group == 1 and not narrow:
        return lambda b_, i, j: (b_, j, 0)
    g32 = np.int32(group)

    def kmap(b_, i, jj):
        j = jj
        if narrow:
            j = jnp.minimum(_kv_tile(i, jj, window, q_offset, block_q, block_k),
                            np.int32(narrow[1] - 1))
        return (b_ // g32 if group > 1 else b_, j, 0)
    return kmap


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
               segment_ids=None, window=None, pair_mask=None):
    b, h, sq, d = q.shape
    skv = k.shape[2]
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
    vf = v.reshape(b * hk, skv, d)
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
    _tally_tiles(bh, causal, window, q_offset, block_q, block_k, nq, nk)
    # a window's walk: the inner axis holds the band's tiles, not the row's
    narrow = (nq, nk) if window is not None else None
    kv_steps = _band_steps(window, q_offset, block_q, block_k, nq, nk)[0] \
        if narrow else nk
    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        q_offset=q_offset, kv_len=skv, block_q=block_q, block_k=block_k,
        precision=_dot_precision(q.dtype), dynamic_kv=dynamic_kv,
        dynamic_seg=dynamic_seg, n_heads=h, window=window, narrow=narrow,
        dynamic_mask=pair_mask is not None)
    kv_map = _kv_map(group, window, narrow, q_offset, block_q, block_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [qf, kf, vf, kvlf]
    if dynamic_seg:
        in_specs += _seg_specs(block_q, block_k, h, transposed_grid=False)
        operands += list(_prep_segments(segment_ids, b, sq, skv,
                                        sq_p, skv_p, block_q, block_k))
    if pair_mask is not None:
        in_specs += _mask_specs(block_q, block_k, h, transposed_grid=False)
        operands += _prep_pair_mask(pair_mask, sq_p, skv_p, block_q, block_k)
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, kv_steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b_, i, j: (b_, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        name="mxtpu_flash_fwd",
        interpret=interpret,
    )(*operands)
    o = o[:, :sq].reshape(b, h, sq, d)
    lse = lse[:, :sq, 0].reshape(b, h, sq)
    return o, lse


@x32
def _flash_bwd(q, k, v, o, lse, do, sm_scale, causal, q_offset, interpret,
               block_q=None, block_k=None, dlse=None, kv_lens=None,
               segment_ids=None, window=None, pair_mask=None):
    b, h, sq, d = q.shape
    skv = k.shape[2]
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
    vf = v.reshape(b * hk, skv, d)
    dof = do.reshape(bh, sq, d)
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
    _tally_tiles(bh, causal, window, q_offset, block_q, block_k, nq, nk)
    common = dict(sm_scale=sm_scale, causal=causal, q_offset=q_offset,
                  kv_len=skv, block_q=block_q, block_k=block_k,
                  precision=_dot_precision(q.dtype), dynamic_kv=dynamic_kv,
                  dynamic_seg=seg_ops is not None, n_heads=h,
                  window=window, group=group,
                  dynamic_mask=mask_ops is not None)

    # the fused pass writes nk f32 dq-partial copies to HBM; past nk=2
    # that memory/write cliff outweighs the recompute saving, so long
    # multi-k-block rows (S > 2*block_k cap) take the split path whose
    # dq accumulates in VMEM scratch
    if nk <= 2:
        return _flash_bwd_fused(qf, kf, vf, dof, lsef, delta, kvlf, seg_ops,
                                (b, h, sq, skv, d), nq, nk, common,
                                interpret, k.dtype, v.dtype, q.dtype, group,
                                mask_ops)
    return _flash_bwd_split(qf, kf, vf, dof, lsef, delta, kvlf, seg_ops,
                            (b, h, sq, skv, d), nq, nk, common,
                            interpret, k.dtype, v.dtype, q.dtype, group,
                            mask_ops)


def _flash_bwd_fused(qf, kf, vf, dof, lsef, delta, kvlf, seg_ops, dims,
                     nq, nk, common, interpret, k_dtype, v_dtype, q_dtype,
                     group=1, mask_ops=None):
    """Single-pass dq/dk/dv, taken where the kv grid has at most two
    blocks: ``bert_base.train_b64x512`` (S=512, nk=1) runs this kernel;
    ``kimi_linear_48b_a3b.train_8k``'s latent attention (S=8192 at the
    2048 cap, nk=4) runs ``_flash_bwd_split``'s dq/dkv pair."""
    b, h, sq, skv, d = dims
    bh = b * h
    block_q, block_k = common["block_q"], common["block_k"]
    sq_p, skv_p = nq * block_q, nk * block_k
    common = dict(common, steps=nq)
    # the q side: with grouped heads the inner axis runs the group's query
    # heads one after the other, nq tiles each
    q_map = _q_map(group, nq, None, 0, block_q, block_k)
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
        pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, d), q_map, memory_space=pltpu.VMEM),
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
    dq_part, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, **common),
        grid=(bh // group, nk, group * nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), dq_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            # f32 partials: the cross-k-block sum happens outside the
            # kernel in f32, then casts once to the caller dtype
            jax.ShapeDtypeStruct((bh, nk, sq_p, d), jnp.float32),
            jax.ShapeDtypeStruct((bh // group, skv_p, d), k_dtype),
            jax.ShapeDtypeStruct((bh // group, skv_p, d), v_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_BWD_COMPILER_PARAMS,
        name="mxtpu_flash_bwd_fused",
        interpret=interpret,
    )(*operands)

    dq = dq_part.sum(axis=1).astype(q_dtype) if nk > 1 \
        else dq_part[:, 0].astype(q_dtype)
    dq = dq[:, :sq].reshape(b, h, sq, d)
    dk = dk[:, :skv].reshape(b, h // group, skv, d)
    dv = dv[:, :skv].reshape(b, h // group, skv, d)
    return dq, dk, dv


def _flash_bwd_split(qf, kf, vf, dof, lsef, delta, kvlf, seg_ops, dims,
                     nq, nk, common, interpret, k_dtype, v_dtype, q_dtype,
                     group=1, mask_ops=None):
    b, h, sq, skv, d = dims
    bh = b * h
    block_q, block_k = common["block_q"], common["block_k"]
    sq_p, skv_p = nq * block_q, nk * block_k
    window, q_offset = common.get("window"), common["q_offset"]
    # a window's walk: both inner axes hold a band's tiles, not a row's
    narrow = (nq, nk) if window is not None else None
    kv_steps, q_steps = _band_steps(window, q_offset, block_q, block_k,
                                    nq, nk) if narrow else (nk, nq)
    common = dict(common, narrow=narrow, steps=q_steps)
    kv_map = _kv_map(group, window, narrow, q_offset, block_q, block_k)
    q_map = _q_map(group, q_steps, narrow, q_offset, block_q, block_k)

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0),
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
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, nq, kv_steps),
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
        pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, d), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    if seg_ops is not None:
        dkv_specs += _seg_specs(block_q, block_k, h, transposed_grid=True)
    if mask_ops is not None:
        dkv_specs += _mask_specs(block_q, block_k, h // group, True, group,
                                 q_steps)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh // group, nk, group * q_steps),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh // group, skv_p, d), k_dtype),
            jax.ShapeDtypeStruct((bh // group, skv_p, d), v_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_BWD_COMPILER_PARAMS,
        name="mxtpu_flash_bwd_dkv",
        interpret=interpret,
    )(*operands)

    dq = dq[:, :sq].reshape(b, h, sq, d)
    dk = dk[:, :skv].reshape(b, h // group, skv, d)
    dv = dv[:, :skv].reshape(b, h // group, skv, d)
    return dq, dk, dv


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
               segment_ids, window=None, pair_mask=None):
    """The forward that the primals and the VJPs' forward rules share:
    (out, lse) under their `REMAT_KEEP` names."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    o, lse = _flash_fwd(q, k, v, sm_scale, bool(causal), int(q_offset),
                        resolve_interpret(interpret), kv_lens=kv_lens,
                        segment_ids=segment_ids, window=window,
                        pair_mask=pair_mask)
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
    block-diagonal over packed sequences (see module docstring). q, k
    and v share one head width; ``mx.nd.flash_attention`` pads a wider
    q.k (192 against v's 128) up to it and passes the true ``sm_scale``.
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
    the kernels here: unequal q.k and v widths (latent attention) are the
    op's business (``flash_attention_op`` zero-pads them to one width)."""
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
