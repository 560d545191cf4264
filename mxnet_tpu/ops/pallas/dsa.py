"""Sparse attention with a learned indexer (DeepSeek-V3.2-Exp's "DSA", the
sparse stage; Keye-VL-2.0's ``sa_config``): what stands beside the flash
kernel. A small indexer scores every causal (query, key) pair, each query
keeps its ``top_k`` best keys, the main attention runs over the kept pairs
only (``flash_attention``'s ``pair_mask``), and the indexer learns from the
main attention's own probabilities on them.

Four pieces, each over (B, S, S) pair matrices that a row of 8,192 tokens
makes 67 M entries large, so each works a block of query rows at a time
(``lax.map``: one block's intermediates are live, forward and backward):

- ``index_scores``: I[t, s] = (H_i d_i)^-1/2 sum_j w[t, j] ReLU(q_i[t, j] .
  k_i[s]) in float32 for s <= t, -inf above the diagonal. The H_i x S^2
  products exist a block at a time only.
- ``topk_mask``: the int8 mask of each query's min(top_k, t + 1) largest
  scores, the lower index first among equals (what ``lax.top_k`` gives),
  exactly that many. No sort: the k-th largest score's bits are found one
  at a time from the top (32 counts over the row), then the equals at the
  threshold are admitted in index order up to the count.
- ``head_mean_probs``: p_bar[t, s] = mean over the heads of exp(q_h[t] .
  k_g[s] / sqrt(d) - lse_h[t]) on the kept pairs, from the flash forward's
  log-sum-exp (no flash kernel emits its probabilities).
- ``index_loss``: sum over the kept pairs of p_bar (log p_bar - log
  softmax_kept(I)), whose gradient to I is softmax_kept(I) - p_bar, written
  out (a ``custom_vjp``: nothing but its three inputs is kept).

Each is a jitted function, or a choice between two (a model's layers trace
one body). ``head_mean_probs`` and ``index_loss`` are XLA on every backend.
``index_scores`` has a Pallas form for the chip (``use_kernel``):
``mxtpu_dsa_index_fwd``, one (block_q, block_k) tile of the pair matrix
resident in VMEM while the heads, the grid's innermost axis, add their terms
to it, so that the heads x S^2 products never reach HBM (the XLA form writes
and reads them: 16 x 268 MB a row of 8,192), and its backward is the pair
``mxtpu_dsa_index_bwd_dq`` (a head's dq and dw summed over the kv tiles in
VMEM) / ``mxtpu_dsa_index_bwd_dk`` (dk summed over the heads and the q
tiles), each rebuilding a tile's products. ``topk_mask`` has one too,
``mxtpu_dsa_topk``, which does the work of the pairs that can be chosen and
no other. XLA's form keeps a block of rows in VMEM as well; its time is 33
vector-unit passes over every column of every row. The kernel takes 128
query rows a grid step: their scores come in once, their keys (the floats'
order as signed integers, the least integer above the diagonal) go to a VMEM
scratch, and each of the 32 counting passes, a loop inside the kernel, walks
512-column chunks only up to the block's last causal column: a compare, a
select and an add into a (rows, 128) partial sum a vreg. A block whose rows
all have at most ``top_k`` causal keys writes the causal mask and searches
nothing. The search carries how many keys stand at or over the threshold:
once that is the count wanted in every row of the block the lower bits
cannot change the choice, so the search stops there (no longer 32 passes:
21 to 26 on normal scores) and the mask is ``key >= threshold``; the running
count among equals (128 columns at a time against a triangle of ones on the
MXU) runs only in a block where, after all 32, some row has more equals than
room. Columns past the diagonal are written as zeros and never read. It
tallies what it visits (``dsa_topk_chunks`` / ``dsa_topk_chunks_live`` in
``profiler.counters()``). The main attention's kernel is the flash kernel
itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import profiler as _profiler
from ._util import resolve_interpret, x32
from .flash_attention import _dot_precision

_NEG_INF = -jnp.inf
_BLOCK_Q = 256      # query rows a block of the (rows, keys) pair matrices


def _blocks(s, block_q):
    """(rows a block, blocks) over ``s`` query rows: ``block_q`` where it
    divides ``s``, else one block."""
    bq = block_q if s % block_q == 0 else s
    return bq, s // bq


def _causal(first, bq, s):
    """(bq, s) bool: key s <= query first + row."""
    row = first + lax.broadcasted_iota(jnp.int32, (bq, s), 0)
    return lax.broadcasted_iota(jnp.int32, (bq, s), 1) <= row


def _by_row_blocks(fn, s, block_q, *row_arrays):
    """``fn(first, *blocks) -> (B, bq, ...)`` over blocks of query rows of
    ``row_arrays`` (each (B, S, ...)), one after the other; the blocks'
    results side by side, (B, S, ...)."""
    bq, n = _blocks(s, block_q)
    if n == 1:
        return fn(jnp.int32(0), *row_arrays)
    split = [jnp.moveaxis(a.reshape((a.shape[0], n, bq) + a.shape[2:]), 1, 0)
             for a in row_arrays]
    out = lax.map(lambda xs: fn(xs[0], *xs[1:]),
                  (jnp.arange(n, dtype=jnp.int32) * bq, *split))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], s) + out.shape[3:])


def _index_scores_xla(q_i, k_i, w, block_q=_BLOCK_Q):
    b, h, s, d = q_i.shape
    scale = np.float32((h * d) ** -0.5)
    bq, _ = _blocks(s, block_q)

    @jax.checkpoint     # the backward rebuilds a block's H_i score maps
    def block(first, q_blk, w_blk):
        prod = jnp.einsum("bqhd,bkd->bhqk", q_blk, k_i,
                          preferred_element_type=jnp.float32)
        wt = jnp.moveaxis(w_blk.astype(jnp.float32), 2, 1)[..., None]
        scores = jnp.sum(wt * jax.nn.relu(prod), axis=1) * scale
        return jnp.where(_causal(first, bq, s)[None], scores, _NEG_INF)

    return _by_row_blocks(block, s, block_q, jnp.moveaxis(q_i, 1, 2), w)


# Tiles of the scores' kernels: the float32 pair tile (2 MiB) stays in VMEM
# over the heads; q, k and the per-row column stream past it.
_TILE_Q, _TILE_K = 512, 1024
_KERNEL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 1024 * 1024)


def _tiles(s):
    """(block_q, block_k) of the kernels' pair tile, or None where ``s`` is
    no multiple of them (toy lengths: the XLA form)."""
    bq, bk = min(_TILE_Q, s), min(_TILE_K, s)
    return (bq, bk) if s % bq == 0 and s % bk == 0 and bq % 8 == 0 \
        and bk % 128 == 0 else None


def _dot_nt(a, b):
    # an explicit precision a dot, as the flash kernel's: Mosaic refuses the
    # process-wide 'high'
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=_dot_precision(a.dtype))


def _index_fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, block_q, block_k, heads,
                      scale):
    i, j, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    below = j * block_k <= (i + 1) * block_q - 1      # the tile has causal pairs

    @pl.when(h == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(below)
    def _():
        prod = _dot_nt(q_ref[0], k_ref[0])
        o_ref[0] += w_ref[0] * jnp.maximum(prod, np.float32(0.0))

    @pl.when(h == heads - 1)
    def _():
        row = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        col = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        o_ref[0] = jnp.where(col <= row, o_ref[0] * np.float32(scale),
                             np.float32(_NEG_INF))


@x32
def _index_scores_pallas(q_i, k_i, w, interpret):
    b, h, s, d = q_i.shape
    bq, bk = _tiles(s)
    wf = jnp.moveaxis(w.astype(jnp.float32), 2, 1).reshape(b * h, s, 1)
    h32 = np.int32(h)
    return pl.pallas_call(
        functools.partial(_index_fwd_kernel, block_q=bq, block_k=bk, heads=h,
                          scale=(h * d) ** -0.5),
        grid=(b, s // bq, s // bk, h),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b_, i, j, h_: (b_ * h32 + h_, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b_, i, j, h_: (b_, j, 0)),
            pl.BlockSpec((1, bq, 1), lambda b_, i, j, h_: (b_ * h32 + h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, bk), lambda b_, i, j, h_: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_KERNEL_PARAMS, interpret=interpret,
        name="mxtpu_dsa_index_fwd",
    )(q_i.reshape(b * h, s, d), k_i, wf)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _index_scores_kernel(q_i, k_i, w, interpret):
    return _index_scores_pallas(q_i, k_i, w, interpret)


def _index_scores_kernel_fwd(q_i, k_i, w, interpret):
    return _index_scores_pallas(q_i, k_i, w, interpret), (q_i, k_i, w)


def _index_grad_tile(q_ref, k_ref, w_ref, ct_ref, i, j, block_q, block_k, scale):
    """A head's tile of the backward: (ct on the causal pairs times the
    scale, the head's products, their cotangent)."""
    row = i * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    col = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ct = jnp.where(col <= row, ct_ref[0], np.float32(0.0)) * np.float32(scale)
    prod = _dot_nt(q_ref[0], k_ref[0])
    return ct, prod, jnp.where(prod > 0, ct * w_ref[0], np.float32(0.0))


def _index_bwd_dq_kernel(q_ref, k_ref, w_ref, ct_ref, dq_ref, dw_ref, dq_sc, dw_sc,
                         *, block_q, block_k, scale):
    # grid (B * H_i, q tiles, kv tiles): dq and dw of one head's rows in scratch
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        dw_sc[...] = jnp.zeros_like(dw_sc)

    @pl.when(j * block_k <= (i + 1) * block_q - 1)
    def _():
        ct, prod, g = _index_grad_tile(q_ref, k_ref, w_ref, ct_ref, i, j,
                                       block_q, block_k, scale)
        k = k_ref[0]
        dq_sc[...] += lax.dot_general(
            g.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_dot_precision(k.dtype))
        dw_sc[...] += jnp.sum(ct * jnp.maximum(prod, np.float32(0.0)), axis=1,
                              keepdims=True)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_sc[...]


def _index_bwd_dk_kernel(q_ref, k_ref, w_ref, ct_ref, dk_ref, dk_sc, *, block_q,
                         block_k, scale, q_tiles):
    # grid (B, kv tiles, H_i * q tiles): the heads one after the other
    j, t = pl.program_id(1), pl.program_id(2)
    i = t % q_tiles

    @pl.when(t == 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)

    @pl.when(j * block_k <= (i + 1) * block_q - 1)
    def _():
        _, _, g = _index_grad_tile(q_ref, k_ref, w_ref, ct_ref, i, j, block_q,
                                   block_k, scale)
        q = q_ref[0]
        dk_sc[...] += lax.dot_general(
            g.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_dot_precision(q.dtype))

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)


@x32
def _index_scores_bwd_pallas(q_i, k_i, w, ct, interpret):
    b, h, s, d = q_i.shape
    bq, bk = _tiles(s)
    nq, nk = s // bq, s // bk
    scale = (h * d) ** -0.5
    qf = q_i.reshape(b * h, s, d)
    wf = jnp.moveaxis(w.astype(jnp.float32), 2, 1).reshape(b * h, s, 1)
    h32, n32 = np.int32(h), np.int32(nq)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=32 * 1024 * 1024)
    dq, dw = pl.pallas_call(
        functools.partial(_index_bwd_dq_kernel, block_q=bq, block_k=bk, scale=scale),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda r, i, j: (r, i, 0)),
            pl.BlockSpec((1, bk, d), lambda r, i, j: (r // h32, j, 0)),
            pl.BlockSpec((1, bq, 1), lambda r, i, j: (r, i, 0)),
            pl.BlockSpec((1, bq, bk), lambda r, i, j: (r // h32, i, j)),
        ],
        out_specs=[pl.BlockSpec((1, bq, d), lambda r, i, j: (r, i, 0)),
                   pl.BlockSpec((1, bq, 1), lambda r, i, j: (r, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q_i.dtype),
                   jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=params, interpret=interpret, name="mxtpu_dsa_index_bwd_dq",
    )(qf, k_i, wf, ct)
    dk = pl.pallas_call(
        functools.partial(_index_bwd_dk_kernel, block_q=bq, block_k=bk, scale=scale,
                          q_tiles=nq),
        grid=(b, nk, h * nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b_, j, t: (b_ * h32 + t // n32, t % n32, 0)),
            pl.BlockSpec((1, bk, d), lambda b_, j, t: (b_, j, 0)),
            pl.BlockSpec((1, bq, 1), lambda b_, j, t: (b_ * h32 + t // n32, t % n32, 0)),
            pl.BlockSpec((1, bq, bk), lambda b_, j, t: (b_, t % n32, j)),
        ],
        out_specs=pl.BlockSpec((1, bk, d), lambda b_, j, t: (b_, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), k_i.dtype),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=params, interpret=interpret, name="mxtpu_dsa_index_bwd_dk",
    )(qf, k_i, wf, ct)
    dw = jnp.moveaxis(dw.reshape(b, h, s), 1, 2).astype(w.dtype)
    return dq.reshape(b, h, s, d), dk, dw


def _index_scores_kernel_bwd(interpret, res, ct):
    return _index_scores_bwd_pallas(*res, ct.astype(jnp.float32), interpret)


_index_scores_kernel.defvjp(_index_scores_kernel_fwd, _index_scores_kernel_bwd)


@functools.partial(jax.jit, static_argnames=("block_q", "use_kernel", "interpret"))
def index_scores(q_i, k_i, w, block_q=_BLOCK_Q, use_kernel=False, interpret=None):
    """The indexer's scores. q_i (B, H_i, S, d_i), k_i (B, S, d_i) (one key
    head for all), w (B, S, H_i). Returns (B, S, S) float32, -inf for s > t.
    ``use_kernel``: ``mxtpu_dsa_index_fwd`` and, for its backward,
    ``mxtpu_dsa_index_bwd_dq`` / ``_dk`` (lengths that their tiles divide)."""
    if use_kernel and _tiles(q_i.shape[2]) is not None:
        return _index_scores_kernel(q_i, k_i, w, resolve_interpret(interpret))
    return _index_scores_xla(q_i, k_i, w, block_q)


def _order_bits(x):
    """float32 -> uint32 in the floats' total order (-0 below +0, as
    ``lax.top_k`` has them)."""
    return lax.bitcast_convert_type(_order_key(x), jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def _order_key(x):
    """float32 -> int32 in the floats' total order, signed."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


@functools.partial(jax.jit, static_argnames=("top_k", "block_q"))
def _topk_mask_xla(scores, top_k, block_q):
    b, s, _ = scores.shape
    bq, _ = _blocks(s, block_q)

    def block(first, sc):
        causal = _causal(first, bq, s)[None]
        # 0 sorts below every causal score's bits (the least, -inf's, are
        # 0x007FFFFF), and no candidate below is 0
        u = jnp.where(causal, _order_bits(sc), jnp.uint32(0))
        want = jnp.minimum(
            first + lax.broadcasted_iota(jnp.int32, (1, bq), 1) + 1, top_k)

        def bit(i, thr):
            cand = thr | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
            enough = jnp.sum(u >= cand[..., None], axis=-1,
                             dtype=jnp.int32) >= want
            return jnp.where(enough, cand, thr)

        # the want-th largest: its bits, from the top one down
        thr = lax.fori_loop(0, 32, bit, jnp.zeros((b, bq), jnp.uint32))[..., None]
        above = u > thr
        equal = jnp.logical_and(u == thr, causal)
        room = want[..., None] - jnp.sum(above, axis=-1, keepdims=True,
                                         dtype=jnp.int32)
        admitted = jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= room
        return jnp.logical_or(above, jnp.logical_and(equal, admitted)) \
            .astype(jnp.int8)

    return _by_row_blocks(block, s, block_q, scores)


# The selection's kernel: a block of query rows' ordered keys in VMEM, read a
# chunk of columns at a time up to the block's last causal column.
_TOPK_ROWS, _TOPK_CHUNK = 128, 512
_INT_MIN = -2 ** 31


def _topk_tiles(s):
    """(rows a block, columns a chunk) of the selection's kernel, or None
    where ``s`` is no multiple of them (toy lengths: the XLA form)."""
    bq, ck = min(_TOPK_ROWS, s), min(_TOPK_CHUNK, s)
    return (bq, ck) if s % bq == 0 and s % ck == 0 and bq % 32 == 0 \
        and ck % 128 == 0 else None


def _topk_visits(s, top_k, bq, ck):
    """(Row block, column chunk) visits of one (S, S) selection, from its
    shapes: (those of 32 counting passes and the writing pass over the whole
    rectangle, those the kernel makes: a block's chunks up to its last causal
    column, 33 times where a row of the block chooses and once, for the
    causal mask's write, where none does)."""
    ends = np.arange(bq, s + 1, bq)                 # one past a block's last row
    live = -(-ends // ck)
    return ((s // bq) * (s // ck) * 33,
            int(np.where(ends > top_k, 33 * live, live).sum()))


def _topk_kernel(sc_ref, o_ref, key_ref, *, top_k, block_q, chunk):
    bq, ck = block_q, chunk
    lanes = ck // 128
    first = pl.program_id(1) * bq
    n_live = (first + bq + (ck - 1)) // ck          # chunks with a causal column
    row = first + lax.broadcasted_iota(jnp.int32, (bq, 128), 0)
    lane = lax.broadcasted_iota(jnp.int32, (bq, 128), 1)

    def columns(c, j):
        return pl.ds(pl.multiple_of(c * ck + j * 128, 128), 128)

    def causal(c, j):
        return c * ck + j * 128 + lane <= row

    def write(c, j, kept):
        o_ref[0, 0, :, columns(c, j)] = jnp.where(kept, 1, 0).astype(jnp.int8)

    def over_live(body, carry=None):
        """``body(c, j, carry)`` over the live chunks' 128-column slices."""
        def chunk_body(c, carry):
            for j in range(lanes):
                carry = body(c, j, carry)
            return carry
        return lax.fori_loop(0, n_live, chunk_body, carry)

    @pl.when(first + bq <= top_k)
    def _():            # every row keeps all its causal keys
        over_live(lambda c, j, _: write(c, j, causal(c, j)))

    @pl.when(first + bq > top_k)
    def _():
        def build(c, j, _):
            # the least int32 sorts below every score's key, and no
            # candidate below is it
            key_ref[:, columns(c, j)] = jnp.where(
                causal(c, j), _order_key(sc_ref[0, :, columns(c, j)]),
                jnp.int32(_INT_MIN))
        over_live(build)

        def count(pred, level):
            """(bq, 1): each row's keys with ``pred(key, level)``."""
            wide = jnp.broadcast_to(level, (bq, 128))
            part = over_live(
                lambda c, j, acc: acc + jnp.where(
                    pred(key_ref[:, columns(c, j)], wide), 1, 0),
                jnp.zeros((bq, 128), jnp.int32))
            return jnp.sum(part, axis=1, keepdims=True)

        want = jnp.minimum(
            first + lax.broadcasted_iota(jnp.int32, (bq, 1), 0) + 1, top_k)

        def open_rows(found):
            """Is there a row without exactly its count at or over ``thr``?"""
            return jnp.max(jnp.where(found != want, 1, 0)) > 0

        def bit(state):
            # thr: the threshold's bits so far, in the unsigned order; a
            # candidate's signed twin is what the signed keys are held to
            i, thr, found = state
            cand = thr | lax.shift_left(jnp.int32(1), 31 - i)
            n = count(lambda k, c: k >= c, cand ^ jnp.int32(_INT_MIN))
            enough = n >= want
            return (i + 1, jnp.where(enough, cand, thr),
                    jnp.where(enough, n, found))

        # the want-th largest: its bits, from the top one down, and how many
        # keys are no less (-1: no candidate had enough yet). A row whose
        # threshold has exactly its count at or over it is settled, whatever
        # the lower bits: the search stops when every row of the block is
        # (after 21 to 26 of the 32 passes on normal scores at 8,192)
        _, thr, found = lax.while_loop(
            lambda state: jnp.logical_and(state[0] < 32, open_rows(state[2])),
            bit, (jnp.int32(0), jnp.zeros((bq, 1), jnp.int32),
                  jnp.full((bq, 1), -1, jnp.int32)))
        thr = thr ^ jnp.int32(_INT_MIN)
        wide = jnp.broadcast_to(thr, (bq, 128))
        crowded = open_rows(found)

        @pl.when(jnp.logical_not(crowded))
        def _():        # as many at or over the threshold as wanted: those
            over_live(lambda c, j, _: write(
                c, j, key_ref[:, columns(c, j)] >= wide))

        @pl.when(crowded)
        def _():        # more equals than room: the lower index first
            room = (want - count(lambda k, t: k > t, thr)).astype(jnp.float32)
            upto = (lax.broadcasted_iota(jnp.int32, (128, 128), 0)
                    >= lax.broadcasted_iota(jnp.int32, (128, 128), 1))
            upto = jnp.where(upto, 1.0, 0.0).astype(jnp.bfloat16)

            def admit(c, j, before):
                key = key_ref[:, columns(c, j)]
                equal = jnp.logical_and(key == wide, causal(c, j))
                # equals up to and with each column (0/1 sums to 128: exact)
                run = _dot_nt(jnp.where(equal, 1.0, 0.0).astype(jnp.bfloat16),
                              upto)
                write(c, j, jnp.logical_or(
                    key > wide, jnp.logical_and(equal, before + run <= room)))
                return before + run[:, 127:128]
            over_live(admit, jnp.zeros((bq, 1), jnp.float32))

    def beyond(c, _):   # no causal column: written, never read
        o_ref[0, 0, :, pl.ds(pl.multiple_of(c * ck, ck), ck)] = \
            jnp.zeros((bq, ck), jnp.int8)
    lax.fori_loop(n_live, o_ref.shape[3] // ck, beyond, None)


@functools.partial(jax.jit, static_argnames=("top_k", "tiles", "interpret"))
@x32
def _topk_mask_pallas(scores, top_k, tiles, interpret):
    b, s, _ = scores.shape
    bq, ck = tiles
    # out as (B, blocks, rows, S), a free reshape from (B, S, S): the flash
    # wrapper's tile summary (a max over tiles of rows) then reduces each
    # block's rows where they lie; from a (B, S, S) custom call XLA re-tiled
    # the whole mask first (0.6 ms a summary at 8,192, three a layer-row)
    return pl.pallas_call(
        functools.partial(_topk_kernel, top_k=top_k, block_q=bq, chunk=ck),
        grid=(b, s // bq),
        in_specs=[pl.BlockSpec((1, bq, s), lambda b_, i: (b_, i, 0))],
        out_specs=pl.BlockSpec((1, 1, bq, s), lambda b_, i: (b_, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s // bq, bq, s), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bq, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret, name="mxtpu_dsa_topk",
    )(scores).reshape(b, s, s)


def topk_mask(scores, top_k, block_q=_BLOCK_Q, use_kernel=False, interpret=None):
    """(B, S, S) int8: 1 on each query's min(top_k, t + 1) largest causal
    scores, the lower index first among equals; exactly that many a query.
    ``use_kernel``: ``mxtpu_dsa_topk`` (lengths that its tiles divide), which
    tallies ``dsa_topk_chunks`` / ``dsa_topk_chunks_live`` as it is traced."""
    b, s, _ = scores.shape
    scores = lax.stop_gradient(scores)
    tiles = _topk_tiles(s) if use_kernel else None
    if tiles is None:
        return _topk_mask_xla(scores, top_k, block_q)
    full, live = _topk_visits(s, top_k, *tiles)
    _profiler.count("dsa_topk_chunks", b * full)
    _profiler.count("dsa_topk_chunks_live", b * live)
    return _topk_mask_pallas(scores, top_k, tiles, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_q"))
def head_mean_probs(q, k, lse, mask, sm_scale, block_q=_BLOCK_Q):
    """The main attention's probabilities on the kept pairs, averaged over
    the heads: q (B, H, S, d), k (B, Hk, S, d), lse (B, H, S) the flash
    forward's, mask (B, S, S) int8 (causal already). (B, S, S) float32, no
    gradient. q is scaled and rounded as the flash kernel scales it, so that
    a row sums to one as the kernel's own probabilities do."""
    b, h, s, d = q.shape
    hk = k.shape[1]
    q, k, lse = (lax.stop_gradient(t) for t in (q, k, lse))
    qs = (q * sm_scale).astype(q.dtype).reshape(b, hk, h // hk, s, d)
    qs = jnp.moveaxis(qs, 3, 1)                              # (B, S, Hk, g, d)
    lse_rows = jnp.moveaxis(lse.reshape(b, hk, h // hk, s), 3, 1)

    def block(first, q_blk, lse_blk, m_blk):
        sc = jnp.einsum("bqgjd,bgkd->bgjqk", q_blk, k,
                        preferred_element_type=jnp.float32)
        p = jnp.exp(sc - jnp.moveaxis(lse_blk, 1, 3)[..., None])
        p = jnp.where(m_blk[:, None, None] != 0, p, 0.0)
        return jnp.sum(p, axis=(1, 2)) * np.float32(1.0 / h)

    return _by_row_blocks(block, s, block_q, qs, lse_rows, mask)


def _log_softmax_kept(scores, kept):
    masked = jnp.where(kept, scores, _NEG_INF)
    top = jnp.max(masked, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)            # a row that keeps nothing
    total = jnp.sum(jnp.exp(masked - top), axis=-1, keepdims=True)
    return jnp.where(kept, scores - top - jnp.log(jnp.where(total > 0, total, 1.0)),
                     0.0)


@jax.custom_vjp
def _index_loss(scores, mask, p_bar):
    """sum over the kept pairs of p_bar (log p_bar - log softmax_kept(I)), a
    float32 scalar; 0 log 0 = 0. d/dI = softmax_kept(I) - p_bar on the kept
    pairs; none to ``mask`` or ``p_bar``."""
    kept = mask != 0
    logp = _log_softmax_kept(scores, kept)
    live = jnp.logical_and(kept, p_bar > 0)
    safe = jnp.where(live, p_bar, 1.0)
    return jnp.sum(jnp.where(live, p_bar * (jnp.log(safe) - logp), 0.0))


def _index_loss_fwd(scores, mask, p_bar):
    return _index_loss(scores, mask, p_bar), (scores, mask, p_bar)


def _index_loss_bwd(res, g):
    scores, mask, p_bar = res
    kept = mask != 0
    soft = jnp.where(kept, jnp.exp(_log_softmax_kept(scores, kept)), 0.0)
    d = g * (soft - jnp.where(kept, p_bar, 0.0))
    return d, np.zeros(mask.shape, jax.dtypes.float0), jnp.zeros_like(p_bar)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)
index_loss = jax.jit(_index_loss)
