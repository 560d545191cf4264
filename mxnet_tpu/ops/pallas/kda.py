"""Chunked gated delta-rule linear attention (KDA; Kimi Linear,
arXiv:2510.26692) with a backward that keeps one state a CHUNK, not one a
token.

Per head (d_k, d_v), with a per-channel decay ``a_t = exp(g_t)`` in
(0,1)^{d_k} and a write strength ``beta_t``:

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t,                              S_0 = 0

No reference analog (SURVEY: MXNet v1.x has no linear attention). The
program never runs the token loop. Within a chunk of C tokens (the
paper's 64), with G the running sum of g inside the chunk,

    A[t,j] = beta_t sum_d k_t[d] k_j[d] exp(G_t[d] - G_j[d])     (j < t)
    B[t,j] =        sum_d q_t[d] k_j[d] exp(G_t[d] - G_j[d])     (j <= t)
    T      = (I + A)^-1            (unit lower triangular: the UT transform)
    U      = T (beta V) - T (beta K e^G) S_0  =  U0 - W S_0
    O      = scale ((Q e^G) S_0 + B U)
    S_C    = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

``T`` comes from matmuls alone: the 16 x 16 diagonal blocks by doubling,
``(I+a)^-1 = prod_i (I + (-a)^(2^i))`` (a is nilpotent), the rest by forward
substitution over the block rows. The pairwise decay inside A and B is
factored through one reference point per 16-token sub-chunk so that every
exponent but the diagonal sub-block's is <= 0; the diagonal sub-block's is
bounded by 16 steps of decay and clamped at ``_EXP_CLAMP`` (exact unless a
channel loses more than e^-80 inside 16 tokens). Log-decays accumulate in
float32.

Two phases. The first (A, B, T, W, U0, the decayed Q and K) is parallel
over chunks; the second carries S from chunk to chunk. On the chip both are
Pallas kernels with hand-written backwards. ``mxtpu_kda_chunk_fwd`` builds
the terms of whole tiles of chunks in VMEM (nothing wider than the keys
reaches HBM) and saves T; ``mxtpu_kda_chunk_bwd`` rebuilds the cheap terms
from the inputs and reads T instead of inverting again. ``mxtpu_kda_fwd``
walks the chunks with the state in VMEM and ``mxtpu_kda_bwd`` walks back
over the saved chunk-start states. A grid step of either pair takes several
independent chains (tiles of chunks; heads of the walk) side by side, so that
one's wait for a product is another's work. Elsewhere (the twin: the CPU, the tests'
oracle) the terms are plain ``jax.numpy`` and the walk a ``lax.scan``, both
differentiated by jax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import resolve_interpret, x32
from .flash_attention import _dot_precision

_SUB = 16           # tokens per reference point of the pairwise decay
_EXP_CLAMP = 80.0   # the diagonal sub-block's exponent never passes this


def _precisions(dtype):
    """(matmul precision, the chunk inverse's precision) for operands of
    ``dtype``: float32 stays exact; bfloat16 operands take one MXU pass,
    the inverse three (its error compounds over its products)."""
    matmul = _dot_precision(dtype)
    return matmul, (matmul if matmul == lax.Precision.HIGHEST
                    else lax.Precision.HIGH)


# ---- phase 2: the state's walk over the chunks --------------------------------

def _scan_twin(w, u0, qg, bm, kend, ec, precision):
    """The walk as a ``lax.scan`` (differentiated by jax). Shapes:
    w, qg, kend (BH, N, C, dk); u0 (BH, N, C, dv); bm (BH, N, C, C);
    ec (BH, N, 1, dk) float32. Returns o (BH, N, C, dv)."""
    bh, dk, dv = w.shape[0], w.shape[-1], u0.shape[-1]
    mm = functools.partial(jnp.matmul, precision=precision,
                           preferred_element_type=jnp.float32)

    def step(s, xs):
        w_, u0_, qg_, bm_, kend_, ec_ = xs
        s_op = s.astype(w_.dtype)
        u = u0_.astype(jnp.float32) - mm(w_, s_op)
        u_op = u.astype(w_.dtype)
        o = mm(qg_, s_op) + mm(bm_, u_op)
        s = jnp.swapaxes(ec_, -1, -2) * s + mm(jnp.swapaxes(kend_, -1, -2), u_op)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (w, u0, qg, bm, kend, ec))
    _, o = lax.scan(step, jnp.zeros((bh, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def _dot(a, b, dims, precision):
    return lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _in_step(chains):
    """Run generators stage by stage: every chain's first stage, then every
    chain's second, ... (a chain ``yield``s where its next product needs the
    last one's result). The compiler overlaps two chains of dependent products
    only where their products stand side by side in the program."""
    chains, done = list(chains), object()
    while chains:
        chains = [c for c in chains if next(c, done) is not done]


_HEADS_PER_STEP = 4    # heads a grid step of the walk takes, in step with each other

# The four ``pallas_call`` wrappers below are jitted: a grid step's unrolled
# chains make a kernel body of thousands of operations, and a model traces the
# op eight times a layer (forward, the vjp, remat's forward again, the
# backward rules). Under ``jax.jit`` the body is traced once a process for
# each shape, not each time (17 s of a 78 s set-up in the benchmark's cell).


def _fwd_kernel(w_ref, u0_ref, qg_ref, bm_ref, kend_ref, ec_ref,
                o_ref, s_all_ref, s_sc, *, precision):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_sc[:] = jnp.zeros_like(s_sc)

    f32 = jnp.float32
    op = w_ref.dtype

    def head(h):
        s = s_sc[h]
        s_all_ref[h, 0] = s
        w, qg, kend = (r[h, 0].astype(f32) for r in (w_ref, qg_ref, kend_ref))
        s_op = s.astype(op).astype(f32)
        u = u0_ref[h, 0].astype(f32) - _dot(w, s_op, _NN, precision)
        o = _dot(qg, s_op, _NN, precision)
        yield
        u_op = u.astype(op).astype(f32)
        o = o + _dot(bm_ref[h, 0].astype(f32), u_op, _NN, precision)
        o_ref[h, 0] = o.astype(o_ref.dtype)
        # ec arrives as a (dk, 1) column so that it scales S's rows
        s_sc[h] = ec_ref[h, 0] * s + _dot(kend, u_op, _TN, precision)

    _in_step(head(h) for h in range(w_ref.shape[0]))


def _bwd_kernel(w_ref, u0_ref, qg_ref, bm_ref, kend_ref, ec_ref, s_ref, do_ref,
                dw_ref, du0_ref, dqg_ref, dbm_ref, dkend_ref, dec_ref, ds_sc,
                *, precision):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_sc[:] = jnp.zeros_like(ds_sc)

    f32 = jnp.float32
    op = w_ref.dtype

    def head(h):
        ds_out = ds_sc[h]                   # d loss / d (this chunk's end state)
        s = s_ref[h, 0]
        w, qg, kend, bm = (r[h, 0].astype(f32)
                           for r in (w_ref, qg_ref, kend_ref, bm_ref))
        do = do_ref[h, 0].astype(f32)
        s_op = s.astype(op).astype(f32)
        u = u0_ref[h, 0].astype(f32) - _dot(w, s_op, _NN, precision)
        ds_op = ds_out.astype(op).astype(f32)
        du = _dot(bm, do, _TN, precision) + _dot(kend, ds_op, _NN, precision)
        dqg_ref[h, 0] = _dot(do, s_op, _NT, precision).astype(dqg_ref.dtype)
        dec_ref[h, 0] = jnp.sum(ds_out * s, axis=1, keepdims=True)
        yield
        u_op = u.astype(op).astype(f32)
        du_op = du.astype(op).astype(f32)
        dbm_ref[h, 0] = _dot(do, u_op, _NT, precision).astype(dbm_ref.dtype)
        dkend_ref[h, 0] = _dot(u_op, ds_op, _NT, precision).astype(dkend_ref.dtype)
        du0_ref[h, 0] = du.astype(du0_ref.dtype)
        dw_ref[h, 0] = (-_dot(du_op, s_op, _NT, precision)).astype(dw_ref.dtype)
        ds_sc[h] = (_dot(qg, do, _TN, precision) + ec_ref[h, 0] * ds_out
                    - _dot(w, du_op, _TN, precision))

    _in_step(head(h) for h in range(w_ref.shape[0]))


def _specs(bh, n, c, dk, dv, rev):
    """(heads a grid step, the block specs): the walk takes ``_HEADS_PER_STEP``
    heads a step where that divides their number."""
    hb = max(d for d in range(1, _HEADS_PER_STEP + 1) if bh % d == 0)
    at = (lambda b, i: (b, n - 1 - i, 0, 0)) if rev else (lambda b, i: (b, i, 0, 0))

    def spec(rows, cols):
        return pl.BlockSpec((hb, 1, rows, cols), at, memory_space=pltpu.VMEM)

    return hb, (spec(c, dk), spec(c, dv), spec(c, c), spec(dk, 1), spec(dk, dv))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


@x32
@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def _scan_fwd_pallas(w, u0, qg, bm, kend, ec, precision, interpret):
    bh, n, c, dk = w.shape
    dv = u0.shape[-1]
    hb, (ck, cv, cc, col, st) = _specs(bh, n, c, dk, dv, rev=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, precision=precision),
        grid=(bh // hb, n),
        in_specs=[ck, cv, ck, cc, ck, col],
        out_specs=[cv, st],
        out_shape=[jax.ShapeDtypeStruct((bh, n, c, dv), u0.dtype),
                   jax.ShapeDtypeStruct((bh, n, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="mxtpu_kda_fwd",
    )(w, u0, qg, bm, kend, jnp.swapaxes(ec, -1, -2))


@x32
@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def _scan_bwd_pallas(w, u0, qg, bm, kend, ec, s_all, do, precision, interpret):
    bh, n, c, dk = w.shape
    dv = u0.shape[-1]
    hb, (ck, cv, cc, col, st) = _specs(bh, n, c, dk, dv, rev=True)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in (w, u0, qg, bm, kend)]
    shapes.append(jax.ShapeDtypeStruct((bh, n, dk, 1), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, precision=precision),
        grid=(bh // hb, n),
        in_specs=[ck, cv, ck, cc, ck, col, st, cv],
        out_specs=[ck, cv, ck, cc, ck, col],
        out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="mxtpu_kda_bwd",
    )(w, u0, qg, bm, kend, jnp.swapaxes(ec, -1, -2), s_all, do)
    return tuple(outs[:5]) + (jnp.swapaxes(outs[5], -1, -2),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_pallas(w, u0, qg, bm, kend, ec, precision, interpret):
    return _scan_fwd_pallas(w, u0, qg, bm, kend, ec, precision, interpret)[0]


def _scan_pallas_fwd(w, u0, qg, bm, kend, ec, precision, interpret):
    o, s_all = _scan_fwd_pallas(w, u0, qg, bm, kend, ec, precision, interpret)
    return o, (w, u0, qg, bm, kend, ec, s_all)


def _scan_pallas_bwd(precision, interpret, res, do):
    return _scan_bwd_pallas(*res, do, precision, interpret)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


# ---- phase 1, the twin: everything inside a chunk in jax.numpy ------------------

def _doubling_inverse(a, precision):
    """(I + a)^-1 for strictly lower triangular ``a`` (..., n, n) by
    doubling: prod_i (I + (-a)^(2^i)), i < log2 n. The powers of ``a`` grow
    like binomials before they vanish, so this is for SMALL n only."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    mm = functools.partial(jnp.matmul, precision=precision)
    power = -a
    inv = eye + power
    span = 2
    while span < n:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        span *= 2
    return inv


def _unit_lower_inverse(a, precision, block=_SUB):
    """(I + a)^-1 for strictly lower triangular ``a`` (..., C, C): the
    diagonal ``block`` x ``block`` blocks by doubling (their powers stay under
    C(15, 8) = 6,435 however correlated the keys), then forward substitution
    over the block rows, T[i, <i] = -T[i, i] a[i, <i] T[<i, <i]. Doubling
    over the whole chunk loses every digit once keys repeat (a's entries
    near 1: a^32 reaches 1e18 against an inverse of order 1), which is what
    sent a training run to NaN in its fourth step (PR 27)."""
    c = a.shape[-1]
    if c <= block:
        return _doubling_inverse(a, precision)
    nb = c // block
    mm = functools.partial(jnp.matmul, precision=precision)
    lead = a.shape[:-2]
    blocks = a.reshape(lead + (nb, block, nb, block))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)
    d = _doubling_inverse(diag, precision)                  # (..., nb, blk, blk)
    inv = d[..., 0, :, :]
    for i in range(1, nb):
        below = a[..., i * block:(i + 1) * block, :i * block]
        d_i = d[..., i, :, :]
        left = -mm(d_i, mm(below, inv))
        top = jnp.concatenate(
            [inv, jnp.zeros(lead + (i * block, block), a.dtype)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([left, d_i], axis=-1)], axis=-2)
    return inv


def _chunk_terms(q, k, v, g, beta, scale, c):
    """The per-chunk operands of the walk, from (BH, N, C, d) inputs."""
    op = q.dtype
    f32 = jnp.float32
    precision, inv_precision = _precisions(op)
    sub = min(_SUB, c)
    ns = c // sub
    gc = jnp.cumsum(g.astype(f32), axis=-2)                  # (BH,N,C,dk)
    # the reference point of a row's sub-chunk: the running sum before it
    starts = jnp.concatenate(
        [jnp.zeros_like(gc[..., :1, :]), gc[..., sub - 1:-1:sub, :]], axis=-2)
    own = jnp.repeat(starts, sub, axis=-2)                   # (BH,N,C,dk)
    row = jnp.exp(gc - own)                                  # <= 1
    kf, qf = k.astype(f32), q.astype(f32)
    # a column as a row's sub-chunk sees it: k_j e^{ref_I - G_j}, zero for
    # the columns after that sub-chunk (they are masked anyway)
    expo = starts[..., :, None, :] - gc[..., None, :, :]     # (BH,N,ns,C,dk)
    seen = (jnp.arange(c)[None, :] < (jnp.arange(ns)[:, None] + 1) * sub)
    col = jnp.where(seen[..., None], jnp.exp(jnp.minimum(expo, _EXP_CLAMP)), 0.0)
    kcol = (kf[..., None, :, :] * col).astype(op)            # (BH,N,ns,C,dk)

    def pairwise(x):                                         # x: (BH,N,C,dk)
        xr = (x * row).astype(op).reshape(x.shape[:-2] + (ns, sub, x.shape[-1]))
        out = jnp.einsum("...scd,...sjd->...scj", xr, kcol, precision=precision,
                         preferred_element_type=f32)
        return out.reshape(x.shape[:-2] + (c, c))

    t_idx = jnp.arange(c)
    bcol = beta.astype(f32)[..., None]                       # (BH,N,C,1)
    a = jnp.where(t_idx[:, None] > t_idx[None, :], pairwise(kf) * bcol, 0.0)
    bm = jnp.where(t_idx[:, None] >= t_idx[None, :], pairwise(qf) * scale, 0.0)
    t = _unit_lower_inverse(a, inv_precision)
    eg = jnp.exp(gc)
    rhs = jnp.concatenate([kf * eg * bcol, v.astype(f32) * bcol], axis=-1)
    wu = jnp.matmul(t, rhs, precision=inv_precision)
    dk = k.shape[-1]
    w, u0 = wu[..., :dk], wu[..., dk:]
    g_end = gc[..., -1:, :]
    kend = kf * jnp.exp(g_end - gc)
    qg = qf * eg * scale
    return (w.astype(op), u0.astype(op), qg.astype(op), bm.astype(op),
            kend.astype(op), jnp.exp(g_end), precision)


# ---- phase 1 on the chip: the same terms, built in VMEM ----------------------------
#
# A tile is ``m`` whole chunks of one head (128 tokens where the length allows: two
# chunks of 64), taken as ONE block-diagonal (tile x tile) problem: A, B, the
# inverse's products and T [K e^G beta, V beta] then fill whole MXU passes, and
# the block structure costs a mask. A grid step works through several tiles in
# step with each other (``_in_step``): a tile's inverse is a chain of products
# each waiting for the last.

_TILE = 128            # tokens a tile holds (more only where one chunk is longer)
_TILES_PER_STEP = 4    # tiles a grid step works through


def _mm(a, b, dims, precision):
    """One product in VMEM, float32 out. HIGHEST: float32 operands as they are.
    HIGH: three bfloat16 passes split by hand (Mosaic has no HIGH; the split is
    XLA's: hi*hi + hi*lo + lo*hi). DEFAULT: one pass on bfloat16 operands."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    if precision == lax.Precision.HIGHEST:
        return _dot(a.astype(f32), b.astype(f32), dims, precision)
    if precision == lax.Precision.HIGH:
        a, b = a.astype(f32), b.astype(f32)
        a_hi, b_hi = a.astype(bf16), b.astype(bf16)
        a_lo = (a - a_hi.astype(f32)).astype(bf16)
        b_lo = (b - b_hi.astype(f32)).astype(bf16)
        one = lax.Precision.DEFAULT      # explicit: the process-wide default is HIGH
        return (_dot(a_hi, b_hi, dims, one) + _dot(a_hi, b_lo, dims, one)
                + _dot(a_lo, b_hi, dims, one))
    return _dot(a.astype(bf16), b.astype(bf16), dims, precision)


def _cumsum_rows(x, pos, c, reverse=False):
    """The running sum down the rows inside each chunk of ``c`` rows (``pos``: a
    row's place in its chunk), float32, by doubling over sublane rolls."""
    r = x.shape[0]
    shift = 1
    while shift < c:
        if reverse:
            x = x + jnp.where(pos + shift < c, pltpu.roll(x, r - shift, 0), 0.0)
        else:
            x = x + jnp.where(pos >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _rows_of(x, picks, span):
    """Row ``p`` of ``x`` repeated over ``span`` rows, for each p of ``picks``
    in turn (None: zeros)."""
    width = x.shape[1]
    return jnp.concatenate(
        [jnp.zeros((span, width), x.dtype) if p is None
         else jnp.broadcast_to(x[p:p + 1], (span, width)) for p in picks], axis=0)


def _block_diag(blocks):
    """(m c, m c) float32 with the (c, c) ``blocks`` down its diagonal."""
    m, c = len(blocks), blocks[0].shape[0]
    rows = []
    for i, blk in enumerate(blocks):
        parts = [jnp.zeros((c, i * c), jnp.float32)] if i else []
        parts.append(blk.astype(jnp.float32))
        if i < m - 1:
            parts.append(jnp.zeros((c, (m - 1 - i) * c), jnp.float32))
        rows.append(jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0])
    return jnp.concatenate(rows, axis=0) if m > 1 else rows[0]


def _tile_pairwise(q, k, g, beta_row, c, precision):
    """What the forward and the backward kernel both build first of a tile:
    the running log-decay, the row and column decays through one reference
    point a sub-chunk (as ``_chunk_terms``: same clamp, same mask), and A and B.
    q, k (r, dk) as served; g (r, dk) float32; beta_row (1, r) float32."""
    f32 = jnp.float32
    op = q.dtype
    r, dk = k.shape
    m, sub = r // c, min(_SUB, c)
    ns = c // sub
    log_c, log_sub = c.bit_length() - 1, sub.bit_length() - 1
    pos = lax.broadcasted_iota(jnp.int32, (r, dk), 0) & (c - 1)
    gc = _cumsum_rows(g, pos, c)

    def before(chunk, i):            # the row whose running sum slot i refers to
        return chunk * c + i * sub - 1 if i else None

    own = _rows_of(gc, [before(ch, i) for ch in range(m) for i in range(ns)], sub)
    row = jnp.exp(gc - own)
    refs = [_rows_of(gc, [before(ch, i) for ch in range(m)], c) for i in range(ns)]
    cols = [jnp.where(pos < (i + 1) * sub,
                      jnp.exp(jnp.minimum(refs[i] - gc, _EXP_CLAMP)), 0.0)
            for i in range(ns)]
    kf, qf = k.astype(f32), q.astype(f32)
    # slot i of the contraction holds row sub-chunk i's operands: the rows that
    # are of sub-chunk i (zero elsewhere) against every column as that
    # sub-chunk sees it, so ONE product gives every row its own reference
    kcol = jnp.concatenate([(kf * col).astype(op) for col in cols], axis=1)
    x = jnp.concatenate([kf * row, qf * row], axis=0)               # (2r, dk)
    slot = jnp.concatenate([pos, pos], axis=0) >> log_sub
    lhs = jnp.concatenate([jnp.where(slot == i, x, 0.0).astype(op)
                           for i in range(ns)], axis=1)             # (2r, ns dk)
    pair = _mm(lhs, kcol, _NT, precision)                           # (2r, r)
    ti = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    tj = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    same = (ti >> log_c) == (tj >> log_c)
    beta_col = jnp.sum(jnp.where(ti == tj, beta_row, 0.0), axis=1, keepdims=True)
    return dict(gc=gc, row=row, refs=refs, cols=cols, kf=kf, qf=qf, kcol=kcol,
                lhs=lhs, slot=slot, pos=pos, ti=ti, tj=tj, beta_col=beta_col,
                pair_k=pair[:r], pair_q=pair[r:],
                below=same & (ti > tj), upto=same & (ti >= tj),
                ends=_rows_of(gc, [(ch + 1) * c - 1 for ch in range(m)], c))


def _tile_inverse(a, ti, tj, c, precision):
    """(I + a)^-1 for a tile: ``a`` (r, r) strictly lower triangular and block
    diagonal over the chunks. ``_unit_lower_inverse``'s algorithm on whole
    tiles: the 16 x 16 diagonal blocks by doubling (as one block-diagonal
    matrix, whose powers stay block diagonal), then block forward
    substitution, two neighbours at a time: [[P, 0], [R, Q]]^-1 =
    X - X [[0, 0], [R, 0]] X with X = diag(P^-1, Q^-1). A generator
    (``_in_step``): it yields after each product and returns the inverse."""
    eye = (ti == tj).astype(jnp.float32)
    mm = functools.partial(_mm, dims=_NN, precision=precision)

    def within(size):                # both indices in one size x size diagonal block
        shift = size.bit_length() - 1
        return (ti >> shift) == (tj >> shift)

    size = min(_SUB, c)
    power = jnp.where(within(size), -a, 0.0)
    inv = eye + power
    span = 2
    while span < size:
        power = mm(power, power)
        yield
        inv = inv + mm(inv, power)
        yield
        span *= 2
    while size < c:
        below = mm(jnp.where(within(2 * size) & ~within(size), a, 0.0), inv)
        yield
        inv = inv - mm(inv, below)
        yield
        size *= 2
    return inv


def _terms_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      w_ref, u0_ref, qg_ref, bm_ref, kend_ref, ec_ref, t_ref,
                      *, c, r, scale, precision, inv_precision):
    f32 = jnp.float32
    dk = k_ref.shape[-1]
    m = r // c

    def tile(i):
        rows = slice(i * r, (i + 1) * r)
        p = _tile_pairwise(q_ref[0, rows], k_ref[0, rows], g_ref[0, rows],
                           beta_ref[0, 0, i:i + 1, :].astype(f32), c, precision)
        yield
        a = jnp.where(p["below"], p["pair_k"] * p["beta_col"], 0.0)
        t = yield from _tile_inverse(a, p["ti"], p["tj"], c, inv_precision)
        bm = jnp.where(p["upto"], p["pair_q"] * scale, 0.0)
        eg = jnp.exp(p["gc"])
        rhs = jnp.concatenate([p["kf"] * eg * p["beta_col"],
                               v_ref[0, rows].astype(f32) * p["beta_col"]], axis=1)
        wu = _mm(t, rhs, _NN, inv_precision)
        w_ref[0, rows] = wu[:, :dk].astype(w_ref.dtype)
        u0_ref[0, rows] = wu[:, dk:].astype(u0_ref.dtype)
        qg_ref[0, rows] = (p["qf"] * eg * scale).astype(qg_ref.dtype)
        kend_ref[0, rows] = (p["kf"] * jnp.exp(p["ends"] - p["gc"])).astype(kend_ref.dtype)
        for ch in range(m):
            blk = slice(ch * c, (ch + 1) * c)
            bm_ref[0, i * m + ch] = bm[blk, blk].astype(bm_ref.dtype)
            t_ref[0, i * m + ch] = t[blk, blk]
            ec_ref[0, i * m + ch] = jnp.exp(p["gc"][(ch + 1) * c - 1:(ch + 1) * c])

    _in_step(tile(i) for i in range(q_ref.shape[1] // r))


def _terms_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref,
                      dw_ref, du0_ref, dqg_ref, dbm_ref, dkend_ref, dec_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                      *, c, r, scale, precision, inv_precision):
    """The chunk terms' cotangents, a tile at a time: the cheap terms rebuilt
    from the inputs, T read as the forward saved it. With wu = T rhs and
    d rhs = T^T d wu: dA = -T^T (d wu rhs^T) T^T = -(d rhs) wu^T."""
    f32 = jnp.float32
    dk = k_ref.shape[-1]
    m, sub = r // c, min(_SUB, c)
    ns = c // sub

    def tile(i):
        rows = slice(i * r, (i + 1) * r)
        chunks = range(i * m, (i + 1) * m)
        p = _tile_pairwise(q_ref[0, rows], k_ref[0, rows], g_ref[0, rows],
                           beta_ref[0, 0, i:i + 1, :].astype(f32), c, precision)
        yield
        gc, kf, qf, beta_col = p["gc"], p["kf"], p["qf"], p["beta_col"]
        vf = v_ref[0, rows].astype(f32)
        t = _block_diag([t_ref[0, ch] for ch in chunks])
        eg = jnp.exp(gc)
        to_end = jnp.exp(p["ends"] - gc)
        keg = kf * eg
        # ---- through T [K e^G beta, V beta]
        dwu = jnp.concatenate([dw_ref[0, rows].astype(f32),
                               du0_ref[0, rows].astype(f32)], axis=1)
        rhs = jnp.concatenate([keg * beta_col, vf * beta_col], axis=1)
        drhs = _mm(t, dwu, _TN, inv_precision)
        wu = _mm(t, rhs, _NN, inv_precision)
        yield
        da = jnp.where(p["below"], -_mm(drhs, wu, _NT, inv_precision), 0.0)
        drhs_k, drhs_v = drhs[:, :dk], drhs[:, dk:]
        dbeta_col = (jnp.sum(da * p["pair_k"], axis=1, keepdims=True)
                     + jnp.sum(drhs_k * keg, axis=1, keepdims=True)
                     + jnp.sum(drhs_v * vf, axis=1, keepdims=True))
        # ---- through A and B's one product
        dbm = _block_diag([dbm_ref[0, ch] for ch in chunks])
        dpair = jnp.concatenate([da * beta_col,
                                 jnp.where(p["upto"], dbm * scale, 0.0)], axis=0)
        yield
        dlhs = _mm(dpair, p["kcol"], _NN, precision)                # (2r, ns dk)
        dkcol = _mm(dpair, p["lhs"], _TN, precision)                # (r, ns dk)
        yield
        dx = sum(jnp.where(p["slot"] == s, dlhs[:, s * dk:(s + 1) * dk], 0.0)
                 for s in range(ns))
        dx_k, dx_q = dx[:r], dx[r:]
        drow = (dx_k * kf + dx_q * qf) * p["row"]       # d (gc - own)
        dq = dx_q * p["row"]
        dk_ = dx_k * p["row"]
        dgc = drow
        dcols = []
        for s in range(ns):
            dkc = dkcol[:, s * dk:(s + 1) * dk]
            dk_ = dk_ + dkc * p["cols"][s]
            dcol = jnp.where(p["refs"][s] - gc < _EXP_CLAMP,
                             dkc * kf * p["cols"][s], 0.0)          # d (ref_s - gc)
            dgc = dgc - dcol
            dcols.append(dcol)
        # ---- Q e^G, K e^(G_C - G), e^(G_C), and e^G inside rhs
        dqg = dqg_ref[0, rows].astype(f32)
        dkend = dkend_ref[0, rows].astype(f32)
        dq = dq + dqg * eg * scale
        dk_ = dk_ + dkend * to_end + drhs_k * eg * beta_col
        dend = dkend * kf * to_end                                  # d (G_C - gc)
        dgc = dgc + (dqg * qf * scale + drhs_k * kf * beta_col) * eg - dend
        # ---- the reverse running sum. What reached a reference point (the
        # row before a sub-chunk, the chunk's last row) goes to every row up
        # to it: one vector a sub-chunk, added after the rows' own reverse sum
        adds = []
        for n, ch in enumerate(chunks):
            lo = n * c
            tail = (jnp.sum(dend[lo:lo + c], axis=0, keepdims=True)
                    + dec_ref[0, ch] * jnp.exp(gc[lo + c - 1:lo + c]))
            per_slot = [None] * ns
            for s in reversed(range(ns)):
                per_slot[s] = tail
                if s:
                    at = lo + s * sub
                    tail = (tail + jnp.sum(dcols[s][lo:lo + c], axis=0, keepdims=True)
                            - jnp.sum(drow[at:at + sub], axis=0, keepdims=True))
            adds += [jnp.broadcast_to(v_, (sub, dk)) for v_ in per_slot]
        dg = _cumsum_rows(dgc, p["pos"], c, reverse=True) + jnp.concatenate(adds, axis=0)
        dq_ref[0, rows] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows] = dk_.astype(dk_ref.dtype)
        dv_ref[0, rows] = (drhs_v * beta_col).astype(dv_ref.dtype)
        dg_ref[0, rows] = dg
        dbeta_ref[0, 0, i:i + 1, :] = jnp.sum(
            jnp.where(p["ti"] == p["tj"], dbeta_col, 0.0), axis=0, keepdims=True)

    _in_step(tile(i) for i in range(q_ref.shape[1] // r))


def _terms_specs(bh, n, c, dk, dv):
    """The grid and block specs for ``n`` chunks of ``c`` tokens a head: ``m``
    chunks a tile and ``per`` tiles a grid step, both dividing what they
    count, so no tile is ragged."""
    m = max(d for d in range(1, n + 1) if n % d == 0 and (d * c <= _TILE or d == 1))
    tiles = n // m
    per = max(d for d in range(1, _TILES_PER_STEP + 1) if tiles % d == 0)
    r = m * c
    steps = tiles // per

    def spec(*block):
        zeros = (0,) * (len(block) - 1)
        return pl.BlockSpec((1,) + block, lambda b, i: (b, i) + zeros,
                            memory_space=pltpu.VMEM)

    return dict(r=r, grid=(bh, steps), beta_shape=(bh, steps, per, r),
                k=spec(per * r, dk), v=spec(per * r, dv), beta=spec(1, per, r),
                cc=spec(per * m, c, c), ec=spec(per * m, 1, dk))


_TERMS_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


@x32
@functools.partial(jax.jit, static_argnames=("scale", "c", "interpret"))
def _terms_fwd_pallas(q, k, v, g, beta, scale, c, interpret):
    """q, k, g (BH, S, dk), v (BH, S, dv), beta (BH, S), S a multiple of ``c``:
    the walk's six operands, and T (BH, N, c, c) float32 for the backward."""
    bh, s, dk = k.shape
    dv = v.shape[-1]
    n = s // c
    z = _terms_specs(bh, n, c, dk, dv)
    precision, inv_precision = _precisions(q.dtype)
    op = q.dtype
    rows = lambda width: jax.ShapeDtypeStruct((bh, s, width), op)
    w, u0, qg, bm, kend, ec, t = pl.pallas_call(
        functools.partial(_terms_fwd_kernel, c=c, r=z["r"], scale=scale,
                          precision=precision, inv_precision=inv_precision),
        grid=z["grid"],
        in_specs=[z["k"], z["k"], z["v"], z["k"], z["beta"]],
        out_specs=[z["k"], z["v"], z["k"], z["cc"], z["k"], z["ec"], z["cc"]],
        out_shape=[rows(dk), rows(dv), rows(dk),
                   jax.ShapeDtypeStruct((bh, n, c, c), op), rows(dk),
                   jax.ShapeDtypeStruct((bh, n, 1, dk), jnp.float32),
                   jax.ShapeDtypeStruct((bh, n, c, c), jnp.float32)],
        compiler_params=_TERMS_PARAMS, interpret=interpret,
        name="mxtpu_kda_chunk_fwd",
    )(q, k, v, g, beta.reshape(z["beta_shape"]))
    chunked = lambda x: x.reshape(bh, n, c, x.shape[-1])
    return chunked(w), chunked(u0), chunked(qg), bm, chunked(kend), ec, t


@x32
@functools.partial(jax.jit, static_argnames=("scale", "c", "interpret"))
def _terms_bwd_pallas(q, k, v, g, beta, t, cts, scale, c, interpret):
    bh, s, dk = k.shape
    dv = v.shape[-1]
    n = s // c
    z = _terms_specs(bh, n, c, dk, dv)
    precision, inv_precision = _precisions(q.dtype)
    dw, du0, dqg, dbm, dkend, dec = cts
    flat = lambda x: x.reshape(bh, s, x.shape[-1])
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_terms_bwd_kernel, c=c, r=z["r"], scale=scale,
                          precision=precision, inv_precision=inv_precision),
        grid=z["grid"],
        in_specs=[z["k"], z["k"], z["v"], z["k"], z["beta"], z["cc"],
                  z["k"], z["v"], z["k"], z["cc"], z["k"], z["ec"]],
        out_specs=[z["k"], z["k"], z["v"], z["k"], z["beta"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(z["beta_shape"], jnp.float32)],
        compiler_params=_TERMS_PARAMS, interpret=interpret,
        name="mxtpu_kda_chunk_bwd",
    )(q, k, v, g, beta.reshape(z["beta_shape"]), t,
      flat(dw), flat(du0), flat(dqg), dbm, flat(dkend), dec)
    return dq, dk_, dv_, dg, dbeta.reshape(bh, s).astype(beta.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _terms_pallas(q, k, v, g, beta, scale, c, interpret):
    return _terms_fwd_pallas(q, k, v, g, beta, scale, c, interpret)[:6]


def _terms_pallas_fwd(q, k, v, g, beta, scale, c, interpret):
    *terms, t = _terms_fwd_pallas(q, k, v, g, beta, scale, c, interpret)
    return tuple(terms), (q, k, v, g, beta, t)


def _terms_pallas_bwd(scale, c, interpret, res, cts):
    return _terms_bwd_pallas(*res, cts, scale, c, interpret)


_terms_pallas.defvjp(_terms_pallas_fwd, _terms_pallas_bwd)


@jax.named_scope("mxtpu_kda")
def kda_chunked(q, k, v, g, beta, scale=None, chunk_size=64, use_kernel=False,
                interpret=None):
    """``o`` (B, H, S, d_v) of the gated delta rule over q, k (B, H, S, d_k),
    v (B, H, S, d_v), per-step log-decay g (B, H, S, d_k; <= 0, float32) and
    beta (B, H, S). ``chunk_size`` is a power of two (16 or more: a multiple
    of 16); S need not be a multiple of it (the tail is padded with tokens
    that neither decay nor write). ``use_kernel``: both phases in Pallas
    kernels (the chunk terms of all B*H heads in ``mxtpu_kda_chunk_fwd`` /
    ``_bwd``, the walk in ``mxtpu_kda_fwd`` / ``_bwd``) instead of the
    ``jax.numpy`` terms and the ``lax.scan`` twin.

    Everything here runs under the name scope ``mxtpu_kda``: XLA keeps it in
    each instruction's ``op_name``, which is how a device trace finds all of
    the mechanism's device time, the kernels and what little is left
    beside them (the padding, the reshapes)."""
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk_size)
    if c & (c - 1) or (c > _SUB and c % _SUB):
        raise ValueError(f"chunk_size {c} is not a power of two")
    scale = float(dk ** -0.5 if scale is None else scale)
    n = -(-s // c)
    pad = n * c - s
    bh = b * h

    def heads(x, width):
        x = x.reshape((bh, s) + ((width,) if width else ()))
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + (((0, 0),) if width else ()))
        return x

    xs = (heads(q, dk), heads(k, dk), heads(v, dv), heads(g, dk), heads(beta, 0))
    if use_kernel:
        interpret = resolve_interpret(interpret)
        terms = _terms_pallas(*xs[:3], xs[3].astype(jnp.float32), xs[4], scale, c,
                              interpret)
        o = _scan_pallas(*terms, _precisions(q.dtype)[0], interpret)
    else:
        o = _scan_twin(*_chunk_terms(
            *(x.reshape((bh, n, c) + x.shape[2:]) for x in xs), scale, c))
    o = o.reshape(bh, n * c, dv)[:, :s]
    return o.reshape(b, h, s, dv).astype(v.dtype)


def kda_recurrent(q, k, v, g, beta, scale=None):
    """The same function token by token (a ``lax.scan`` over time, float32):
    what the chunked form is tested against. Not on any training path."""
    dk = q.shape[-1]
    if scale is None:
        scale = dk ** -0.5
    f32 = jnp.float32
    hp = lax.Precision.HIGHEST

    def step(s, xs):
        q_, k_, v_, g_, b_ = xs                   # (B,H,dk) ... (B,H)
        s = jnp.exp(g_)[..., None] * s
        pred = jnp.einsum("bhkv,bhk->bhv", s, k_, precision=hp)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_, (v_ - pred) * b_[..., None],
                           precision=hp)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_, precision=hp) * scale

    xs = tuple(jnp.moveaxis(a.astype(f32), 2, 0) for a in (q, k, v, g, beta))
    s0 = jnp.zeros(q.shape[:2] + (dk, v.shape[-1]), f32)
    _, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2)
