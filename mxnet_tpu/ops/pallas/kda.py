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
over chunks: plain ``jax.numpy``, differentiated by jax. The second carries
S from chunk to chunk: on the chip a Pallas kernel with the state in VMEM
(``mxtpu_kda_fwd``) and a hand-written reverse kernel
(``mxtpu_kda_bwd``) that reads the saved chunk-start states; elsewhere
the same arithmetic as a ``lax.scan`` (the twin).
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


def _fwd_kernel(w_ref, u0_ref, qg_ref, bm_ref, kend_ref, ec_ref,
                o_ref, s_all_ref, s_sc, *, precision):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_sc[:] = jnp.zeros_like(s_sc)

    s = s_sc[:]
    s_all_ref[0, 0] = s
    f32 = jnp.float32
    w, qg, kend = (r[0, 0].astype(f32) for r in (w_ref, qg_ref, kend_ref))
    op = w_ref.dtype
    s_op = s.astype(op).astype(f32)
    u = u0_ref[0, 0].astype(f32) - _dot(w, s_op, _NN, precision)
    u_op = u.astype(op).astype(f32)
    o = _dot(qg, s_op, _NN, precision) \
        + _dot(bm_ref[0, 0].astype(f32), u_op, _NN, precision)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    # ec arrives as a (dk, 1) column so that it scales S's rows
    s_sc[:] = ec_ref[0, 0] * s + _dot(kend, u_op, _TN, precision)


def _bwd_kernel(w_ref, u0_ref, qg_ref, bm_ref, kend_ref, ec_ref, s_ref, do_ref,
                dw_ref, du0_ref, dqg_ref, dbm_ref, dkend_ref, dec_ref, ds_sc,
                *, precision):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_sc[:] = jnp.zeros_like(ds_sc)

    f32 = jnp.float32
    op = w_ref.dtype
    ds_out = ds_sc[:]                       # d loss / d (this chunk's end state)
    s = s_ref[0, 0]
    w, qg, kend, bm = (r[0, 0].astype(f32)
                       for r in (w_ref, qg_ref, kend_ref, bm_ref))
    do = do_ref[0, 0].astype(f32)
    s_op = s.astype(op).astype(f32)
    u = u0_ref[0, 0].astype(f32) - _dot(w, s_op, _NN, precision)
    u_op = u.astype(op).astype(f32)
    ds_op = ds_out.astype(op).astype(f32)
    du = _dot(bm, do, _TN, precision) + _dot(kend, ds_op, _NN, precision)
    du_op = du.astype(op).astype(f32)
    dbm_ref[0, 0] = _dot(do, u_op, _NT, precision).astype(dbm_ref.dtype)
    dqg_ref[0, 0] = _dot(do, s_op, _NT, precision).astype(dqg_ref.dtype)
    dkend_ref[0, 0] = _dot(u_op, ds_op, _NT, precision).astype(dkend_ref.dtype)
    dec_ref[0, 0] = jnp.sum(ds_out * s, axis=1, keepdims=True)
    du0_ref[0, 0] = du.astype(du0_ref.dtype)
    dw_ref[0, 0] = (-_dot(du_op, s_op, _NT, precision)).astype(dw_ref.dtype)
    ds_sc[:] = (_dot(qg, do, _TN, precision) + ec_ref[0, 0] * ds_out
                - _dot(w, du_op, _TN, precision))


def _specs(n, c, dk, dv, rev):
    at = (lambda b, i: (b, n - 1 - i, 0, 0)) if rev else (lambda b, i: (b, i, 0, 0))

    def spec(rows, cols):
        return pl.BlockSpec((1, 1, rows, cols), at, memory_space=pltpu.VMEM)

    return spec(c, dk), spec(c, dv), spec(c, c), spec(dk, 1), spec(dk, dv)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


@x32
def _scan_fwd_pallas(w, u0, qg, bm, kend, ec, precision, interpret):
    bh, n, c, dk = w.shape
    dv = u0.shape[-1]
    ck, cv, cc, col, st = _specs(n, c, dk, dv, rev=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, precision=precision),
        grid=(bh, n),
        in_specs=[ck, cv, ck, cc, ck, col],
        out_specs=[cv, st],
        out_shape=[jax.ShapeDtypeStruct((bh, n, c, dv), u0.dtype),
                   jax.ShapeDtypeStruct((bh, n, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="mxtpu_kda_fwd",
    )(w, u0, qg, bm, kend, jnp.swapaxes(ec, -1, -2))


@x32
def _scan_bwd_pallas(w, u0, qg, bm, kend, ec, s_all, do, precision, interpret):
    bh, n, c, dk = w.shape
    dv = u0.shape[-1]
    ck, cv, cc, col, st = _specs(n, c, dk, dv, rev=True)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in (w, u0, qg, bm, kend)]
    shapes.append(jax.ShapeDtypeStruct((bh, n, dk, 1), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, precision=precision),
        grid=(bh, n),
        in_specs=[ck, cv, ck, cc, ck, col, st, cv],
        out_specs=[ck, cv, ck, cc, ck, col],
        out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
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


# ---- phase 1: everything inside a chunk ----------------------------------------

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


@jax.named_scope("mxtpu_kda")
def kda_chunked(q, k, v, g, beta, scale=None, chunk_size=64, use_kernel=False,
                interpret=None, heads_per_group=8):
    """``o`` (B, H, S, d_v) of the gated delta rule over q, k (B, H, S, d_k),
    v (B, H, S, d_v), per-step log-decay g (B, H, S, d_k; <= 0, float32) and
    beta (B, H, S). ``chunk_size`` is a power of two (16 or more: a multiple
    of 16); S need not be a multiple of it (the tail is padded with tokens
    that neither decay nor write). ``use_kernel``: walk the chunks in the
    Pallas kernels instead of the ``lax.scan`` twin.

    The B*H heads are taken ``heads_per_group`` at a time (a ``lax.map`` whose
    body is checkpointed: the backward rebuilds one group's chunk terms, so
    the pairwise-decay operands of one group, not of all heads, are live).

    Everything here runs under the name scope ``mxtpu_kda``: XLA keeps it in
    each instruction's ``op_name``, which is how a device trace finds the
    chunk terms' fusions and the groups' loop beside the walk's kernels."""
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk_size)
    if c & (c - 1) or (c > _SUB and c % _SUB):
        raise ValueError(f"chunk_size {c} is not a power of two")
    scale = float(dk ** -0.5 if scale is None else scale)
    n = -(-s // c)
    pad = n * c - s
    bh = b * h
    per = heads_per_group if bh % heads_per_group == 0 else bh
    groups = bh // per

    def chunks(x, width):
        x = x.reshape((bh, s) + ((width,) if width else ()))
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + (((0, 0),) if width else ()))
        return x.reshape((groups, per, n, c) + ((width,) if width else ()))

    def group(xs):
        w, u0, qg, bm, kend, ec, precision = _chunk_terms(*xs, scale, c)
        if use_kernel:
            return _scan_pallas(w, u0, qg, bm, kend, ec, precision,
                                resolve_interpret(interpret))
        return _scan_twin(w, u0, qg, bm, kend, ec, precision)

    xs = (chunks(q, dk), chunks(k, dk), chunks(v, dv), chunks(g, dk),
          chunks(beta, 0))
    if groups == 1:
        o = group(tuple(x[0] for x in xs))
    else:
        o = lax.map(jax.checkpoint(group), xs)
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
