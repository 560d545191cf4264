"""Fused softmax cross-entropy Pallas kernel.

Reference analog: src/operator/nn/softmax.cc + the
softmax_cross_entropy op (src/operator/loss_binary_op.cc). The unfused
path materializes the full (N, V) log-softmax and its gradient in HBM;
for LM heads (V = 30k–250k) that doubles the activation-memory bill.
This kernel streams vocab blocks through VMEM: forward keeps only
(loss, lse) per row; backward reconstructs softmax(x) blockwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import resolve_interpret, x32

_NEG_INF = -1e30


def _xent_fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref,
                     m_sc, l_sc, corr_sc, *, v_len, block_n, block_v):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        corr_sc[:] = jnp.zeros_like(corr_sc)

    x = x_ref[:].astype(jnp.float32)
    col = j * block_v + lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1)
    x = jnp.where(col < v_len, x, _NEG_INF)

    m_prev = m_sc[:]
    m_cur = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    l_sc[:] = l_sc[:] * jnp.exp(m_prev - m_cur) + \
        jnp.sum(jnp.exp(x - m_cur), axis=1, keepdims=True)
    m_sc[:] = m_cur

    lab = lab_ref[:]  # (block_n, 1) int32
    hit = col == lab
    corr_sc[:] = corr_sc[:] + jnp.sum(jnp.where(hit, x, 0.0), axis=1,
                                      keepdims=True)

    @pl.when(j == nv - 1)
    def _():
        lse = m_sc[:] + jnp.log(l_sc[:])
        lse_ref[:] = lse
        loss_ref[:] = lse - corr_sc[:]


def _xent_bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref,
                     *, v_len, block_n, block_v):
    j = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32)
    col = j * block_v + lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1)
    p = jnp.exp(jnp.where(col < v_len, x, _NEG_INF) - lse_ref[:])
    onehot = (col == lab_ref[:]).astype(jnp.float32)
    dx_ref[:] = ((p - onehot) * g_ref[:]).astype(dx_ref.dtype)


def _pad_to(n, m):
    return ((n + m - 1) // m) * m


# row- and vocab-tile caps
_BLOCK_N_CAP = 128
_BLOCK_V_CAP = 2048


def _blocks(n, v):
    bn = min(_BLOCK_N_CAP, _pad_to(n, 8))
    bv = min(_BLOCK_V_CAP, _pad_to(v, 128))
    return bn, bv


@x32
def _xent_fwd(logits, labels, interpret):
    """No explicit padding: Mosaic masks partial edge blocks (reads of
    the out-of-bounds tail are garbage but the kernel's col < v_len
    mask and the caller's row slice neutralize them)."""
    interpret = resolve_interpret(interpret)
    n, v = logits.shape
    bn, bv = _blocks(n, v)
    lab = labels.astype(jnp.int32).reshape(n, 1)

    loss, lse = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, v_len=v, block_n=bn, block_v=bv),
        grid=(pl.cdiv(n, bn), pl.cdiv(v, bv)),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        name="mxtpu_softmax_xent_fwd",
        interpret=interpret,
    )(logits, lab)
    return loss[:, 0], lse[:, 0]


@x32
def _xent_bwd(logits, labels, lse, g, interpret):
    interpret = resolve_interpret(interpret)
    n, v = logits.shape
    bn, bv = _blocks(n, v)
    lab = labels.astype(jnp.int32).reshape(n, 1)
    lse2 = lse.reshape(n, 1)
    g2 = g.astype(jnp.float32).reshape(n, 1)

    dx = pl.pallas_call(
        functools.partial(_xent_bwd_kernel, v_len=v, block_n=bn, block_v=bv),
        grid=(pl.cdiv(n, bn), pl.cdiv(v, bv)),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, bv), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, v), logits.dtype),
        name="mxtpu_softmax_xent_bwd",
        interpret=interpret,
    )(logits, lab, lse2, g2)
    return dx


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_xent_fused(logits, labels, interpret=None):
    """Per-row -log softmax(logits)[labels]. logits (N, V), labels (N,)."""
    loss, _ = _xent_fwd(logits, labels, interpret)
    return loss


def _xent_vjp_fwd(logits, labels, interpret):
    loss, lse = _xent_fwd(logits, labels, interpret)
    return loss, (logits, labels, lse)


def _xent_vjp_bwd(interpret, res, g):
    logits, labels, lse = res
    dx = _xent_bwd(logits, labels, lse, g, interpret)
    return dx, None


softmax_xent_fused.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)
