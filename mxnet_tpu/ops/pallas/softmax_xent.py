"""Fused softmax cross-entropy Pallas kernel.

Reference analog: src/operator/nn/softmax.cc + the
softmax_cross_entropy op (src/operator/loss_binary_op.cc). The unfused
path materializes the full (N, V) log-softmax and its gradient in HBM;
for LM heads (V = 30k–250k) that doubles the activation-memory bill.
This kernel streams vocab blocks through VMEM: forward keeps only
(loss, lse) per row; backward reconstructs softmax(x) blockwise.

The kernels work on the logits **vocabulary-major**, ``(V, N)``:
vocabulary on sublanes, tokens on lanes. ``softmax_xent_fused`` takes
``(N, V)`` and transposes; behind a 2-D product ``x @ W.T`` XLA folds
that transpose into the matmul's output layout, so the logits are
written once as ``(V, N)``, kept so as the backward's residual, and
``dlogits`` reaches both backward matmuls as it lies. Why: a vocabulary
that is no multiple of 128 (BERT's 30,522) cannot be the minor axis of
the layout the TPU prefers for an array that crosses from the forward
program to the backward program, and a row-major kernel paid three
logits-sized copies a step for it (2 GB each at 64 x 512 tokens; PR 32).
The token count of a training step is a multiple of 128, the vocabulary
need not be: the last vocabulary block is masked, never padded. The
running max, sum and label logit reduce over sublanes (VPU work between
vregs, no cross-lane reduction) and every per-token vector (labels, lse,
loss, g) is a lane-dense ``(1, N)`` row. A tile is 2 MiB of logits at
any width (bfloat16 2048 x 512, float32 1024 x 512; chosen on the chip
at the three cells' head shapes, PR 32).
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
                     m_sc, l_sc, corr_sc, *, v_len, block_v):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        corr_sc[:] = jnp.zeros_like(corr_sc)

    x = x_ref[:].astype(jnp.float32)  # (block_v, block_n)
    row = j * block_v + lax.broadcasted_iota(jnp.int32, x.shape, 0)
    x = jnp.where(row < v_len, x, _NEG_INF)

    m_prev = m_sc[:]
    m_cur = jnp.maximum(m_prev, jnp.max(x, axis=0, keepdims=True))
    l_sc[:] = l_sc[:] * jnp.exp(m_prev - m_cur) + \
        jnp.sum(jnp.exp(x - m_cur), axis=0, keepdims=True)
    m_sc[:] = m_cur

    hit = row == lab_ref[:]  # labels: (1, block_n) int32
    corr_sc[:] = corr_sc[:] + jnp.sum(jnp.where(hit, x, 0.0), axis=0,
                                      keepdims=True)

    @pl.when(j == nv - 1)
    def _():
        lse = m_sc[:] + jnp.log(l_sc[:])
        lse_ref[:] = lse
        loss_ref[:] = lse - corr_sc[:]


def _xent_bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref,
                     *, v_len, block_v):
    j = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32)
    row = j * block_v + lax.broadcasted_iota(jnp.int32, x.shape, 0)
    p = jnp.exp(jnp.where(row < v_len, x, _NEG_INF) - lse_ref[:])
    onehot = (row == lab_ref[:]).astype(jnp.float32)
    dx_ref[:] = ((p - onehot) * g_ref[:]).astype(dx_ref.dtype)


# vocabulary- and token-tile sizes (sublanes x lanes) of 2-byte logits
_BLOCK_V = 2048
_BLOCK_N = 512


def _blocks(v, n, dtype):
    """A whole axis where it fits one tile (any length is a legal block
    then), else the tile: the last block of either axis is partial. A
    tile holds the same 2 MiB at every width (float32: 1024 x 512): the
    backward's double-buffered input and ``dx`` are four tiles, and at
    4 MiB each they alone are Mosaic's 16 MiB of scoped VMEM."""
    return (min(v, _BLOCK_V * 2 // jnp.dtype(dtype).itemsize),
            min(n, _BLOCK_N))


def _row_spec(bn):
    return pl.BlockSpec((1, bn), lambda i, j: (0, i), memory_space=pltpu.VMEM)


def _tile_spec(bv, bn):
    return pl.BlockSpec((bv, bn), lambda i, j: (j, i),
                        memory_space=pltpu.VMEM)


@x32
def _xent_fwd(xt, labels, interpret):
    """``xt`` (V, N). No explicit padding: Mosaic masks partial edge
    blocks (reads of the out-of-bounds tail are garbage; the kernel's
    row < v_len mask neutralizes the vocabulary's, and a token past N
    only ever touches its own lane, which is never written back)."""
    interpret = resolve_interpret(interpret)
    v, n = xt.shape
    bv, bn = _blocks(v, n, xt.dtype)
    row = jax.ShapeDtypeStruct((1, n), jnp.float32)

    loss, lse = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, v_len=v, block_v=bv),
        grid=(pl.cdiv(n, bn), pl.cdiv(v, bv)),
        in_specs=[_tile_spec(bv, bn), _row_spec(bn)],
        out_specs=[_row_spec(bn), _row_spec(bn)],
        out_shape=[row, row],
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)] * 3,
        name="mxtpu_softmax_xent_fwd",
        interpret=interpret,
    )(xt, labels.astype(jnp.int32).reshape(1, n))
    return loss[0], lse[0]


@x32
def _xent_bwd(xt, labels, lse, g, interpret):
    interpret = resolve_interpret(interpret)
    v, n = xt.shape
    bv, bn = _blocks(v, n, xt.dtype)

    return pl.pallas_call(
        functools.partial(_xent_bwd_kernel, v_len=v, block_v=bv),
        grid=(pl.cdiv(n, bn), pl.cdiv(v, bv)),
        in_specs=[_tile_spec(bv, bn), _row_spec(bn), _row_spec(bn),
                  _row_spec(bn)],
        out_specs=_tile_spec(bv, bn),
        out_shape=jax.ShapeDtypeStruct((v, n), xt.dtype),
        name="mxtpu_softmax_xent_bwd",
        interpret=interpret,
    )(xt, labels.astype(jnp.int32).reshape(1, n), lse.reshape(1, n),
      g.astype(jnp.float32).reshape(1, n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_xent_fused(logits, labels, interpret=None):
    """Per-row -log softmax(logits)[labels]. logits (N, V), labels (N,).
    Make ``logits`` with a 2-D product (module docstring)."""
    loss, _ = _xent_fwd(logits.T, labels, interpret)
    return loss


def _xent_vjp_fwd(logits, labels, interpret):
    xt = logits.T
    loss, lse = _xent_fwd(xt, labels, interpret)
    return loss, (xt, labels, lse)


def _xent_vjp_bwd(interpret, res, g):
    xt, labels, lse = res
    return _xent_bwd(xt, labels, lse, g, interpret).T, None


softmax_xent_fused.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)
