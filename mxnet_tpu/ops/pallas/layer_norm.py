"""Fused LayerNorm Pallas kernel (fwd + bwd).

Reference analog: src/operator/nn/layer_norm.cc (+ the CUDA
LayerNormGPU kernels in layer_norm.cu). The un-fused XLA lowering reads
x from HBM three times (mean, var, normalize); this kernel keeps a row
block resident in VMEM and does one pass, saving (mean, rstd) as
residuals for backward. dgamma/dbeta are accumulated across the
sequential TPU grid into the output refs.

Layout: the wrapper flattens any input to (R, D) over the normalized
(last) axis; rows are tiled (TILE_R, D) blocks. Non-last-axis LayerNorm
falls back to the jnp path (op_impl_nn.layer_norm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import resolve_interpret, x32


def _pick_tile_r(n_rows: int, d: int) -> int:
    # keep the x block + fp32 temps well under VMEM (~16MB); 4 bytes/elt
    # fp32 working set ≈ 3 * TILE_R * D * 4
    budget = 2 * 1024 * 1024
    tile = max(8, min(256, budget // max(1, d * 4)))
    # round down to a multiple of 8 (fp32 sublane)
    tile = max(8, (tile // 8) * 8)
    return min(tile, max(8, ((n_rows + 7) // 8) * 8))


def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rs_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rs = lax.rsqrt(var + eps)
    g = g_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    o_ref[:] = (xc * rs * g + b).astype(o_ref.dtype)
    mu_ref[:] = mu
    rs_ref[:] = rs


def _ln_bwd_kernel(x_ref, g_ref, mu_ref, rs_ref, dy_ref,
                   dx_ref, dg_ref, db_ref, *, n_rows, tile_r):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    mu = mu_ref[:]
    rs = rs_ref[:]
    xhat = (x - mu) * rs
    dxhat = dy * g
    m1 = jnp.mean(dxhat, axis=1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dx_ref[:] = (rs * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)

    # dgamma/dbeta: reduce over rows; TPU grid iterations run
    # sequentially, so accumulate into the (1, D) output refs. Rows past
    # n_rows are block padding (garbage reads) — mask them out.
    d = x.shape[1]
    row = i * tile_r + lax.broadcasted_iota(jnp.int32, (tile_r, d), 0)
    valid = row < n_rows
    pg = jnp.sum(jnp.where(valid, dy * xhat, 0.0), axis=0, keepdims=True)
    pb = jnp.sum(jnp.where(valid, dy, 0.0), axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dg_ref[:] = pg
        db_ref[:] = pb

    @pl.when(i > 0)
    def _():
        dg_ref[:] = dg_ref[:] + pg
        db_ref[:] = db_ref[:] + pb


@x32
def _ln_fwd(x2, gamma, beta, eps, interpret):
    r, d = x2.shape
    tile = _pick_tile_r(r, d)
    grid = (pl.cdiv(r, tile),)
    out, mu, rs = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, d), x2.dtype),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        name="mxtpu_layer_norm_fwd",
        interpret=interpret,
    )(x2, gamma.reshape(1, d), beta.reshape(1, d))
    return out, mu, rs


@x32
def _ln_bwd(x2, gamma, mu, rs, dy2, interpret):
    r, d = x2.shape
    tile = _pick_tile_r(r, d)
    grid = (pl.cdiv(r, tile),)
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, n_rows=r, tile_r=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, d), x2.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        name="mxtpu_layer_norm_bwd",
        interpret=interpret,
    )(x2, gamma.reshape(1, d), mu, rs, dy2)
    return dx, dg, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm_fused(x, gamma, beta, eps=1e-5, interpret=None):
    """Fused LayerNorm over the last axis. Any leading shape."""
    out, _, _ = _ln_res(x, gamma, beta, eps, interpret)
    return out


def _ln_res(x, gamma, beta, eps, interpret):
    interpret = resolve_interpret(interpret)
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    out, mu, rs = _ln_fwd(x2, gamma, beta, eps, interpret)
    return out.reshape(shape), mu, rs


def _layer_norm_vjp_fwd(x, gamma, beta, eps, interpret):
    out, mu, rs = _ln_res(x, gamma, beta, eps, interpret)
    return out, (x, gamma, mu, rs)


def _layer_norm_vjp_bwd(eps, interpret, res, dy):
    interpret = resolve_interpret(interpret)
    x, gamma, mu, rs = res
    shape = x.shape
    d = shape[-1]
    dx, dg, db = _ln_bwd(x.reshape(-1, d), gamma, mu, rs,
                         dy.reshape(-1, d), interpret)
    return (dx.reshape(shape), dg.reshape(gamma.shape).astype(gamma.dtype),
            db.reshape(gamma.shape).astype(gamma.dtype))


layer_norm_fused.defvjp(_layer_norm_vjp_fwd, _layer_norm_vjp_bwd)
