"""Depthwise causal short convolution over time as a Pallas kernel pair,
forward and a hand-written backward.

Per channel, in float32::

    y_t = act(sum_i w_i x_{t-(K-1)+i} + bias),    i = 0 .. K-1

with zeros before a row's first token (the last tap is the token's own) and
``act`` SiLU or nothing: the short convolution in front of a linear-attention
or state-space mixer (``F.causal_conv1d``; no upstream-MXNet analog). Four
taps a channel is bytes, not FLOPs: the kernels read x (and dy) once and
write y (dx) once, in the projections' own (B, S, C) layout, where the XLA
form pads a float32 copy of x and sums K slices of it that start at sublane
offsets 0 .. K-1.

Layout. Tokens on the sublanes, channels on the lanes. The grid is (rows,
channel blocks, token blocks), the token blocks innermost and in order; a
grid step holds `_T_BLOCKS` tokens x `_C_BLOCKS` channels and walks them
`_SUB` tokens a loop trip: the trip's tokens are cast to float32 into a VMEM
scratch behind `_HALO` rows of what came before them, and the K shifted
windows are read back from that scratch at static sublane offsets, `_ROWS`
tokens of one 128-lane strip a pass so that a pass lives in registers. The
forward carries the `_HALO` rows from trip to trip and from grid step to
grid step (zeros at a row's first block), so x is read once.

The backward keeps x, weight and bias only. It walks the tokens from the
last to the first: a trip rebuilds its pre-activation from x (the rows before
a grid step's block come from a second, `_HALO`-row BlockSpec on x), forms
g = dy * act'(pre), writes dx_t = sum_i w_i g_{t+K-1-i} with the K - 1 rows
of g AFTER the trip carried from the trip before it, and adds sum_t g_t
x_{t-(K-1)+i} and sum_t g_t into a (K + 1, 8, channel block) float32 output
tile that stays in VMEM over a row's token blocks; the rows and the eight
sublanes are summed outside.

The twin is ``F.causal_conv1d``'s own ``jax.numpy`` body
(``ndarray/op_impl_nn.py``): what runs off the chip and at shapes the tiles
do not divide (`tiles`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import resolve_interpret, x32

_LANES = 128
MAX_TAPS = 8
# rows kept of what came before (after, in the backward) a trip's tokens: one
# bfloat16 sublane tile, of which the last (first) K - 1 are read
_HALO = 16
_T_BLOCKS = (1024, 512, 256)    # tokens a grid step: the largest that divides
_C_BLOCKS = (512, 256, 128)     # channels a grid step
_SUB = 256                      # tokens a loop trip
_ROWS = 64                      # tokens a pass of a trip's unrolled body
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 1024 * 1024)


def tiles(shape, taps, dtype):
    """(tokens a grid step, tokens a loop trip, tokens a pass, channels a
    block) for a (B, S, C) input, or None where the kernels do not take it:
    another type than bfloat16 / float32, more than `MAX_TAPS` taps, a channel
    count that is no multiple of 128, a length no token block divides."""
    if len(shape) != 3 or dtype not in (jnp.bfloat16, jnp.float32):
        return None
    _, length, channels = shape
    if not 1 <= taps <= MAX_TAPS or channels % _LANES:
        return None
    t_block = next((t for t in _T_BLOCKS if length % t == 0), None)
    if t_block is None:
        return None
    width = next(w for w in _C_BLOCKS if channels % w == 0)
    return t_block, min(_SUB, t_block), _ROWS, width


def _strips(width):
    """The 128-lane strips of a channel block: a pass is `_ROWS` tokens of
    one strip, so what it holds (the K taps, K windows, a sum) stays in
    vector registers."""
    return [slice(c, c + _LANES) for c in range(0, width, _LANES)]


def _down(tile, rows):
    """A tap's (8, 128) sublane-replicated tile repeated to (rows, 128)."""
    return jnp.concatenate([tile] * (rows // 8), axis=0)


def _weighted(w, windows):
    """sum_i w_i * window_i in the order i = 0 .. K-1, the twin's. The tap
    stands first in each product: Mosaic gives a product its first operand's
    sublane offset, so a window read off a tile's start is shifted once and
    every sum stays aligned."""
    y = w[0] * windows[0]
    for tap, window in zip(w[1:], windows[1:]):
        y = y + tap * window
    return y


def _fwd_kernel(*refs, taps, has_bias, activation, sub, rows):
    if has_bias:
        x_ref, w_ref, b_ref, y_ref, xs_sc = refs
    else:
        (x_ref, w_ref, y_ref, xs_sc), b_ref = refs, None
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():    # a row's first tokens: nothing before them
        xs_sc[0:_HALO, :] = jnp.zeros((_HALO, xs_sc.shape[1]), f32)

    def trip(j, carry):
        start = pl.multiple_of(j * sub, sub)
        for lanes in _strips(xs_sc.shape[1]):
            w = [_down(w_ref[i, :, lanes], rows) for i in range(taps)]
            bias = _down(b_ref[:, lanes], rows) if has_bias else None
            for r0 in range(0, sub, rows):
                x = x_ref[0, pl.ds(start + r0, rows), lanes].astype(f32)
                xs_sc[_HALO + r0:_HALO + r0 + rows, lanes] = x
                lo = _HALO + r0 - (taps - 1)
                y = _weighted(w, [xs_sc[lo + i:lo + i + rows, lanes]
                                  for i in range(taps - 1)] + [x])
                if has_bias:
                    y = y + bias
                if activation == "silu":
                    y = y * jax.nn.sigmoid(y)
                y_ref[0, pl.ds(start + r0, rows), lanes] = y.astype(y_ref.dtype)
        xs_sc[0:_HALO, :] = xs_sc[sub:sub + _HALO, :]
        return carry

    lax.fori_loop(0, x_ref.shape[1] // sub, trip, 0)


def _fold(x):
    """(rows, C) -> (8, C): whole sublane tiles added."""
    return functools.reduce(
        lambda a, b: a + b, [x[r:r + 8] for r in range(0, x.shape[0], 8)])


def _bwd_kernel(*refs, taps, has_bias, activation, sub, rows):
    if has_bias:
        (x_ref, before_ref, dy_ref, w_ref, b_ref,
         dx_ref, dwb_ref, xs_sc, gs_sc) = refs
    else:
        (x_ref, before_ref, dy_ref, w_ref,
         dx_ref, dwb_ref, xs_sc, gs_sc), b_ref = refs, None
    f32 = jnp.float32
    s, last = pl.program_id(2), pl.num_programs(2) - 1
    trips, width = x_ref.shape[1] // sub, xs_sc.shape[1]

    @pl.when(s == 0)
    def _():    # a row's LAST tokens: no cotangent flows in from after them
        gs_sc[sub:sub + _HALO, :] = jnp.zeros((_HALO, width), f32)
        dwb_ref[0] = jnp.zeros(dwb_ref.shape[1:], f32)

    def trip(n, carry):
        j = trips - 1 - n
        start = pl.multiple_of(j * sub, sub)

        # the rows before the trip's: the block's own, the block before's, or
        # (a row's first tokens) zeros
        @pl.when(j > 0)
        def _():
            lo = pl.multiple_of(j * sub - _HALO, _HALO)
            xs_sc[0:_HALO, :] = x_ref[0, pl.ds(lo, _HALO), :].astype(f32)

        @pl.when(jnp.logical_and(j == 0, s < last))
        def _():
            xs_sc[0:_HALO, :] = before_ref[0].astype(f32)

        @pl.when(jnp.logical_and(j == 0, s == last))
        def _():
            xs_sc[0:_HALO, :] = jnp.zeros((_HALO, width), f32)

        for lanes in _strips(width):
            w = [_down(w_ref[i, :, lanes], rows) for i in range(taps)]
            bias = _down(b_ref[:, lanes], rows) if has_bias else None
            for r0 in range(0, sub, rows):
                xs_sc[_HALO + r0:_HALO + r0 + rows, lanes] = \
                    x_ref[0, pl.ds(start + r0, rows), lanes].astype(f32)
            sums = [jnp.zeros((8, _LANES), f32)] * (taps + has_bias)
            for r0 in reversed(range(0, sub, rows)):
                g = dy_ref[0, pl.ds(start + r0, rows), lanes].astype(f32)
                lo = _HALO + r0 - (taps - 1)
                windows = [xs_sc[lo + i:lo + i + rows, lanes]
                           for i in range(taps)]
                if activation == "silu":
                    pre = _weighted(w, windows)
                    if has_bias:
                        pre = pre + bias
                    sig = jax.nn.sigmoid(pre)
                    g = g * (sig * (1.0 + pre * (1.0 - sig)))
                gs_sc[r0:r0 + rows, lanes] = g
                for i in range(taps):
                    sums[i] = sums[i] + _fold(g * windows[i])
                if has_bias:
                    sums[taps] = sums[taps] + _fold(g)
                dx = g * w[taps - 1]        # dx_t = sum_i w_i g_{t+K-1-i}
                for i in range(taps - 1):
                    up = r0 + taps - 1 - i
                    dx = dx + w[i] * gs_sc[up:up + rows, lanes]
                dx_ref[0, pl.ds(start + r0, rows), lanes] = \
                    dx.astype(dx_ref.dtype)
            for i, total in enumerate(sums):
                dwb_ref[0, i, :, lanes] += total
        gs_sc[sub:sub + _HALO, :] = gs_sc[0:_HALO, :]
        return carry

    lax.fori_loop(0, trips, trip, 0)


def _operands(weight, bias, width):
    """The taps as (K, 8, C) float32 and the bias as (8, C), constant down the
    sublanes so that a strip's tap is a whole vector register as it is
    loaded, with their BlockSpecs."""
    channels, taps = weight.shape
    w = weight.astype(jnp.float32).T
    arrays = [lax.broadcast_in_dim(w, (taps, 8, channels), (0, 2))]
    specs = [pl.BlockSpec((taps, 8, width), lambda b, c, s: (0, 0, c),
                          memory_space=pltpu.VMEM)]
    if bias is not None:
        arrays.append(lax.broadcast_in_dim(bias.astype(jnp.float32),
                                           (8, channels), (1,)))
        specs.append(pl.BlockSpec((8, width), lambda b, c, s: (0, c),
                                  memory_space=pltpu.VMEM))
    return arrays, specs


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@x32
def _conv_fwd(x, weight, bias, activation, tile, interpret):
    batch, length, channels = x.shape
    t_block, sub, rows, width = tile
    block = pl.BlockSpec((1, t_block, width), lambda b, c, s: (b, s, c),
                         memory_space=pltpu.VMEM)
    arrays, specs = _operands(weight, bias, width)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=weight.shape[1],
                          has_bias=bias is not None, activation=activation,
                          sub=sub, rows=rows),
        grid=(batch, channels // width, length // t_block),
        in_specs=[block] + specs,
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + sub, width), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="mxtpu_conv1d_fwd",
        interpret=interpret,
    )(x, *arrays)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
@x32
def _conv_bwd(x, weight, bias, dy, activation, tile, interpret):
    """(dx, dweight, dbias or None): the token blocks walked from the last
    to the first."""
    batch, length, channels = x.shape
    taps = weight.shape[1]
    t_block, sub, rows, width = tile
    blocks, per = length // t_block, t_block // _HALO
    block = pl.BlockSpec((1, t_block, width),
                         lambda b, c, s: (b, blocks - 1 - s, c),
                         memory_space=pltpu.VMEM)
    before = pl.BlockSpec(
        (1, _HALO, width),
        lambda b, c, s: (b, jnp.maximum((blocks - 1 - s) * per - 1, 0), c),
        memory_space=pltpu.VMEM)
    arrays, specs = _operands(weight, bias, width)
    n_sums = taps + (bias is not None)
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, has_bias=bias is not None,
                          activation=activation, sub=sub, rows=rows),
        grid=(batch, channels // width, blocks),
        in_specs=[block, before, block] + specs,
        out_specs=[block,
                   pl.BlockSpec((1, n_sums, 8, width),
                                lambda b, c, s: (b, 0, 0, c),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((batch, n_sums, 8, channels),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_HALO + sub, width), jnp.float32),
                        pltpu.VMEM((sub + _HALO, width), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name="mxtpu_conv1d_bwd",
        interpret=interpret,
    )(x, x, dy, *arrays)
    sums = sums.sum((0, 2))                             # (K or K + 1, C)
    dweight = sums[:taps].T.astype(weight.dtype)
    dbias = None if bias is None else sums[taps].astype(bias.dtype)
    return dx, dweight, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(x, weight, bias, activation, tile, interpret):
    return _conv_fwd(x, weight, bias, activation, tile, interpret)


def _conv_vjp_fwd(x, weight, bias, activation, tile, interpret):
    return _conv_fwd(x, weight, bias, activation, tile, interpret), \
        (x, weight, bias)


def _conv_vjp_bwd(activation, tile, interpret, res, dy):
    x, weight, bias = res
    return _conv_bwd(x, weight, bias, dy, activation, tile, interpret)


_conv.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


def causal_conv1d(x, weight, bias=None, activation=None, interpret=None):
    """x (B, S, C), weight (C, K), bias (C,) or None; ``activation`` 'silu'
    or None. The kernel pair (``mxtpu_conv1d_fwd`` / ``mxtpu_conv1d_bwd``) at
    a shape `tiles` takes; the caller asks `tiles` first."""
    if activation not in (None, "silu"):
        raise ValueError(f"causal_conv1d: unknown activation {activation!r}")
    tile = tiles(x.shape, weight.shape[1], x.dtype)
    if tile is None:
        raise ValueError(f"causal_conv1d: no tiles for {x.shape} "
                         f"{x.dtype}, {weight.shape[1]} taps")
    return _conv(x, weight, bias, activation, tile,
                 resolve_interpret(interpret))
