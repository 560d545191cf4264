"""Selective state-space scan (Mamba, arXiv:2312.00752) as a Pallas kernel
pair, forward and a hand-written backward.

Per channel c and state n, in float32::

    h_t[c, n] = exp(D_t[c] A[c, n]) h_{t-1}[c, n] + D_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] h_t[c, n] + skip[c] u_t[c]

with D_t = softplus(delta_t), A = -exp(a_log), h_0 = 0. No upstream-MXNet
analog. Nothing here feeds the MXU: a token is ~6 multiply-adds and one
exponential a state element, on the VPU and EUP, and the tokens of a row come
one after the other.

Layout. The state of a block of channels is (N, C_BLOCK): states on the
sublanes, channels on the lanes, so u_t and D_t (rows of a (T, C_BLOCK) tile)
broadcast down the sublanes for free. B_t[n] and C_t[n] are wanted along the
sublanes and constant along the lanes: the wrapper hands them over replicated
across one lane group, (B, L, N, 128), and the kernel repeats that tile over
the block's lane groups. The grid is (rows, time blocks, channel blocks) with
the channel blocks innermost: the replicated tiles are fetched once a time
block, every channel block's state waits in VMEM scratch (N x C floats) for
its next time block, and the backward sums dB and dC over the channel blocks
in their output tile.

Materialising h per token is L x C x N floats (2.7 GB a row at 8,192 x 5,120 x
16). The forward keeps the state at the START of each time block only
(L / T_BLOCK x N x C floats); the backward walks the time blocks from the last
to the first, rebuilds a block's states from its start into VMEM, then runs
the adjoint recurrence back through the block.

``selective_scan(..., use_kernel=False)`` is the ``jax.numpy`` twin (a
``lax.scan`` over time, checkpointed in stretches, differentiated by jax):
what runs off the chip.
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
# tokens a grid step; the backward holds T_BLOCK + 1 states of a channel
# block in VMEM (128 x 16 x 512 floats = 4 MiB)
_T_BLOCK = 128
_C_BLOCKS = (512, 256, 128)
_UNROLL = 4
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _lane_groups(x):
    """(N, C_BLOCK) -> (N, 128): the sum over the block's lane groups (whole
    vregs added; the 128 lanes left are summed outside the kernel)."""
    out = x[:, :_LANES]
    for g in range(1, x.shape[1] // _LANES):
        out = out + x[:, g * _LANES:(g + 1) * _LANES]
    return out


def _over_lanes(tile, width):
    """A (N, 128) lane-replicated tile repeated to (N, width)."""
    reps = width // _LANES
    return pltpu.repeat(tile, reps, axis=1) if reps > 1 else tile


def _walk(steps, token, carry):
    """``token(t, carry)`` for t = 0 .. steps - 1, `_UNROLL` tokens a loop
    trip (Mosaic unrolls a loop wholly or not at all): the scheduler then has
    the next tokens' exponentials beside this token's chain."""
    def trip(g, carry):
        for i in range(_UNROLL):
            carry = token(g * _UNROLL + i, carry)
        return carry

    return lax.fori_loop(0, steps // _UNROLL, trip, carry)


def _fwd_kernel(u_ref, dl_ref, a_ref, b_ref, c_ref, skip_ref,
                y_ref, hs_ref, h_sc, dt_sc, du_sc, y_sc):
    tb, cb = pl.program_id(1), pl.program_id(2)
    t_block, width = dt_sc.shape

    @pl.when(tb == 0)
    def _():
        h_sc[cb] = jnp.zeros(h_sc.shape[1:], jnp.float32)

    u = u_ref[0].astype(jnp.float32)
    dt_sc[:] = _softplus(dl_ref[0].astype(jnp.float32))
    du_sc[:] = dt_sc[:] * u
    a = a_ref[...]
    h0 = h_sc[cb]
    hs_ref[0, 0] = h0

    def token(t, h):
        dt = dt_sc[pl.ds(t, 1), :]
        b = _over_lanes(b_ref[0, t].astype(jnp.float32), width)
        c = _over_lanes(c_ref[0, t].astype(jnp.float32), width)
        h = jnp.exp(dt * a) * h + b * du_sc[pl.ds(t, 1), :]
        y_sc[pl.ds(t, 1), :] = jnp.sum(c * h, axis=0, keepdims=True)
        return h

    h_sc[cb] = _walk(t_block, token, h0)
    y_ref[0] = (y_sc[:] + skip_ref[...] * u).astype(y_ref.dtype)


def _bwd_kernel(u_ref, dl_ref, a_ref, b_ref, c_ref, skip_ref, dy_ref, hs_ref,
                du_ref, ddl_ref, db_ref, dc_ref, da_ref,
                dh_sc, u_sc, dt_sc, du_sc, dy_sc, gu_sc, gdt_sc, h_sc):
    tb, cb = pl.program_id(1), pl.program_id(2)
    t_block, width = dt_sc.shape
    f32 = jnp.float32

    @pl.when(tb == 0)
    def _():    # the LAST time block: nothing flows in from the future
        dh_sc[cb] = jnp.zeros(dh_sc.shape[1:], f32)
        da_ref[0, cb] = jnp.zeros(da_ref.shape[2:], f32)

    @pl.when(cb == 0)
    def _():
        db_ref[0] = jnp.zeros(db_ref.shape[1:], f32)
        dc_ref[0] = jnp.zeros(dc_ref.shape[1:], f32)

    u = u_ref[0].astype(f32)
    u_sc[:] = u
    raw = dl_ref[0].astype(f32)
    dt_sc[:] = _softplus(raw)
    du_sc[:] = dt_sc[:] * u
    dy = dy_ref[0].astype(f32)
    dy_sc[:] = dy
    a = a_ref[...]

    # the block's states again, from the state it started with: h_sc[t] is
    # the state BEFORE token t, h_sc[t + 1] after it
    h_sc[0] = hs_ref[0, 0]

    def rebuild(t, h):
        b = _over_lanes(b_ref[0, t].astype(f32), width)
        h = jnp.exp(dt_sc[pl.ds(t, 1), :] * a) * h + b * du_sc[pl.ds(t, 1), :]
        h_sc[t + 1] = h
        return h

    _walk(t_block, rebuild, h_sc[0])

    def token(k, carry):
        dh, da = carry
        t = t_block - 1 - k
        dt = dt_sc[pl.ds(t, 1), :]
        dy_t = dy_sc[pl.ds(t, 1), :]
        b = _over_lanes(b_ref[0, t].astype(f32), width)
        c = _over_lanes(c_ref[0, t].astype(f32), width)
        dh = dh + c * dy_t
        dc_ref[0, t] += _lane_groups(h_sc[t + 1] * dy_t)
        db_ref[0, t] += _lane_groups(dh * du_sc[pl.ds(t, 1), :])
        decay = jnp.exp(dt * a)
        into_du = jnp.sum(dh * b, axis=0, keepdims=True)     # d(dt * u)
        g = dh * h_sc[t] * decay                             # d(dt * A)
        gu_sc[pl.ds(t, 1), :] = into_du * dt
        gdt_sc[pl.ds(t, 1), :] = (into_du * u_sc[pl.ds(t, 1), :]
                                  + jnp.sum(g * a, axis=0, keepdims=True))
        return dh * decay, da + g * dt

    dh, da = _walk(t_block, token, (dh_sc[cb], jnp.zeros(a.shape, f32)))
    dh_sc[cb] = dh
    da_ref[0, cb] += da
    du_ref[0] = (gu_sc[:] + skip_ref[...] * dy).astype(du_ref.dtype)
    ddl_ref[0] = (gdt_sc[:] * jax.nn.sigmoid(raw)).astype(ddl_ref.dtype)


def _blocks(length, channels):
    t_block = min(_T_BLOCK, -(-length // 8) * 8)
    width = next((w for w in _C_BLOCKS if channels % w == 0), channels)
    return t_block, width


def _replicated(x):
    """(B, L, N) -> (B, L, N, 128): constant along the lanes."""
    return lax.broadcast_in_dim(x, x.shape + (_LANES,), (0, 1, 2))


def _padded(x, length):
    if x.shape[1] == length:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, length - x.shape[1])
    return jnp.pad(x, pad)


def _specs(t_block, width, n):
    row = pl.BlockSpec((1, t_block, width), lambda r, t, c: (r, t, c),
                       memory_space=pltpu.VMEM)
    a = pl.BlockSpec((n, width), lambda r, t, c: (0, c),
                     memory_space=pltpu.VMEM)
    rep = pl.BlockSpec((1, t_block, n, _LANES), lambda r, t, c: (r, t, 0, 0),
                       memory_space=pltpu.VMEM)
    skip = pl.BlockSpec((1, width), lambda r, t, c: (0, c),
                        memory_space=pltpu.VMEM)
    start = pl.BlockSpec((1, 1, n, width), lambda r, t, c: (r, t, 0, c),
                         memory_space=pltpu.VMEM)
    return row, a, rep, skip, start


@functools.partial(jax.jit, static_argnums=(6,))
@x32
def _scan_fwd(u, delta, a, b, c, skip, interpret):
    """y (B, L, C) in u's type and the state at the start of every time
    block, (B, L / T, N, C) float32. ``a`` is A transposed, (N, C) float32."""
    rows, length, channels = u.shape
    n = a.shape[0]
    t_block, width = _blocks(length, channels)
    padded = -(-length // t_block) * t_block
    nt, nc = padded // t_block, channels // width
    u_, dl_, b_, c_ = (_padded(x, padded) for x in (u, delta, b, c))
    row, a_spec, rep, skip_spec, start = _specs(t_block, width, n)
    y, starts = pl.pallas_call(
        _fwd_kernel,
        grid=(rows, nt, nc),
        in_specs=[row, row, a_spec, rep, rep, skip_spec],
        out_specs=[row, start],
        out_shape=[jax.ShapeDtypeStruct((rows, padded, channels), u.dtype),
                   jax.ShapeDtypeStruct((rows, nt, n, channels), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nc, n, width), jnp.float32)]
        + [pltpu.VMEM((t_block, width), jnp.float32)] * 3,
        compiler_params=_COMPILER_PARAMS,
        name="mxtpu_ssm_fwd",
        interpret=interpret,
    )(u_, dl_, a, _replicated(b_), _replicated(c_),
      skip.astype(jnp.float32).reshape(1, channels))
    return y[:, :length], starts


@functools.partial(jax.jit, static_argnums=(8,))
@x32
def _scan_bwd(u, delta, a, b, c, skip, dy, starts, interpret):
    """(du, ddelta, dA (N, C), db, dc): the adjoint recurrence, the time
    blocks walked from the last to the first."""
    rows, length, channels = u.shape
    n = a.shape[0]
    t_block, width = _blocks(length, channels)
    nt, nc = starts.shape[1], channels // width
    padded = nt * t_block
    u_, dl_, b_, c_, dy_ = (_padded(x, padded) for x in (u, delta, b, c, dy))

    def back(spec):     # the same block, the time axis walked backwards
        return pl.BlockSpec(
            spec.block_shape,
            lambda r, t, cb, m=spec.index_map: m(r, nt - 1 - t, cb),
            memory_space=pltpu.VMEM)

    row, a_spec, rep, skip_spec, start = _specs(t_block, width, n)
    da_spec = pl.BlockSpec((1, nc, n, width), lambda r, t, cb: (r, 0, 0, 0),
                           memory_space=pltpu.VMEM)
    f32 = jnp.float32
    du, ddl, db, dc, da = pl.pallas_call(
        _bwd_kernel,
        grid=(rows, nt, nc),
        in_specs=[back(row), back(row), a_spec, back(rep), back(rep),
                  skip_spec, back(row), back(start)],
        out_specs=[back(row), back(row), back(rep), back(rep), da_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, padded, channels), u.dtype),
                   jax.ShapeDtypeStruct((rows, padded, channels), delta.dtype),
                   jax.ShapeDtypeStruct((rows, padded, n, _LANES), f32),
                   jax.ShapeDtypeStruct((rows, padded, n, _LANES), f32),
                   jax.ShapeDtypeStruct((rows, nc, n, width), f32)],
        scratch_shapes=[pltpu.VMEM((nc, n, width), f32)]
        + [pltpu.VMEM((t_block, width), f32)] * 6
        + [pltpu.VMEM((t_block + 1, n, width), f32)],
        compiler_params=_COMPILER_PARAMS,
        name="mxtpu_ssm_bwd",
        interpret=interpret,
    )(u_, dl_, a, _replicated(b_), _replicated(c_),
      skip.astype(f32).reshape(1, channels), dy_, starts)
    # each lane of a replicated tile holds the sum over its own channels
    db = db[:, :length].sum(-1).astype(b.dtype)
    dc = dc[:, :length].sum(-1).astype(c.dtype)
    da = jnp.moveaxis(da.sum(0), 0, 1).reshape(n, channels)
    return du[:, :length], ddl[:, :length], da, db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernel(u, delta, a, b, c, skip, interpret):
    return _scan_fwd(u, delta, a, b, c, skip, interpret)[0]


def _scan_vjp_fwd(u, delta, a, b, c, skip, interpret):
    y, starts = _scan_fwd(u, delta, a, b, c, skip, interpret)
    return y, (u, delta, a, b, c, skip, starts)


def _scan_vjp_bwd(interpret, res, dy):
    u, delta, a, b, c, skip, starts = res
    du, ddl, da, db, dc = _scan_bwd(u, delta, a, b, c, skip, dy, starts,
                                    interpret)
    dskip = jnp.einsum("blc,blc->c", dy.astype(jnp.float32),
                       u.astype(jnp.float32)).astype(skip.dtype)
    return du, ddl, da, db, dc, dskip


_scan_kernel.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)

_STRETCH = 64       # tokens a checkpointed stretch of the twin's walk


def _scan_twin(u, delta, a, b, c, skip):
    """The same scan in ``jax.numpy``: a ``lax.scan`` over time, token by
    token, checkpointed in stretches; differentiated by jax."""
    f32 = jnp.float32
    rows, length, channels = u.shape
    u32, dt = u.astype(f32), _softplus(delta.astype(f32))

    def token(h, xs):
        u_t, dt_t, b_t, c_t = xs                    # (B, C), (B, C), (B, N) x 2
        h = (jnp.exp(dt_t[:, None, :] * a) * h
             + b_t[:, :, None] * (dt_t * u_t)[:, None, :])
        return h, jnp.sum(c_t[:, :, None] * h, axis=1)

    @jax.checkpoint
    def stretch(h, xs):
        return lax.scan(token, h, xs)

    seg = _STRETCH if length % _STRETCH == 0 else length
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((length // seg, seg) + x.shape[:1]
                                             + x.shape[2:])
               for x in (u32, dt, b.astype(f32), c.astype(f32)))
    _, y = lax.scan(stretch, jnp.zeros((rows,) + a.shape, f32), xs)
    y = jnp.moveaxis(y.reshape(length, rows, channels), 0, 1)
    return (y + skip.astype(f32) * u32).astype(u.dtype)


def selective_scan(u, delta, a_log, b, c, skip, use_kernel=False,
                   interpret=None):
    """u, delta (B, L, C); a_log (C, N); b, c (B, L, N); skip (C,). Returns
    y (B, L, C) in u's type: the recurrence of the module docstring with
    D_t = softplus(delta_t) and A = -exp(a_log), state in float32."""
    a = -jnp.exp(a_log.astype(jnp.float32)).T            # (N, C)
    if not use_kernel:
        return _scan_twin(u, delta, a, b, c, skip)
    return _scan_kernel(u, delta, a, b, c, skip, resolve_interpret(interpret))
