"""The control's arithmetic: the nearest precision below bfloat16.

``fp8(f)`` wraps a bilinear map ``f(x, w)`` (a matmul or a convolution) so
that both operands are rounded to float8_e4m3 (per-tensor scale to the
type's range, as fp8 training recipes do) before it, and so are the
cotangent and the saved operands in its backward. Accumulation stays
float32, as the hardware's does. ``exact`` is the reference's own."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_E4M3_MAX = 448.0


def _q(x):
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, _E4M3_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def exact(f):
    return f


def fp8(f):
    @jax.custom_vjp
    def g(x, w):
        return f(_q(x), _q(w))

    def fwd(x, w):
        qx, qw = _q(x), _q(w)
        return f(qx, qw), (qx, qw)

    def bwd(res, dy):
        qx, qw = res
        _, vjp = jax.vjp(f, qx, qw)
        return vjp(_q(dy))

    g.defvjp(fwd, bwd)
    return g


def bf16(f):
    """Operands rounded to bfloat16 (the configuration's own precision):
    used by the tests to show that the limits admit it."""
    def r(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    @jax.custom_vjp
    def g(x, w):
        return f(r(x), r(w))

    def fwd(x, w):
        return f(r(x), r(w)), (r(x), r(w))

    def bwd(res, dy):
        _, vjp = jax.vjp(f, *res)
        return vjp(r(dy))

    g.defvjp(fwd, bwd)
    return g


PRECISIONS = {"exact": exact, "fp8": fp8, "bf16": bf16}
