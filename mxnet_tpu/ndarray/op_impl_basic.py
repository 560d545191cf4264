"""Tensor operators: elementwise, broadcast, reductions, shape/index ops.

TPU-native implementations of the reference's ``src/operator/tensor/``
family (elemwise_unary_op_basic.cc, elemwise_binary_op_basic.cc,
broadcast_reduce_op_value.cc, matrix_op.cc, indexing_op.cc,
ordering_op.cc, init_op.cc) and the mshadow functor library
(src/operator/mshadow_op.h). Each op is a pure jax function registered
through the op registry; XLA fuses elementwise chains (the mshadow
Kernel::Launch analog is simply XLA fusion) and tiles matmuls onto the
MXU. Gradients come from jax.vjp — the per-op FGradient table of the
reference collapses into JAX's AD rules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import dtype_np
from .register import register_op

# ----------------------------------------------------------------------
# elementwise unary (mshadow_op.h functors)
# ----------------------------------------------------------------------
_UNARY = {
    "abs": jnp.abs,
    "exp": jnp.exp,
    "log": jnp.log,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "log1p": jnp.log1p,
    "expm1": jnp.expm1,
    "sqrt": jnp.sqrt,
    "rsqrt": lambda x: lax.rsqrt(x),
    "cbrt": jnp.cbrt,
    "rcbrt": lambda x: 1.0 / jnp.cbrt(x),
    "square": jnp.square,
    "reciprocal": lambda x: 1.0 / x,
    "negative": jnp.negative,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "arctan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh,
    "arccosh": jnp.arccosh,
    "arctanh": jnp.arctanh,
    "sigmoid": jax.nn.sigmoid,
    "softsign": jax.nn.soft_sign,
    "relu": jax.nn.relu,
    "gamma": lambda x: jnp.exp(jax.lax.lgamma(x)),
    "gammaln": lambda x: jax.lax.lgamma(x),
    "erf": jax.lax.erf,
    "erfinv": jax.lax.erf_inv,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
    "identity": lambda x: x + 0,
}

for _name, _fn in _UNARY.items():
    register_op(_name)(_fn)

register_op("copy", aliases=("_copy",))(lambda x: x + 0)
register_op("BlockGrad", aliases=("stop_gradient",), differentiable=False)(
    lambda x: lax.stop_gradient(x))
register_op("make_loss")(lambda x: x + 0)

_NONDIFF_UNARY = {
    "round": jnp.round,
    "rint": jnp.rint,
    "fix": jnp.trunc,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "trunc": jnp.trunc,
    "sign": jnp.sign,
    "logical_not": lambda x: jnp.logical_not(x.astype(bool)).astype(x.dtype),
    "isnan": lambda x: jnp.isnan(x),
    "isinf": lambda x: jnp.isinf(x),
    "isfinite": lambda x: jnp.isfinite(x),
}
for _name, _fn in _NONDIFF_UNARY.items():
    register_op(_name, differentiable=False)(_fn)


@register_op("clip")
def clip(x, a_min=None, a_max=None):
    return jnp.clip(x, a_min, a_max)


@register_op("Cast", aliases=("cast",))
def cast(x, dtype="float32"):
    return x.astype(dtype_np(dtype))


@register_op("_constant", differentiable=False)
def _constant(value=None, dtype="float32"):
    """Embed a small static constant into the graph (works on every
    frontend path: eager, traced, and SYMBOLIC — symbols cannot wrap
    runtime numpy arrays, so constants must be op parameters)."""
    return jnp.asarray(np.asarray(value), dtype_np(dtype))


@register_op("amp_cast")
def amp_cast(x, dtype="float32"):
    return x.astype(dtype_np(dtype))


@register_op("amp_multicast", wrap=False, dynamic_arity=True)
def amp_multicast(*xs, num_outputs=None, cast_narrow=False):
    dts = [x.dtype for x in xs]
    widths = [jnp.dtype(d).itemsize for d in dts]
    target = dts[int(np.argmin(widths))] if cast_narrow else dts[int(np.argmax(widths))]
    return tuple(x.astype(target) for x in xs)


# ----------------------------------------------------------------------
# broadcast binary (elemwise_binary_op_basic.cc + broadcast_op)
# jnp broadcasting covers both the reference's elemwise_* (same-shape)
# and broadcast_* (numpy rules) variants.
# ----------------------------------------------------------------------
_BINARY = {
    "broadcast_add": jnp.add,
    "broadcast_sub": jnp.subtract,
    "broadcast_mul": jnp.multiply,
    "broadcast_div": jnp.divide,
    "broadcast_mod": jnp.mod,
    "broadcast_power": jnp.power,
    "broadcast_maximum": jnp.maximum,
    "broadcast_minimum": jnp.minimum,
    "broadcast_hypot": jnp.hypot,
    "arctan2": jnp.arctan2,
}
_BIN_ALIASES = {
    "broadcast_add": ("elemwise_add", "_plus", "_add"),
    "broadcast_sub": ("elemwise_sub", "_minus", "_sub"),
    "broadcast_mul": ("elemwise_mul", "_mul"),
    "broadcast_div": ("elemwise_div", "_div"),
    "broadcast_power": ("_power",),
    "broadcast_mod": ("_mod",),
}
for _name, _fn in _BINARY.items():
    register_op(_name, aliases=_BIN_ALIASES.get(_name, ()))(_fn)

_CMP = {
    "broadcast_equal": jnp.equal,
    "broadcast_not_equal": jnp.not_equal,
    "broadcast_greater": jnp.greater,
    "broadcast_greater_equal": jnp.greater_equal,
    "broadcast_lesser": jnp.less,
    "broadcast_lesser_equal": jnp.less_equal,
    "broadcast_logical_and": lambda a, b: jnp.logical_and(a, b),
    "broadcast_logical_or": lambda a, b: jnp.logical_or(a, b),
    "broadcast_logical_xor": lambda a, b: jnp.logical_xor(a, b),
}


def _cmp_wrap(fn):
    # MXNet comparison ops return the input dtype (1.0/0.0), not bool
    def impl(lhs, rhs):
        dt = lhs.dtype if hasattr(lhs, "dtype") else jnp.float32
        return fn(lhs, rhs).astype(dt)
    return impl


for _name, _fn in _CMP.items():
    register_op(_name, differentiable=False)(_cmp_wrap(_fn))


# scalar variants (mshadow_op scalar kernels; _plus_scalar etc.)
def _scalar_op(fn, swap_ok=True):
    def impl(x, scalar=0.0, reverse=False):
        a, b = (scalar, x) if reverse else (x, scalar)
        out = fn(a, b)
        dt = x.dtype
        if out.dtype != dt and jnp.issubdtype(dt, jnp.floating):
            out = out.astype(dt)
        return out
    return impl


_SCALAR = {
    "broadcast_add_scalar": (jnp.add, ("_plus_scalar",)),
    "broadcast_sub_scalar": (jnp.subtract, ("_minus_scalar",)),
    "broadcast_mul_scalar": (jnp.multiply, ("_mul_scalar",)),
    "broadcast_div_scalar": (jnp.divide, ("_div_scalar",)),
    "broadcast_mod_scalar": (jnp.mod, ("_mod_scalar",)),
    "broadcast_power_scalar": (jnp.power, ("_power_scalar",)),
    "broadcast_maximum_scalar": (jnp.maximum, ("_maximum_scalar",)),
    "broadcast_minimum_scalar": (jnp.minimum, ("_minimum_scalar",)),
}
for _name, (_fn, _al) in _SCALAR.items():
    register_op(_name, aliases=_al)(_scalar_op(_fn))


# reversed-scalar ops (MXNet contract: scalar ∘ tensor)
def _rev_scalar_op(fn):
    def impl(x, scalar=0.0, reverse=True):
        out = fn(scalar, x)
        if out.dtype != x.dtype and jnp.issubdtype(x.dtype, jnp.floating):
            out = out.astype(x.dtype)
        return out
    return impl


register_op("_rminus_scalar")(_rev_scalar_op(jnp.subtract))
register_op("_rdiv_scalar")(_rev_scalar_op(jnp.divide))
register_op("_rpower_scalar")(_rev_scalar_op(jnp.power))
register_op("_rmod_scalar")(_rev_scalar_op(jnp.mod))

_SCALAR_CMP = {
    "broadcast_equal_scalar": jnp.equal,
    "broadcast_not_equal_scalar": jnp.not_equal,
    "broadcast_greater_scalar": jnp.greater,
    "broadcast_greater_equal_scalar": jnp.greater_equal,
    "broadcast_lesser_scalar": jnp.less,
    "broadcast_lesser_equal_scalar": jnp.less_equal,
}
for _name, _fn in _SCALAR_CMP.items():
    def _mk(fn):
        def impl(x, scalar=0.0, reverse=False):
            a, b = (scalar, x) if reverse else (x, scalar)
            return fn(a, b).astype(x.dtype)
        return impl
    register_op(_name, differentiable=False)(_mk(_fn))


@register_op("add_n", aliases=("ElementWiseSum", "_sum"))
def add_n(*args):
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


@register_op("where")
def where(condition, x, y):
    return jnp.where(condition.astype(bool), x, y)


@register_op("maximum")
def maximum(lhs, rhs):
    return jnp.maximum(lhs, rhs)


@register_op("minimum")
def minimum(lhs, rhs):
    return jnp.minimum(lhs, rhs)


# ----------------------------------------------------------------------
# reductions (broadcast_reduce_op_value.cc)
# ----------------------------------------------------------------------
def _norm_axis(axis):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(axis)
    return int(axis)


def _reduce(fn):
    def impl(x, axis=None, keepdims=False, exclude=False):
        ax = _norm_axis(axis)
        if exclude and ax is not None:
            all_ax = set(range(x.ndim))
            keep = {a % x.ndim for a in (ax if isinstance(ax, tuple) else (ax,))}
            ax = tuple(sorted(all_ax - keep))
        return fn(x, axis=ax, keepdims=bool(keepdims))
    return impl


register_op("sum", aliases=("sum_axis",))(_reduce(jnp.sum))
register_op("nansum")(_reduce(jnp.nansum))
register_op("mean")(_reduce(jnp.mean))
register_op("prod")(_reduce(jnp.prod))
register_op("nanprod")(_reduce(jnp.nanprod))
register_op("max", aliases=("max_axis",))(_reduce(jnp.max))
register_op("min", aliases=("min_axis",))(_reduce(jnp.min))


@register_op("norm")
def norm(x, ord=2, axis=None, keepdims=False):
    ax = _norm_axis(axis)
    if ord == 1:
        return jnp.sum(jnp.abs(x), axis=ax, keepdims=bool(keepdims))
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=bool(keepdims)))


@register_op("L2Normalization")
def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        ax = tuple(range(1, x.ndim))
    elif mode == "channel":
        ax = 1
    else:  # spatial
        ax = tuple(range(2, x.ndim))
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=True) + eps)
    return x / n


def _argreduce(fn):
    def impl(x, axis=None, keepdims=False):
        ax = axis
        if ax is None:
            out = fn(x.reshape(-1), axis=0)
            return out.astype(jnp.float32)
        out = fn(x, axis=int(ax))
        if keepdims:
            out = jnp.expand_dims(out, int(ax))
        return out.astype(jnp.float32)
    return impl


register_op("argmax", differentiable=False)(_argreduce(jnp.argmax))
register_op("argmin", differentiable=False)(_argreduce(jnp.argmin))


@register_op("argmax_channel", differentiable=False)
def argmax_channel(x):
    return jnp.argmax(x, axis=1).astype(jnp.float32)


# ----------------------------------------------------------------------
# shape ops (matrix_op.cc)
# ----------------------------------------------------------------------
@register_op("reshape", aliases=("Reshape",))
def reshape(x, shape=None, reverse=False):
    """MXNet reshape with special codes 0 (keep), -1 (infer), -2 (copy
    rest), -3 (merge next two), -4 (split, takes two following values)."""
    shape = tuple(shape)
    if not any(s in (0, -2, -3, -4) for s in shape):
        return jnp.reshape(x, shape)
    src = list(x.shape)
    if reverse:
        src = src[::-1]
        shape = tuple(reversed(shape))
    out = []
    i = 0  # index into src
    j = 0
    shape = list(shape)
    while j < len(shape):
        s = shape[j]
        if s == 0:
            out.append(src[i]); i += 1
        elif s == -1:
            out.append(-1); i += 1
        elif s == -2:
            out.extend(src[i:]); i = len(src)
        elif s == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif s == -4:
            d1, d2 = shape[j + 1], shape[j + 2]
            if d1 == -1:
                d1 = src[i] // d2
            if d2 == -1:
                d2 = src[i] // d1
            out.extend([d1, d2]); i += 1; j += 2
        else:
            out.append(s); i += 1
        j += 1
    if reverse:
        out = out[::-1]
    return jnp.reshape(x, tuple(out))


@register_op("reshape_like")
def reshape_like(x, other, lhs_begin=None, lhs_end=None, rhs_begin=None,
                 rhs_end=None):
    """``x``'s axes [lhs_begin, lhs_end) take the shape of ``other``'s
    [rhs_begin, rhs_end); None is an end of the shape, negatives count
    from the back (matrix_op.cc ReshapeLikeParam)."""
    lhs = range(x.ndim)[lhs_begin:lhs_end]
    return jnp.reshape(x, x.shape[:lhs.start] + other.shape[rhs_begin:rhs_end]
                       + x.shape[lhs.stop:])


@register_op("shape_array", differentiable=False)
def shape_array(x):
    return jnp.asarray(x.shape, dtype=jnp.int64)


@register_op("size_array", differentiable=False)
def size_array(x):
    return jnp.asarray([x.size], dtype=jnp.int64)


@register_op("transpose")
def transpose(x, axes=None):
    return jnp.transpose(x, axes)


@register_op("swapaxes", aliases=("SwapAxis",))
def swapaxes(x, dim1=0, dim2=1):
    return jnp.swapaxes(x, int(dim1), int(dim2))


@register_op("Flatten", aliases=("flatten",))
def flatten(x):
    return jnp.reshape(x, (x.shape[0], -1))


@register_op("expand_dims")
def expand_dims(x, axis=0):
    return jnp.expand_dims(x, int(axis))


@register_op("squeeze")
def squeeze(x, axis=None):
    return jnp.squeeze(x, axis if axis is None else tuple(np.atleast_1d(axis)))


@register_op("broadcast_to")
def broadcast_to(x, shape=None):
    shape = tuple(int(t) if t != 0 else s for t, s in zip(shape, x.shape))
    return jnp.broadcast_to(x, shape)


@register_op("broadcast_like")
def broadcast_like(x, other):
    return jnp.broadcast_to(x, other.shape)


@register_op("broadcast_axis", aliases=("broadcast_axes",))
def broadcast_axis(x, axis=(), size=()):
    axis = tuple(np.atleast_1d(axis))
    size = tuple(np.atleast_1d(size))
    target = list(x.shape)
    for a, s in zip(axis, size):
        target[a] = int(s)
    return jnp.broadcast_to(x, tuple(target))


@register_op("tile")
def tile(x, reps=()):
    return jnp.tile(x, tuple(reps))


@register_op("repeat")
def repeat(x, repeats=1, axis=None):
    return jnp.repeat(x, int(repeats), axis=None if axis is None else int(axis))


@register_op("flip", aliases=("reverse",))
def flip(x, axis=0):
    return jnp.flip(x, tuple(np.atleast_1d(axis)))


@register_op("pad", aliases=("Pad",))
def pad(x, mode="constant", pad_width=(), constant_value=0.0):
    pw = tuple(pad_width)
    pairs = [(pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2)]
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    if jmode == "constant":
        return jnp.pad(x, pairs, mode="constant", constant_values=constant_value)
    return jnp.pad(x, pairs, mode=jmode)


@register_op("depth_to_space")
def depth_to_space(x, block_size=1):
    b = int(block_size)
    n, c, h, w = x.shape
    y = x.reshape(n, b, b, c // (b * b), h, w)
    y = y.transpose(0, 3, 4, 1, 5, 2)
    return y.reshape(n, c // (b * b), h * b, w * b)


@register_op("space_to_depth")
def space_to_depth(x, block_size=1):
    b = int(block_size)
    n, c, h, w = x.shape
    y = x.reshape(n, c, h // b, b, w // b, b)
    y = y.transpose(0, 3, 5, 1, 2, 4)
    return y.reshape(n, c * b * b, h // b, w // b)


# ----------------------------------------------------------------------
# slicing & indexing (matrix_op.cc / indexing_op.cc)
# ----------------------------------------------------------------------
@register_op("_slice_get", wrap=False)
def _slice_get(x, key=None):
    return x[key]


@register_op("slice", aliases=("crop",))
def slice_op(x, begin=(), end=(), step=None):
    idx = []
    step = step or [None] * len(begin)
    for b, e, s in zip(begin, end, step):
        idx.append(builtins_slice(b, e, s))
    return x[tuple(idx)]


def builtins_slice(b, e, s):
    return slice(b, e, s)


@register_op("slice_axis")
def slice_axis(x, axis=0, begin=0, end=None):
    axis = int(axis) % x.ndim
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(begin, end)
    return x[tuple(idx)]


@register_op("slice_like")
def slice_like(x, shape_like, axes=()):
    axes = tuple(np.atleast_1d(axes)) if axes != () and axes is not None else tuple(range(x.ndim))
    idx = [slice(None)] * x.ndim
    for a in axes:
        idx[a] = slice(0, shape_like.shape[a])
    return x[tuple(idx)]


@register_op("take")
def take(x, indices, axis=0, mode="clip"):
    idx = indices.astype(jnp.int32)
    jmode = "clip" if mode == "clip" else "wrap"
    return jnp.take(x, idx, axis=int(axis), mode=jmode)


@register_op("batch_take")
def batch_take(x, indices):
    idx = indices.astype(jnp.int32).reshape(-1)
    return x[jnp.arange(x.shape[0]), idx]


@register_op("pick")
def pick(x, index, axis=-1, keepdims=False, mode="clip"):
    ax = int(axis) % x.ndim
    idx = jnp.clip(index.astype(jnp.int32), 0, x.shape[ax] - 1)
    picked = jnp.take_along_axis(x, jnp.expand_dims(idx, ax), axis=ax)
    if not keepdims:
        picked = jnp.squeeze(picked, ax)
    return picked


@register_op("gather_nd")
def gather_nd(data, indices):
    idx = tuple(indices.astype(jnp.int32))
    return data[idx]


@register_op("scatter_nd", wrap=False)
def scatter_nd(data, indices, shape=None):
    idx = tuple(indices.astype(jnp.int32))
    out = jnp.zeros(tuple(shape), data.dtype)
    return out.at[idx].add(data)


@register_op("one_hot", differentiable=False)
def one_hot(indices, depth=1, on_value=1.0, off_value=0.0, dtype="float32"):
    oh = jax.nn.one_hot(indices.astype(jnp.int32), int(depth), dtype=dtype_np(dtype))
    return oh * (on_value - off_value) + off_value


@register_op("SequenceMask")
def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data + 0
    ax = int(axis)
    T = data.shape[ax]
    steps = jnp.arange(T)
    shape = [1] * data.ndim
    shape[ax] = T
    steps = steps.reshape(shape)
    batch_axis = 1 if ax == 0 else 0
    lshape = [1] * data.ndim
    lshape[batch_axis] = data.shape[batch_axis]
    lens = sequence_length.reshape(lshape)
    return jnp.where(steps < lens, data, jnp.asarray(value, data.dtype))


@register_op("SequenceLast")
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    ax = int(axis)
    if not use_sequence_length or sequence_length is None:
        idx = [slice(None)] * data.ndim
        idx[ax] = -1
        return data[tuple(idx)]
    last = sequence_length.astype(jnp.int32) - 1  # shape (batch,)
    batch_axis = 1 if ax == 0 else 0
    shape = [1] * data.ndim
    shape[batch_axis] = data.shape[batch_axis]
    idx = jnp.broadcast_to(
        last.reshape(shape),
        tuple(1 if i == ax else data.shape[i] for i in range(data.ndim)))
    return jnp.take_along_axis(data, idx, axis=ax).squeeze(ax)


@register_op("SequenceReverse")
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, int(axis))
    T = data.shape[0]
    steps = jnp.arange(T)[:, None]
    lens = sequence_length.astype(jnp.int32)[None, :]
    rev_idx = jnp.where(steps < lens, lens - 1 - steps, steps)
    return jnp.take_along_axis(
        data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


# ----------------------------------------------------------------------
# concat / stack / split
# ----------------------------------------------------------------------
@register_op("concat", aliases=("Concat",))
def concat(*args, dim=1, num_args=None):
    return jnp.concatenate(args, axis=int(dim))


@register_op("stack")
def stack(*args, axis=0, num_args=None):
    return jnp.stack(args, axis=int(axis))


@register_op("split", aliases=("SliceChannel",), wrap=False,
             dynamic_arity=True)
def split(x, num_outputs=1, axis=1, squeeze_axis=False):
    parts = jnp.split(x, int(num_outputs), axis=int(axis))
    if squeeze_axis:
        parts = [jnp.squeeze(p, int(axis)) for p in parts]
    return tuple(parts)


@register_op("split_v2", wrap=False)
def split_v2(x, indices_or_sections=1, axis=0, squeeze_axis=False):
    if isinstance(indices_or_sections, int):
        parts = jnp.split(x, indices_or_sections, axis=int(axis))
    else:
        parts = jnp.split(x, list(indices_or_sections), axis=int(axis))
    if squeeze_axis:
        parts = [jnp.squeeze(p, int(axis)) for p in parts]
    return tuple(parts)


# ----------------------------------------------------------------------
# dot / batch_dot / matmul (dot-inl.h — MXU territory)
# ----------------------------------------------------------------------
@register_op("dot")
def dot(lhs, rhs, transpose_a=False, transpose_b=False, forward_stype=None):
    a = lhs.T if transpose_a and lhs.ndim == 2 else (jnp.moveaxis(lhs, 0, -1) if transpose_a else lhs)
    b = rhs.T if transpose_b and rhs.ndim == 2 else (jnp.moveaxis(rhs, -1, 0) if transpose_b else rhs)
    if a.ndim == 1 and b.ndim == 1:
        return jnp.dot(a, b)
    # MXNet dot: contract last axis of a with first axis of b
    return jnp.tensordot(a, b, axes=([a.ndim - 1], [0]))


@register_op("batch_dot")
def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False, forward_stype=None):
    a = jnp.swapaxes(lhs, -1, -2) if transpose_a else lhs
    b = jnp.swapaxes(rhs, -1, -2) if transpose_b else rhs
    return jnp.matmul(a, b)


@register_op("matmul", aliases=("linalg_gemm2_nn",))
def matmul(a, b):
    return jnp.matmul(a, b)


@register_op("khatri_rao")
def khatri_rao(*args):
    out = args[0]
    for m in args[1:]:
        out = jnp.einsum("i...,j...->ij...", out, m).reshape(-1, out.shape[-1])
    return out


# ----------------------------------------------------------------------
# ordering (ordering_op.cc)
# ----------------------------------------------------------------------
@register_op("sort")
def sort(x, axis=-1, is_ascend=True):
    out = jnp.sort(x, axis=None if axis is None else int(axis))
    if not is_ascend:
        out = jnp.flip(out, axis=-1 if axis is None else int(axis))
    return out


@register_op("argsort", differentiable=False)
def argsort(x, axis=-1, is_ascend=True, dtype="float32"):
    key = x if is_ascend else -x
    out = jnp.argsort(key, axis=None if axis is None else int(axis))
    return out.astype(dtype_np(dtype))


@register_op("topk", differentiable=False, wrap=False)
def topk(x, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    ax = x.ndim - 1 if axis is None else int(axis) % x.ndim
    xs = jnp.moveaxis(x, ax, -1)
    vals, idx = jax.lax.top_k(xs if not is_ascend else -xs, int(k))
    if is_ascend:
        vals = -vals
    vals = jnp.moveaxis(vals, -1, ax)
    idx = jnp.moveaxis(idx, -1, ax)
    if ret_typ == "indices":
        return idx.astype(dtype_np(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "mask":
        oh = jnp.sum(jax.nn.one_hot(jnp.moveaxis(idx, ax, -1), x.shape[ax], dtype=x.dtype), axis=-2)
        return jnp.moveaxis(oh, -1, ax)
    return (vals, idx.astype(dtype_np(dtype)))  # 'both'


# ----------------------------------------------------------------------
# init-like ops
# ----------------------------------------------------------------------
@register_op("zeros_like")
def zeros_like(x):
    return jnp.zeros_like(x)


@register_op("ones_like")
def ones_like(x):
    return jnp.ones_like(x)


@register_op("_full_like", wrap=False)
def full_like(x, value=0.0):
    return jnp.full_like(x, value)


@register_op("_arange_like", aliases=("arange_like",), differentiable=False)
def arange_like(x, start=0.0, step=1.0, repeat=1, axis=None):
    if axis is None:
        n = x.size
    else:
        n = x.shape[int(axis)]
    return jnp.arange(start, start + step * n, step, dtype=x.dtype)


# ----------------------------------------------------------------------
# linalg (la_op.cc subset)
# ----------------------------------------------------------------------
@register_op("linalg_gemm")
def linalg_gemm(A, B, C, transpose_a=False, transpose_b=False, alpha=1.0, beta=1.0, axis=-2):
    a = jnp.swapaxes(A, -1, -2) if transpose_a else A
    b = jnp.swapaxes(B, -1, -2) if transpose_b else B
    return alpha * jnp.matmul(a, b) + beta * C


@register_op("linalg_gemm2")
def linalg_gemm2(A, B, transpose_a=False, transpose_b=False, alpha=1.0, axis=-2):
    a = jnp.swapaxes(A, -1, -2) if transpose_a else A
    b = jnp.swapaxes(B, -1, -2) if transpose_b else B
    return alpha * jnp.matmul(a, b)


@register_op("linalg_potrf")
def linalg_potrf(A):
    return jnp.linalg.cholesky(A)


@register_op("linalg_trsm")
def linalg_trsm(A, B, transpose=False, rightside=False, lower=True, alpha=1.0):
    a = jnp.swapaxes(A, -1, -2) if transpose else A
    if rightside:
        x = jnp.swapaxes(jax.scipy.linalg.solve_triangular(
            jnp.swapaxes(a, -1, -2), jnp.swapaxes(alpha * B, -1, -2),
            lower=not lower if transpose else lower), -1, -2)
    else:
        x = jax.scipy.linalg.solve_triangular(
            a, alpha * B, lower=not lower if transpose else lower)
    return x


@register_op("linalg_sumlogdiag")
def linalg_sumlogdiag(A):
    return jnp.sum(jnp.log(jnp.diagonal(A, axis1=-2, axis2=-1)), axis=-1)


@register_op("linalg_extractdiag")
def linalg_extractdiag(A, offset=0):
    return jnp.diagonal(A, offset=int(offset), axis1=-2, axis2=-1)


@register_op("linalg_syrk")
def linalg_syrk(A, transpose=False, alpha=1.0):
    a = jnp.swapaxes(A, -1, -2) if transpose else A
    return alpha * jnp.matmul(a, jnp.swapaxes(a, -1, -2))


@register_op("linalg_potri")
def linalg_potri(A):
    """Inverse of B = A A^T from its Cholesky factor A (la_op.cc potri):
    (A A^T)^{-1} = A^{-T} A^{-1}, via two triangular solves — no
    general inverse materializes."""
    eye = jnp.broadcast_to(jnp.eye(A.shape[-1], dtype=A.dtype), A.shape)
    ainv = jax.scipy.linalg.solve_triangular(A, eye, lower=True)
    return jnp.matmul(jnp.swapaxes(ainv, -1, -2), ainv)


@register_op("linalg_trmm")
def linalg_trmm(A, B, transpose=False, rightside=False, lower=True,
                alpha=1.0):
    """Triangular matrix multiply (la_op.cc trmm): B <- alpha op(tri(A))
    B, or B op(tri(A)) when rightside."""
    tri = jnp.tril(A) if lower else jnp.triu(A)
    if transpose:
        tri = jnp.swapaxes(tri, -1, -2)
    out = jnp.matmul(B, tri) if rightside else jnp.matmul(tri, B)
    return alpha * out


@register_op("linalg_makediag")
def linalg_makediag(A, offset=0):
    """(..., n) vector(s) -> (..., n+|k|, n+|k|) diagonal matrices."""
    offset = int(offset)
    n = A.shape[-1] + abs(offset)
    rows, cols = np.nonzero(np.eye(n, k=offset, dtype=bool))
    out = jnp.zeros(A.shape[:-1] + (n, n), A.dtype)
    return out.at[..., rows, cols].set(A)


def _tri_count(n, offset, lower):
    """Entries in the (lower, k=offset) / (upper, k=offset) triangle of
    an (n, n) matrix — closed form, no index materialization."""
    k = offset if lower else -offset  # upper(k) == lower(-k) transposed
    # lower triangle with diagonal shift k: rows i get
    # clip(i + k + 1, 0, n) entries
    c = np.clip(np.arange(n) + k + 1, 0, n)
    return int(c.sum())


def _trian_n(m, offset, lower):
    """Matrix size n whose triangle has m entries (closed-form count,
    linear scan over n without building index arrays)."""
    for n in range(1, 65536):
        cnt = _tri_count(n, offset, lower)
        if cnt == m:
            return n
        if cnt > m:
            break
    raise ValueError(f"no matrix size has a {m}-entry triangle "
                     f"(offset={offset}, lower={lower})")


@register_op("linalg_maketrian")
def linalg_maketrian(A, offset=0, lower=True):
    """Packed (..., m) vector -> (..., n, n) triangular matrix, row-major
    packing (la_op.cc maketrian)."""
    offset, lower = int(offset), bool(lower)
    n = _trian_n(A.shape[-1], offset, lower)
    rows, cols = (np.tril_indices(n, k=offset) if lower
                  else np.triu_indices(n, k=offset))
    out = jnp.zeros(A.shape[:-1] + (n, n), A.dtype)
    return out.at[..., rows, cols].set(A)


@register_op("linalg_extracttrian")
def linalg_extracttrian(A, offset=0, lower=True):
    """(..., n, n) -> packed (..., m) triangle, row-major (inverse of
    maketrian)."""
    offset, lower = int(offset), bool(lower)
    n = A.shape[-1]
    rows, cols = (np.tril_indices(n, k=offset) if lower
                  else np.triu_indices(n, k=offset))
    return A[..., rows, cols]


# ----------------------------------------------------------------------
# im2col / col2im (src/operator/nn/im2col.h surface ops)
# ----------------------------------------------------------------------
def _conv_geom(kernel, stride, dilate, pad):
    k = tuple(int(v) for v in kernel)
    nd_ = len(k)
    as_t = lambda v, d: tuple(int(x) for x in v) if v else (d,) * nd_
    return k, as_t(stride, 1), as_t(dilate, 1), as_t(pad, 0)


@register_op("im2col")
def im2col(data, kernel=None, stride=None, dilate=None, pad=None):
    """(N, C, H, W) -> (N, C*kh*kw, out_h*out_w): unfold sliding
    windows, channel-major then kernel-position row-major — the
    reference's im2col buffer layout (src/operator/nn/im2col.h), so a
    conv is im2col + one gemm."""
    (kh, kw), (sh, sw), (dh, dw), (ph, pw) = _conv_geom(
        kernel, stride, dilate, pad)
    x = jnp.pad(data, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    n, c, hp, wp = x.shape
    oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
    ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            patch = jax.lax.slice(
                x, (0, 0, i * dh, j * dw),
                (n, c, i * dh + (oh - 1) * sh + 1, j * dw + (ow - 1) * sw + 1),
                (1, 1, sh, sw))
            cols.append(patch)  # (n, c, oh, ow)
    out = jnp.stack(cols, axis=2)  # (n, c, kh*kw, oh, ow)
    return out.reshape(n, c * kh * kw, oh * ow)


@register_op("col2im")
def col2im(data, output_size=None, kernel=None, stride=None, dilate=None,
           pad=None):
    """(N, C*kh*kw, L) -> (N, C, H, W): scatter-add the unfolded
    windows back (im2col's adjoint, src/operator/nn/im2col.h col2im)."""
    (kh, kw), (sh, sw), (dh, dw), (ph, pw) = _conv_geom(
        kernel, stride, dilate, pad)
    H, W = (int(v) for v in output_size)
    n, ckk, L = data.shape
    c = ckk // (kh * kw)
    hp, wp = H + 2 * ph, W + 2 * pw
    oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
    ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
    cols = data.reshape(n, c, kh * kw, oh, ow)
    out = jnp.zeros((n, c, hp, wp), data.dtype)
    for i in range(kh):
        for j in range(kw):
            patch = cols[:, :, i * kw + j]  # (n, c, oh, ow)
            out = out.at[:, :,
                         i * dh:i * dh + (oh - 1) * sh + 1:sh,
                         j * dw:j * dw + (ow - 1) * sw + 1:sw].add(patch)
    return out[:, :, ph:ph + H, pw:pw + W]


# ----------------------------------------------------------------------
# misc
# ----------------------------------------------------------------------
@register_op("diag")
def diag(x, k=0, axis1=0, axis2=1):
    if x.ndim == 1:
        return jnp.diag(x, k=int(k))
    return jnp.diagonal(x, offset=int(k), axis1=int(axis1), axis2=int(axis2))


@register_op("smooth_l1")
def smooth_l1(x, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * jnp.square(x),
                     jnp.abs(x) - 0.5 / s2)


@register_op("hard_sigmoid")
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


@register_op("log_sigmoid")
def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


@register_op("mish")
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@register_op("softplus")
def softplus(x):
    return jax.nn.softplus(x)


@register_op("cumsum")
def cumsum(x, axis=None, dtype=None):
    out = jnp.cumsum(x if dtype is None else x.astype(dtype_np(dtype)),
                     axis=None if axis is None else int(axis))
    return out


@register_op("digamma")
def digamma(x):
    """psi(x) (reference mshadow_op.h gamma family — backward of
    gammaln, exposed as an op as in upstream unary math)."""
    return jax.scipy.special.digamma(x)


@register_op("unravel_index", aliases=["_unravel_index"])
def unravel_index(x, shape=()):
    """Flat index -> multi-index coordinates, stacked on a leading axis
    (reference src/operator/tensor/ravel.cc UnravelIndex)."""
    dims = tuple(int(s) for s in shape)
    coords = jnp.unravel_index(x.astype(jnp.int64), dims)
    # reference infers output dtype = input dtype (ravel.cc)
    return jnp.stack(coords, axis=0).astype(x.dtype)


@register_op("ravel_multi_index", aliases=["_ravel_multi_index"])
def ravel_multi_index(x, shape=()):
    """Multi-index (leading axis = coordinates) -> flat index
    (reference src/operator/tensor/ravel.cc RavelMultiIndex). Plain
    stride arithmetic, NO range clipping — out-of-range coordinates
    produce out-of-range flat indices exactly as the reference does.
    True 64-bit arithmetic relies on the package-wide jax_enable_x64
    (set at import; without it jnp.int64 silently degrades to int32)."""
    dims = tuple(int(s) for s in shape)
    stride = 1
    flat = jnp.zeros(x.shape[1:], jnp.int64)
    for i in range(len(dims) - 1, -1, -1):
        flat = flat + x[i].astype(jnp.int64) * stride
        stride *= dims[i]
    return flat.astype(x.dtype)  # reference: output dtype = input dtype
