"""NumPy-semantics internal operators (the ``_npi_*`` family).

The reference's deep-NumPy frontend (python/mxnet/numpy/multiarray.py,
v>=1.6) is backed by internal registry ops named ``_npi_*``
(src/operator/numpy/np_*.cc). Here the same contract holds: every
``mx.np.*`` function that is not expressible through an existing
classic op dispatches one of these registered ops, so the autograd
tape, AMP cast hook, profiler, symbolic tracing and the recorded
op-coverage gate all see np-mode work exactly like classic-mode work.

Only numpy-specific semantics get new entries; where a classic op is
already the right kernel (tanh, sum, clip, ...) ``mx.np`` reuses it —
the registry is the single source of compute either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ndarray.register import register_op

__all__ = []  # everything here is reached through the registry


# ---------------------------------------------------------------------------
# elementwise binaries numpy adds over the classic broadcast_* family
# ---------------------------------------------------------------------------
@register_op("_npi_floor_divide")
def _npi_floor_divide(a, b):
    return jnp.floor_divide(a, b)


@register_op("_npi_logaddexp")
def _npi_logaddexp(a, b):
    return jnp.logaddexp(a, b)


@register_op("_npi_logaddexp2")
def _npi_logaddexp2(a, b):
    return jnp.logaddexp2(a, b)


@register_op("_npi_copysign")
def _npi_copysign(a, b):
    return jnp.copysign(a, b)


@register_op("_npi_fmax")
def _npi_fmax(a, b):
    return jnp.fmax(a, b)


@register_op("_npi_fmin")
def _npi_fmin(a, b):
    return jnp.fmin(a, b)


@register_op("_npi_fmod")
def _npi_fmod(a, b):
    return jnp.fmod(a, b)


@register_op("_npi_bitwise_and", differentiable=False)
def _npi_bitwise_and(a, b):
    return jnp.bitwise_and(a, b)


@register_op("_npi_bitwise_or", differentiable=False)
def _npi_bitwise_or(a, b):
    return jnp.bitwise_or(a, b)


@register_op("_npi_bitwise_xor", differentiable=False)
def _npi_bitwise_xor(a, b):
    return jnp.bitwise_xor(a, b)


@register_op("_npi_invert", differentiable=False)
def _npi_invert(a):
    return jnp.invert(a)


@register_op("_npi_left_shift", differentiable=False)
def _npi_left_shift(a, b):
    return jnp.left_shift(a, b)


@register_op("_npi_right_shift", differentiable=False)
def _npi_right_shift(a, b):
    return jnp.right_shift(a, b)


@register_op("_npi_gcd", differentiable=False)
def _npi_gcd(a, b):
    return jnp.gcd(a, b)


@register_op("_npi_lcm", differentiable=False)
def _npi_lcm(a, b):
    return jnp.lcm(a, b)


@register_op("_npi_exp2")
def _npi_exp2(a):
    return jnp.exp2(a)


@register_op("_npi_nan_to_num")
def _npi_nan_to_num(a, nan=0.0, posinf=None, neginf=None):
    return jnp.nan_to_num(a, nan=nan, posinf=posinf, neginf=neginf)


@register_op("_npi_isclose", differentiable=False)
def _npi_isclose(a, b, rtol=1e-05, atol=1e-08, equal_nan=False):
    return jnp.isclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)


@register_op("_npi_signbit", differentiable=False)
def _npi_signbit(a):
    return jnp.signbit(a)


@register_op("_npi_heaviside")
def _npi_heaviside(a, b):
    return jnp.heaviside(a, b)


@register_op("_npi_ldexp")
def _npi_ldexp(a, b):
    return jnp.ldexp(a, b)


# ---------------------------------------------------------------------------
# reductions / statistics
# ---------------------------------------------------------------------------
@register_op("_npi_all", differentiable=False)
def _npi_all(a, axis=None, keepdims=False):
    return jnp.all(a, axis=axis, keepdims=keepdims)


@register_op("_npi_any", differentiable=False)
def _npi_any(a, axis=None, keepdims=False):
    return jnp.any(a, axis=axis, keepdims=keepdims)


@register_op("_npi_std")
def _npi_std(a, axis=None, ddof=0, keepdims=False):
    return jnp.std(a, axis=axis, ddof=ddof, keepdims=keepdims)


@register_op("_npi_var")
def _npi_var(a, axis=None, ddof=0, keepdims=False):
    return jnp.var(a, axis=axis, ddof=ddof, keepdims=keepdims)


@register_op("_npi_median")
def _npi_median(a, axis=None, keepdims=False):
    return jnp.median(a, axis=axis, keepdims=keepdims)


@register_op("_npi_quantile")
def _npi_quantile(a, q, axis=None, keepdims=False, interpolation="linear"):
    return jnp.quantile(a, q, axis=axis, keepdims=keepdims,
                        method=interpolation)


@register_op("_npi_percentile")
def _npi_percentile(a, q, axis=None, keepdims=False, interpolation="linear"):
    return jnp.percentile(a, q, axis=axis, keepdims=keepdims,
                          method=interpolation)


@register_op("_npi_average")
def _npi_average(a, weights=None, axis=None):
    return jnp.average(a, axis=axis, weights=weights)


@register_op("_npi_cumprod")
def _npi_cumprod(a, axis=None, dtype=None):
    return jnp.cumprod(a, axis=axis, dtype=dtype)


@register_op("_npi_count_nonzero", differentiable=False)
def _npi_count_nonzero(a, axis=None, keepdims=False):
    return jnp.count_nonzero(a, axis=axis, keepdims=keepdims)


@register_op("_npi_diff")
def _npi_diff(a, n=1, axis=-1):
    return jnp.diff(a, n=n, axis=axis)


@register_op("_npi_ptp")
def _npi_ptp(a, axis=None, keepdims=False):
    return jnp.ptp(a, axis=axis, keepdims=keepdims)


@register_op("_npi_bincount", differentiable=False)
def _npi_bincount(x, weights=None, minlength=0):
    # eager dispatch: concrete shapes, so the true length is known
    length = max(int(minlength), int(x.size and int(jnp.max(x)) + 1))
    return jnp.bincount(x, weights=weights, minlength=minlength,
                        length=length)


@register_op("_npi_histogram", differentiable=False)
def _npi_histogram(a, bins=10, range=None):
    return jnp.histogram(a, bins=bins, range=range)


@register_op("_npi_nanmax")
def _npi_nanmax(a, axis=None, keepdims=False):
    return jnp.nanmax(a, axis=axis, keepdims=keepdims)


@register_op("_npi_nanmin")
def _npi_nanmin(a, axis=None, keepdims=False):
    return jnp.nanmin(a, axis=axis, keepdims=keepdims)


@register_op("_npi_nanmean")
def _npi_nanmean(a, axis=None, keepdims=False):
    return jnp.nanmean(a, axis=axis, keepdims=keepdims)


# ---------------------------------------------------------------------------
# shape / rearrangement numpy-isms
# ---------------------------------------------------------------------------
@register_op("_npi_roll")
def _npi_roll(a, shift=1, axis=None):
    return jnp.roll(a, shift, axis=axis)


@register_op("_npi_rot90")
def _npi_rot90(a, k=1, axes=(0, 1)):
    return jnp.rot90(a, k=k, axes=tuple(axes))


@register_op("_npi_moveaxis")
def _npi_moveaxis(a, source=0, destination=0):
    return jnp.moveaxis(a, source, destination)


@register_op("_npi_tril")
def _npi_tril(a, k=0):
    return jnp.tril(a, k=k)


@register_op("_npi_triu")
def _npi_triu(a, k=0):
    return jnp.triu(a, k=k)


@register_op("_npi_trace")
def _npi_trace(a, offset=0, axis1=0, axis2=1):
    return jnp.trace(a, offset=offset, axis1=axis1, axis2=axis2)


@register_op("_npi_diagonal")
def _npi_diagonal(a, offset=0, axis1=0, axis2=1):
    return jnp.diagonal(a, offset=offset, axis1=axis1, axis2=axis2)


@register_op("_npi_diagflat")
def _npi_diagflat(a, k=0):
    return jnp.diagflat(a, k=k)


@register_op("_npi_unique", differentiable=False)
def _npi_unique(a, return_index=False, return_inverse=False,
                return_counts=False):
    return jnp.unique(a, return_index=return_index,
                      return_inverse=return_inverse,
                      return_counts=return_counts)


@register_op("_npi_nonzero", differentiable=False)
def _npi_nonzero(a):
    # MXNet's np.nonzero returns a transposed-index matrix from the
    # internal op; the frontend unstacks it into the numpy tuple form
    return jnp.stack(jnp.nonzero(a), axis=0)


@register_op("_npi_flatnonzero", differentiable=False)
def _npi_flatnonzero(a):
    return jnp.flatnonzero(a)


@register_op("_npi_searchsorted", differentiable=False)
def _npi_searchsorted(a, v, side="left"):
    return jnp.searchsorted(a, v, side=side)


@register_op("_npi_take_along_axis")
def _npi_take_along_axis(a, indices, axis=-1):
    return jnp.take_along_axis(a, indices, axis=axis)


@register_op("_npi_pad")
def _npi_pad(a, pad_width=0, mode="constant", constant_values=0):
    pw = pad_width
    if isinstance(pw, (list, tuple)):
        pw = tuple(tuple(p) if isinstance(p, (list, tuple)) else p for p in pw)
    kw = {"constant_values": constant_values} if mode == "constant" else {}
    return jnp.pad(a, pw, mode=mode, **kw)


@register_op("_npi_append")
def _npi_append(a, b, axis=None):
    return jnp.append(a, b, axis=axis)


@register_op("_npi_interp")
def _npi_interp(x, xp, fp, left=None, right=None):
    return jnp.interp(x, xp, fp, left=left, right=right)


@register_op("_npi_where")
def _npi_where(cond, x, y):
    return jnp.where(cond, x, y)


@register_op("_npi_ediff1d")
def _npi_ediff1d(a):
    return jnp.ediff1d(a)


@register_op("_npi_cross")
def _npi_cross(a, b, axis=-1):
    return jnp.cross(a, b, axis=axis)


@register_op("_npi_kron")
def _npi_kron(a, b):
    return jnp.kron(a, b)


# ---------------------------------------------------------------------------
# products / contractions
# ---------------------------------------------------------------------------
@register_op("_npi_tensordot")
def _npi_tensordot(a, b, axes=2):
    if isinstance(axes, (list, tuple)):
        axes = tuple(tuple(x) if isinstance(x, (list, tuple)) else x
                     for x in axes)
    return jnp.tensordot(a, b, axes=axes)


@register_op("_npi_einsum")
def _npi_einsum(*operands, subscripts="", optimize=True):
    return jnp.einsum(subscripts, *operands, optimize=bool(optimize))


@register_op("_npi_inner")
def _npi_inner(a, b):
    return jnp.inner(a, b)


@register_op("_npi_outer")
def _npi_outer(a, b):
    return jnp.outer(a, b)


@register_op("_npi_vdot")
def _npi_vdot(a, b):
    return jnp.vdot(a, b)


@register_op("_npi_matmul")
def _npi_matmul(a, b):
    return jnp.matmul(a, b)


@register_op("_npi_dot")
def _npi_dot(a, b):
    # numpy dot semantics (2D matmul, 1D inner, scalar mul) — distinct
    # from the classic mx.nd.dot which has transpose_a/b flags
    return jnp.dot(a, b)


# ---------------------------------------------------------------------------
# np.linalg
# ---------------------------------------------------------------------------
def _x64_safe(fn):
    """Scope out x64 for 32-bit inputs of SVD-based decompositions:
    with jax_enable_x64 on (base.py enables it for int64 NDArray
    parity), jnp.linalg's svd/pinv/lstsq emit f64-tainted graphs that
    abort the TPU compiler (TransposeFolding null-buffer check on this
    libtpu). Disabling x64 in-scope restores the pure-f32 graph; 64-bit
    inputs keep x64 so their numerics are untouched."""
    import functools

    @functools.wraps(fn)
    def wrapped(a, *rest, **kw):
        if hasattr(a, "dtype") and a.dtype.itemsize <= 4:
            with jax.enable_x64(False):
                return fn(a, *rest, **kw)
        return fn(a, *rest, **kw)

    return wrapped


@register_op("_npi_svd", num_visible_outputs=3)
@_x64_safe
def _npi_svd(a, full_matrices=False):
    u, s, vh = jnp.linalg.svd(a, full_matrices=full_matrices)
    return u, s, vh


@register_op("_npi_inv")
def _npi_inv(a):
    return jnp.linalg.inv(a)


@register_op("_npi_pinv")
@_x64_safe
def _npi_pinv(a, rcond=1e-15):
    return jnp.linalg.pinv(a, rtol=rcond)


@register_op("_npi_det")
def _npi_det(a):
    return jnp.linalg.det(a)


@register_op("_npi_slogdet", num_visible_outputs=2)
def _npi_slogdet(a):
    sign, logdet = jnp.linalg.slogdet(a)
    return sign, logdet


@register_op("_npi_eigh", num_visible_outputs=2)
def _npi_eigh(a, UPLO="L"):
    w, v = jnp.linalg.eigh(a, UPLO=UPLO)
    return w, v


@register_op("_npi_eigvalsh")
def _npi_eigvalsh(a, UPLO="L"):
    return jnp.linalg.eigvalsh(a, UPLO=UPLO)


@register_op("_npi_qr", num_visible_outputs=2)
def _npi_qr(a, mode="reduced"):
    q, r = jnp.linalg.qr(a, mode=mode)
    return q, r


@register_op("_npi_cholesky")
def _npi_cholesky(a):
    return jnp.linalg.cholesky(a)


@register_op("_npi_solve")
def _npi_solve(a, b):
    return jnp.linalg.solve(a, b)


@register_op("_npi_lstsq", differentiable=False, num_visible_outputs=4)
@_x64_safe
def _npi_lstsq(a, b, rcond=None):
    x, resid, rank, s = jnp.linalg.lstsq(a, b, rcond=rcond)
    return x, resid, rank, s


@register_op("_npi_matrix_power")
def _npi_matrix_power(a, n=1):
    return jnp.linalg.matrix_power(a, n)


@register_op("_npi_multi_dot")
def _npi_multi_dot(*arrays):
    return jnp.linalg.multi_dot(list(arrays))


@register_op("_npi_norm")
def _npi_norm(a, ord=None, axis=None, keepdims=False):
    if isinstance(axis, (list, tuple)):
        axis = tuple(axis)
    return jnp.linalg.norm(a, ord=ord, axis=axis, keepdims=keepdims)


@register_op("_npi_matrix_rank", differentiable=False)
@_x64_safe
def _npi_matrix_rank(a, tol=None):
    return jnp.linalg.matrix_rank(a, rtol=tol)


# ---------------------------------------------------------------------------
# np.random distributions beyond the classic random_* family
# (reference src/operator/numpy/random/np_*_op.cc). Key discipline is
# the shared threefry chain (mxnet_tpu/random.py) — same resource the
# classic sample ops draw from.
# ---------------------------------------------------------------------------
from .. import random as _random_mod  # noqa: E402


def _rkey(k):
    return _random_mod._next_key() if k is None else k


def _rshape(size):
    if size is None:
        return ()
    if isinstance(size, int):
        return (size,)
    return tuple(size)


@register_op("_npi_random_beta", differentiable=False)
def _npi_random_beta(a=1.0, b=1.0, size=None, _rng_key=None):
    return jax.random.beta(_rkey(_rng_key), a, b, _rshape(size))


@register_op("_npi_random_chisquare", differentiable=False)
def _npi_random_chisquare(df=1.0, size=None, _rng_key=None):
    return jax.random.chisquare(_rkey(_rng_key), df, shape=_rshape(size))


@register_op("_npi_random_lognormal", differentiable=False)
def _npi_random_lognormal(mean=0.0, sigma=1.0, size=None, _rng_key=None):
    return jnp.exp(mean + sigma * jax.random.normal(_rkey(_rng_key),
                                                    _rshape(size)))


@register_op("_npi_random_laplace", differentiable=False)
def _npi_random_laplace(loc=0.0, scale=1.0, size=None, _rng_key=None):
    return loc + scale * jax.random.laplace(_rkey(_rng_key), _rshape(size))


@register_op("_npi_random_logistic", differentiable=False)
def _npi_random_logistic(loc=0.0, scale=1.0, size=None, _rng_key=None):
    return loc + scale * jax.random.logistic(_rkey(_rng_key), _rshape(size))


@register_op("_npi_random_gumbel", differentiable=False)
def _npi_random_gumbel(loc=0.0, scale=1.0, size=None, _rng_key=None):
    return loc + scale * jax.random.gumbel(_rkey(_rng_key), _rshape(size))


@register_op("_npi_random_pareto", differentiable=False)
def _npi_random_pareto(a=1.0, size=None, _rng_key=None):
    return jax.random.pareto(_rkey(_rng_key), a, shape=_rshape(size)) - 1.0


@register_op("_npi_random_rayleigh", differentiable=False)
def _npi_random_rayleigh(scale=1.0, size=None, _rng_key=None):
    return jax.random.rayleigh(_rkey(_rng_key), scale, shape=_rshape(size))


@register_op("_npi_random_weibull", differentiable=False)
def _npi_random_weibull(a=1.0, size=None, _rng_key=None):
    u = jax.random.uniform(_rkey(_rng_key), _rshape(size), minval=1e-7,
                           maxval=1.0)
    return (-jnp.log(u)) ** (1.0 / a)


@register_op("_npi_random_power", differentiable=False)
def _npi_random_power(a=1.0, size=None, _rng_key=None):
    u = jax.random.uniform(_rkey(_rng_key), _rshape(size), minval=1e-7,
                           maxval=1.0)
    return u ** (1.0 / a)


@register_op("_npi_random_choice", differentiable=False)
def _npi_random_choice(a, p=None, size=None, replace=True, _rng_key=None):
    # p is the optional SECOND tensor input (invoke passes tensor
    # inputs positionally), so it precedes the keyword params
    return jax.random.choice(_rkey(_rng_key), a, _rshape(size),
                             replace=replace, p=p)


@register_op("_npi_random_permutation", differentiable=False)
def _npi_random_permutation(x, _rng_key=None):
    return jax.random.permutation(_rkey(_rng_key), x)


# ---------------------------------------------------------------------------
# bool-dtype comparisons/logicals (numpy returns bool; the classic
# broadcast_* family returns the input dtype per MXNet convention —
# reference np_elemwise_broadcast_logic_op.cc)
# ---------------------------------------------------------------------------
_NP_CMP = {
    "_npi_equal": jnp.equal,
    "_npi_not_equal": jnp.not_equal,
    "_npi_greater": jnp.greater,
    "_npi_greater_equal": jnp.greater_equal,
    "_npi_less": jnp.less,
    "_npi_less_equal": jnp.less_equal,
    "_npi_logical_and": jnp.logical_and,
    "_npi_logical_or": jnp.logical_or,
    "_npi_logical_xor": jnp.logical_xor,
}
for _name, _fn in _NP_CMP.items():
    register_op(_name, differentiable=False)(_fn)


@register_op("_npi_logical_not", differentiable=False)
def _npi_logical_not(a):
    return jnp.logical_not(a)


@register_op("_npi_broadcast_to")
def _npi_broadcast_to(a, shape=()):
    # numpy broadcast_to prepends axes; the classic broadcast_to op
    # keeps MXNet's same-rank/0-keeps-dim contract
    return jnp.broadcast_to(a, tuple(shape))


@register_op("_npi_argwhere", differentiable=False)
def _npi_argwhere(a):
    return jnp.argwhere(a)


# ----------------------------------------------------------------------
# composed-function ops (round 5): the eager frontend builds these in
# Python; registering jnp-backed single ops gives `mx.sym.np` a static
# graph lowering too (upstream symbol/numpy has backend ops for the
# same reason). Multi-output counts are parameter-inferable, so the
# symbolic layer exposes real output selectors.
# ----------------------------------------------------------------------
@register_op("_npi_vstack")
def _npi_vstack(*arrays):
    return jnp.vstack(arrays)


@register_op("_npi_hstack")
def _npi_hstack(*arrays):
    return jnp.hstack(arrays)


@register_op("_npi_dstack")
def _npi_dstack(*arrays):
    return jnp.dstack(arrays)


@register_op("_npi_column_stack")
def _npi_column_stack(*arrays):
    return jnp.column_stack(arrays)


def _split_count(params):
    ios = params.get("indices_or_sections", 1)
    if isinstance(ios, (list, tuple)):
        return len(ios) + 1
    return int(ios)


@register_op("_npi_split_np", wrap=False, infer_num_outputs=_split_count)
def _npi_split_np(x, indices_or_sections=1, axis=0):
    ios = indices_or_sections
    return tuple(jnp.split(x, ios if isinstance(ios, int) else list(ios),
                           axis=int(axis)))


@register_op("_npi_array_split", wrap=False, infer_num_outputs=_split_count)
def _npi_array_split(x, indices_or_sections=1, axis=0):
    ios = indices_or_sections
    return tuple(jnp.array_split(
        x, ios if isinstance(ios, int) else list(ios), axis=int(axis)))


@register_op("_npi_meshgrid", wrap=False,
             infer_num_outputs=lambda p: int(p.get("num_outputs", 1)))
def _npi_meshgrid(*arrays, indexing="xy", num_outputs=None):
    return tuple(jnp.meshgrid(*arrays, indexing=indexing))


@register_op("_npi_broadcast_arrays", wrap=False,
             infer_num_outputs=lambda p: int(p.get("num_outputs", 1)))
def _npi_broadcast_arrays(*arrays, num_outputs=None):
    return tuple(jnp.broadcast_arrays(*arrays))


@register_op("_npi_atleast_1d")
def _npi_atleast_1d(a):
    return jnp.atleast_1d(a)


@register_op("_npi_atleast_2d")
def _npi_atleast_2d(a):
    return jnp.atleast_2d(a)


@register_op("_npi_atleast_3d")
def _npi_atleast_3d(a):
    return jnp.atleast_3d(a)


@register_op("_npi_around")
def _npi_around(a, decimals=0):
    return jnp.round(a, int(decimals))
