"""Test utilities (python/mxnet/test_utils.py analog).

The reference's testing backbone, preserved because SURVEY §4 calls it
the gate for everything else:

- ``assert_almost_equal`` with per-dtype default tolerances (extended
  with bfloat16 — the TPU-native half type);
- ``check_numeric_gradient`` — central finite differences vs autograd;
- ``check_consistency`` — run the same computation under several
  contexts/dtypes and compare forward/backward. On this backend the
  pair is cpu-f32 vs tpu-f32/bf16 (the cpu↔gpu golden harness of
  tests/python/gpu/test_operator_gpu.py);
- ``default_context``, ``with_seed``/``@with_seed()`` determinism.
"""
from __future__ import annotations

import functools
import logging
import os
import random as pyrandom

import numpy as np

from .base import dtype_name
from .context import Context, cpu, current_context
from .ndarray import NDArray, array
from . import random as mx_random

__all__ = [
    "default_context", "set_default_context", "default_dtype",
    "assert_almost_equal", "almost_equal", "same", "rand_ndarray",
    "rand_shape_nd", "check_numeric_gradient", "check_consistency",
    "with_seed", "simple_forward", "list_gpus", "download",
]

_DEFAULT_CTX = None

# per-dtype (rtol, atol) — reference test_utils tolerance tables + bf16
_TOLS = {
    "float16": (1e-2, 1e-4),
    "bfloat16": (3e-2, 1e-3),
    "float32": (1e-4, 1e-6),
    "float64": (1e-5, 1e-8),
}

# Per-DEVICE tolerance widening (the reference's check_consistency keys
# tolerances on (device, dtype) for the same reason): on TPU, float32
# matmuls execute as bf16 MXU passes and transcendentals are polynomial
# approximations, so f32 results carry ~1e-3 relative error vs CPU.
_TPU_TOLS = {
    "float32": (5e-3, 2e-3),
    "float64": (5e-3, 2e-3),
}


_ON_TPU_CACHE = None


def _on_tpu():
    """LAZY backend probe: jax.default_backend() initializes the XLA
    backend, which must never happen at mxnet_tpu import time
    (jax.distributed.initialize has to come first in dist workers)."""
    global _ON_TPU_CACHE
    if _ON_TPU_CACHE is None:
        import jax
        # tpu only: the widened tolerances exist because f32 rides
        # multi-pass bf16 MXU matmuls — a rationale that does not hold
        # on gpu, where true-f32 accuracy is expected
        _ON_TPU_CACHE = jax.default_backend() == "tpu"
    return _ON_TPU_CACHE


def device_tols(dtype="float32"):
    """(rtol, atol) for comparing `dtype` results on the active backend
    — use in tests that call numpy asserts directly."""
    if _on_tpu() and str(dtype) in _TPU_TOLS:
        return _TPU_TOLS[str(dtype)]
    return _TOLS.get(str(dtype), (1e-4, 1e-6))


def default_context() -> Context:
    return _DEFAULT_CTX or current_context()


def set_default_context(ctx: Context):
    global _DEFAULT_CTX
    _DEFAULT_CTX = ctx


def default_dtype():
    return np.float32


def _to_np(a):
    return a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)


def same(a, b):
    return np.array_equal(_to_np(a), _to_np(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    a, b = _to_np(a), _to_np(b)
    rtol, atol = _resolve_tols(a, b, rtol, atol)
    return np.allclose(a.astype(np.float64), b.astype(np.float64),
                       rtol=rtol, atol=atol, equal_nan=equal_nan)


def _resolve_tols(a, b, rtol, atol):
    if rtol is None or atol is None:
        names = {str(a.dtype), str(b.dtype)}
        worst = (1e-5, 1e-8)
        for nm in names:
            t = _TPU_TOLS.get(nm) if _on_tpu() else None
            t = t or _TOLS.get(nm, (1e-4, 1e-6))
            worst = (max(worst[0], t[0]), max(worst[1], t[1]))
        rtol = worst[0] if rtol is None else rtol
        atol = worst[1] if atol is None else atol
    return rtol, atol


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    a_np, b_np = _to_np(a), _to_np(b)
    rtol, atol = _resolve_tols(a_np, b_np, rtol, atol)
    if not np.allclose(a_np.astype(np.float64), b_np.astype(np.float64),
                       rtol=rtol, atol=atol, equal_nan=equal_nan):
        err = np.abs(a_np.astype(np.float64) - b_np.astype(np.float64))
        rel = err / (np.abs(b_np.astype(np.float64)) + atol)
        raise AssertionError(
            f"{names[0]} and {names[1]} differ: max abs err {err.max():g}, "
            f"max rel err {rel.max():g} (rtol={rtol} atol={atol})\n"
            f"{names[0]}: {a_np}\n{names[1]}: {b_np}")


def rand_shape_nd(ndim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=ndim))


def rand_ndarray(shape, stype="default", density=None, dtype=None, ctx=None):
    ctx = ctx or default_context()
    arr = np.random.uniform(-1.0, 1.0, size=shape).astype(dtype or np.float32)
    if stype == "default":
        return array(arr, ctx=ctx)
    from .ndarray import sparse
    if density is not None:
        mask = np.random.uniform(size=shape[:1]) < density
        arr = arr * mask.reshape((-1,) + (1,) * (len(shape) - 1))
    return sparse.cast_storage(array(arr, ctx=ctx), stype)


def list_gpus():
    from .context import num_gpus
    return list(range(num_gpus()))


def simple_forward(fn, *inputs, ctx=None, **params):
    ctx = ctx or default_context()
    nd_inputs = [array(x, ctx=ctx) if not isinstance(x, NDArray) else x
                 for x in inputs]
    out = fn(*nd_inputs, **params)
    return out.asnumpy() if isinstance(out, NDArray) else [o.asnumpy() for o in out]


def check_numeric_gradient(fn, inputs, grad_outputs=None, eps=1e-3,
                           rtol=None, atol=None, ctx=None, dtype=np.float64):
    """Central finite differences vs autograd.

    fn: callable(*NDArrays) -> NDArray (scalar or any shape; reduced by
    sum for the check). inputs: list of numpy arrays.

    On an accelerator the DEFAULT tolerances widen (reference:
    per-device tol tables) — finite differences amplify the backend's
    f32 rounding. Explicitly passed rtol/atol are authoritative on every
    backend (callers pinning exact gradients can opt out)."""
    if rtol is None:
        rtol = 5e-2 if _on_tpu() else 1e-2
    if atol is None:
        atol = 5e-3 if _on_tpu() else 1e-3
    from . import autograd

    ctx = ctx or default_context()
    nd_inputs = [array(x.astype(np.float32), ctx=ctx) for x in inputs]
    for x in nd_inputs:
        x.attach_grad()
    with autograd.record():
        out = fn(*nd_inputs)
        loss = out.sum()
    loss.backward()
    analytic = [x.grad.asnumpy().astype(np.float64) for x in nd_inputs]

    for i, x in enumerate(inputs):
        numeric = np.zeros_like(x, dtype=np.float64)
        flat = x.reshape(-1)
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + eps
            plus = float(fn(*[array(v.astype(np.float32), ctx=ctx) for v in inputs])
                         .sum().asscalar())
            flat[j] = orig - eps
            minus = float(fn(*[array(v.astype(np.float32), ctx=ctx) for v in inputs])
                          .sum().asscalar())
            flat[j] = orig
            numeric.reshape(-1)[j] = (plus - minus) / (2 * eps)
        assert_almost_equal(analytic[i], numeric, rtol=rtol, atol=atol,
                            names=(f"analytic[{i}]", f"numeric[{i}]"))


def check_consistency(fn, ctx_list, inputs, rtol=None, atol=None,
                      grad_check=True):
    """Run fn under several (ctx, dtype) combos and compare forward and
    backward results — the cpu↔tpu golden harness.

    ctx_list: list of dicts {"ctx": Context, "dtype": str}.
    inputs: list of numpy arrays (cast per-combo).
    """
    from . import autograd

    results = []
    for combo in ctx_list:
        ctx, dt = combo["ctx"], combo.get("dtype", "float32")
        nd_inputs = [array(x, ctx=ctx, dtype=dt) for x in inputs]
        for x in nd_inputs:
            x.attach_grad()
        with autograd.record():
            out = fn(*nd_inputs)
            loss = out.sum()
        if grad_check:
            loss.backward()
            grads = [x.grad.asnumpy().astype(np.float64) for x in nd_inputs]
        else:
            grads = None
        results.append((out.asnumpy().astype(np.float64), grads, combo))

    ref_out, ref_grads, ref_combo = results[0]
    for out, grads, combo in results[1:]:
        dt = combo.get("dtype", "float32")
        t = device_tols(dt)  # per-(device, dtype) — the harness's point
        r = rtol if rtol is not None else t[0]
        a = atol if atol is not None else t[1]
        assert_almost_equal(out, ref_out, rtol=r, atol=a,
                            names=(str(combo), str(ref_combo)))
        if grad_check and grads is not None:
            for g, rg in zip(grads, ref_grads):
                assert_almost_equal(g, rg, rtol=r, atol=a,
                                    names=(f"grad@{combo}", f"grad@{ref_combo}"))
    return results


def with_seed(seed=None):
    """Decorator: seed mxnet+numpy per test, log seed on failure."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            this_seed = seed if seed is not None else np.random.randint(0, 2**31)
            np.random.seed(this_seed)
            mx_random.seed(this_seed)
            pyrandom.seed(this_seed)
            try:
                return fn(*args, **kwargs)
            except Exception:
                logging.error("test failed with seed %d — reproduce with "
                              "@with_seed(%d)", this_seed, this_seed)
                raise
        return wrapper
    return deco


def download(url, fname=None, dirname=None, overwrite=False):
    raise NotImplementedError(
        "network access is unavailable in the TPU sandbox; place files locally")
