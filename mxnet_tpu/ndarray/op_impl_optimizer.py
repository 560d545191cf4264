"""Fused optimizer update operators.

Analog of the reference's ``src/operator/optimizer_op.{cc,cu}``
(sgd_update, sgd_mom_update, mp_sgd_* multi-precision, adam_update,
ftrl_update, rmsprop_update, signsgd/signum, nag, lamb_* (v≥1.6),
multi-tensor multi_sgd_*). Each is a pure jax function with two callers.
The imperative API (``nd.<op>``, ``Optimizer.update``) runs it eagerly,
one device program per jax primitive in it, and writes results back
through ``out=`` (NDArray._set_data — the in-place engine-write analog).
``Optimizer.update_multi`` (Gluon ``Trainer.step``) calls the same
function for every parameter inside ONE jitted program
(optimizer/optimizer.py ``_fused_update``), where lr, wd and
rescale_grad arrive as traced scalars: an implementation must stay
traceable in them (no ``float(lr)``, no branch on their value).

All ops are registered non-differentiable (the reference marks them
TIsBackward-free utility ops; one never differentiates through an
optimizer step in MXNet v1.x).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .register import register_op


def _rescale_clip(grad, rescale_grad, clip_gradient, wd=None, weight=None):
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return g


@register_op("sgd_update", differentiable=False)
def sgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
               clip_gradient=-1.0, lazy_update=True):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    return weight - lr * (g + wd * weight)


@register_op("sgd_mom_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2),))
def sgd_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    new_mom = momentum * mom - lr * (g + wd * weight)
    return weight + new_mom, new_mom


@register_op("nag_mom_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2),))
def nag_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient) + wd * weight
    new_mom = momentum * mom + g
    return weight - lr * (g + momentum * new_mom), new_mom


@register_op("mp_sgd_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2),))
def mp_sgd_update(weight, grad, weight32, lr=0.01, wd=0.0, rescale_grad=1.0,
                  clip_gradient=-1.0, lazy_update=True):
    """fp16/bf16 weights with fp32 master copy (mp_sgd_update in reference)."""
    g = _rescale_clip(grad.astype(jnp.float32), rescale_grad, clip_gradient)
    new_w32 = weight32 - lr * (g + wd * weight32)
    return new_w32.astype(weight.dtype), new_w32


@register_op("mp_sgd_mom_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2), (2, 3)))
def mp_sgd_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                      wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                      lazy_update=True):
    g = _rescale_clip(grad.astype(jnp.float32), rescale_grad, clip_gradient)
    new_mom = momentum * mom - lr * (g + wd * weight32)
    new_w32 = weight32 + new_mom
    return new_w32.astype(weight.dtype), new_mom, new_w32


@register_op("adam_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2), (2, 3)))
def adam_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                lazy_update=True):
    g = _rescale_clip(grad, rescale_grad, clip_gradient) + wd * weight
    new_mean = beta1 * mean + (1.0 - beta1) * g
    new_var = beta2 * var + (1.0 - beta2) * jnp.square(g)
    return (weight - lr * new_mean / (jnp.sqrt(new_var) + epsilon),
            new_mean, new_var)


@register_op("adamw_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2), (2, 3)))
def adamw_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, wd=0.0, eta=1.0, rescale_grad=1.0,
                 clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    new_mean = beta1 * mean + (1.0 - beta1) * g
    new_var = beta2 * var + (1.0 - beta2) * jnp.square(g)
    upd = new_mean / (jnp.sqrt(new_var) + epsilon) + wd * weight
    return weight - eta * lr * upd, new_mean, new_var


@register_op("rmsprop_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2),))
def rmsprop_update(weight, grad, n, lr=0.001, gamma1=0.95, epsilon=1e-8,
                   wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                   clip_weights=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient) + wd * weight
    new_n = gamma1 * n + (1.0 - gamma1) * jnp.square(g)
    w = weight - lr * g / jnp.sqrt(new_n + epsilon)
    if clip_weights is not None and clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, new_n


@register_op("rmspropalex_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2), (2, 3), (3, 4)))
def rmspropalex_update(weight, grad, n, g, delta, lr=0.001, gamma1=0.95,
                       gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                       clip_gradient=-1.0, clip_weights=-1.0):
    gr = _rescale_clip(grad, rescale_grad, clip_gradient) + wd * weight
    new_n = gamma1 * n + (1.0 - gamma1) * jnp.square(gr)
    new_g = gamma1 * g + (1.0 - gamma1) * gr
    new_delta = gamma2 * delta - lr * gr / jnp.sqrt(new_n - jnp.square(new_g) + epsilon)
    w = weight + new_delta
    if clip_weights is not None and clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, new_n, new_g, new_delta


@register_op("ftrl_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2), (2, 3)))
def ftrl_update(weight, grad, z, n, lr=0.1, lamda1=0.01, beta=1.0, wd=0.0,
                rescale_grad=1.0, clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    new_n = n + jnp.square(g)
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    new_z = z + g - sigma * weight
    w = jnp.where(
        jnp.abs(new_z) <= lamda1,
        jnp.zeros_like(weight),
        -(new_z - jnp.sign(new_z) * lamda1) / ((beta + jnp.sqrt(new_n)) / lr + wd),
    )
    return w, new_z, new_n


@register_op("signsgd_update", differentiable=False)
def signsgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    return weight - lr * (jnp.sign(g) + wd * weight)


@register_op("signum_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2),))
def signum_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                  rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient) + wd * weight
    new_mom = momentum * mom - (1.0 - momentum) * g
    w = weight + lr * jnp.sign(new_mom)
    if wd_lh:
        w = w - lr * wd_lh * weight
    return w, new_mom


@register_op("adagrad_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2),))
def adagrad_update(weight, grad, history, lr=0.01, epsilon=1e-7, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    new_hist = history + jnp.square(g)
    return weight - lr * (g / jnp.sqrt(new_hist + epsilon) + wd * weight), new_hist


@register_op("adadelta_update", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2), (2, 3)))
def adadelta_update(weight, grad, acc_g, acc_delta, rho=0.9, epsilon=1e-5,
                    wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient) + wd * weight
    new_acc_g = rho * acc_g + (1.0 - rho) * jnp.square(g)
    delta = jnp.sqrt(acc_delta + epsilon) / jnp.sqrt(new_acc_g + epsilon) * g
    new_acc_delta = rho * acc_delta + (1.0 - rho) * jnp.square(delta)
    return weight - delta, new_acc_g, new_acc_delta


@register_op("lamb_update_phase1", differentiable=False, num_visible_outputs=1,
             mutates=((1, 2), (2, 3)))
def lamb_update_phase1(weight, grad, mean, var, beta1=0.9, beta2=0.999,
                       epsilon=1e-6, t=1, bias_correction=True, wd=0.0,
                       rescale_grad=1.0, clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    new_mean = beta1 * mean + (1.0 - beta1) * g
    new_var = beta2 * var + (1.0 - beta2) * jnp.square(g)
    m = new_mean / (1.0 - beta1 ** t) if bias_correction else new_mean
    v = new_var / (1.0 - beta2 ** t) if bias_correction else new_var
    return m / (jnp.sqrt(v) + epsilon) + wd * weight, new_mean, new_var


# ----------------------------------------------------------------------
# multi-tensor updates (reference multi_sgd_update/multi_sgd_mom_update/
# multi_mp_sgd_*: one op call over MANY parameters). Kept as `nd` API:
# called eagerly they are a Python loop, a few device programs per
# parameter. `Trainer.step` does not use them: `Optimizer.update_multi`
# compiles the single-parameter ops above over the whole list.
# ----------------------------------------------------------------------
def _per_weight(vals, i, default):
    if vals is None:
        return default
    if isinstance(vals, (tuple, list)):
        return float(vals[i]) if i < len(vals) else default
    return float(vals)  # one scalar for all weights


@register_op("multi_sgd_update", differentiable=False)
def multi_sgd_update(*args, lrs=(), wds=(), rescale_grad=1.0,
                     clip_gradient=-1.0, num_weights=None):
    """args = (w0, g0, w1, g1, ...); returns the updated weights."""
    n = int(num_weights) if num_weights is not None else len(args) // 2
    outs = []
    for i in range(n):
        w, g = args[2 * i], args[2 * i + 1]
        gs = _rescale_clip(g, rescale_grad, clip_gradient)
        outs.append(w - _per_weight(lrs, i, 0.01)
                    * (gs + _per_weight(wds, i, 0.0) * w))
    return tuple(outs)


@register_op("multi_sgd_mom_update", differentiable=False)
def multi_sgd_mom_update(*args, lrs=(), wds=(), momentum=0.0,
                         rescale_grad=1.0, clip_gradient=-1.0,
                         num_weights=None):
    """args = (w0, g0, m0, w1, g1, m1, ...); returns
    (w0', m0', w1', m1', ...) — moms are written back via out=/mutates
    at the caller."""
    n = int(num_weights) if num_weights is not None else len(args) // 3
    outs = []
    for i in range(n):
        w, g, m = args[3 * i], args[3 * i + 1], args[3 * i + 2]
        gs = _rescale_clip(g, rescale_grad, clip_gradient)
        new_m = momentum * m - _per_weight(lrs, i, 0.01) \
            * (gs + _per_weight(wds, i, 0.0) * w)
        outs.append(w + new_m)
        outs.append(new_m)
    return tuple(outs)


@register_op("multi_mp_sgd_update", differentiable=False)
def multi_mp_sgd_update(*args, lrs=(), wds=(), rescale_grad=1.0,
                        clip_gradient=-1.0, num_weights=None):
    """args = (w0, g0, w32_0, ...); returns (w0', w32_0', ...)."""
    n = int(num_weights) if num_weights is not None else len(args) // 3
    outs = []
    for i in range(n):
        w, g, w32 = args[3 * i], args[3 * i + 1], args[3 * i + 2]
        gs = _rescale_clip(g.astype(jnp.float32), rescale_grad, clip_gradient)
        new32 = w32 - _per_weight(lrs, i, 0.01) \
            * (gs + _per_weight(wds, i, 0.0) * w32)
        outs.append(new32.astype(w.dtype))
        outs.append(new32)
    return tuple(outs)


@register_op("multi_mp_sgd_mom_update", differentiable=False)
def multi_mp_sgd_mom_update(*args, lrs=(), wds=(), momentum=0.0,
                            rescale_grad=1.0, clip_gradient=-1.0,
                            num_weights=None):
    """args = (w0, g0, m0, w32_0, ...); returns (w0', m0', w32_0', ...)."""
    n = int(num_weights) if num_weights is not None else len(args) // 4
    outs = []
    for i in range(n):
        w, g, m, w32 = args[4 * i:4 * i + 4]
        gs = _rescale_clip(g.astype(jnp.float32), rescale_grad, clip_gradient)
        new_m = momentum * m - _per_weight(lrs, i, 0.01) \
            * (gs + _per_weight(wds, i, 0.0) * w32)
        new32 = w32 + new_m
        outs.append(new32.astype(w.dtype))
        outs.append(new_m)
        outs.append(new32)
    return tuple(outs)


@register_op("lamb_update_phase2", differentiable=False)
def lamb_update_phase2(weight, g, r1, r2, lr=0.01, lower_bound=-1.0,
                       upper_bound=-1.0):
    r1v = jnp.where(r1 > 0, r1, jnp.ones_like(r1))
    r2v = jnp.where(r2 > 0, r2, jnp.ones_like(r2))
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1v / r2v, jnp.ones_like(r1))
    if lower_bound is not None and lower_bound > 0:
        ratio = jnp.maximum(ratio, lower_bound)
    if upper_bound is not None and upper_bound > 0:
        ratio = jnp.minimum(ratio, upper_bound)
    return weight - lr * ratio * g
