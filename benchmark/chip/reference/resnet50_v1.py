"""Plain reference for resnet50_v1: He et al. 2015 (arXiv:1512.03385) 50-layer
bottleneck network as the MXNet/GluonCV model zoo's ``resnet50_v1`` lays it
out, ``jax.numpy``/``lax`` in float32: 7x7/2 stem, BatchNorm in TRAINING mode
(batch statistics, biased variance), ReLU, 3x3/2 max-pool (pad 1), four
stages of bottlenecks with the stride on the first 1x1, a projection
shortcut at each stage's first block, global average pool, a dense
classifier, summed softmax cross-entropy. The 1x1 convolutions of a block
carry a bias (the zoo's do); BatchNorm cancels it.

BatchNorm couples the rows of a batch, so the batch is NOT split: each
bottleneck is checkpointed instead, so that it fits."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS_INDEPENDENT = False


def _conv(lin, x, w, stride, pad):
    return lin(lambda a, m: lax.conv_general_dilated(
        a, m, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW")))(x, w)


def _bn_relu(x, w, p, eps, relu=True):
    mu = jnp.mean(x, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), (0, 2, 3), keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps) * w[p + "gamma"][None, :, None, None] \
        + w[p + "beta"][None, :, None, None]
    return jnp.maximum(y, 0.0) if relu else y


def _bottleneck(cfg, lin, w, p, stride, down, x):
    eps = cfg["bn_eps"]
    h = _conv(lin, x, w[p[0] + "weight"], stride, 0) \
        + w[p[0] + "bias"][None, :, None, None]
    h = _bn_relu(h, w, p[1], eps)
    h = _conv(lin, h, w[p[2] + "weight"], 1, 1)
    h = _bn_relu(h, w, p[3], eps)
    h = _conv(lin, h, w[p[4] + "weight"], 1, 0) \
        + w[p[4] + "bias"][None, :, None, None]
    h = _bn_relu(h, w, p[5], eps, relu=False)
    if down:
        x = _conv(lin, x, w[p[6] + "weight"], stride, 0)
        x = _bn_relu(x, w, p[7], eps, relu=False)
    return jnp.maximum(h + x, 0.0)


def loss_sum(cfg, w, batch, lin):
    x, y = batch
    eps = cfg["bn_eps"]
    x = _conv(lin, x.astype(jnp.float32), w["conv2d0_weight"], 2, 3)
    x = _bn_relu(x, w, "batchnorm0_", eps)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    cin = cfg["stem_channels"]
    for si, (n, cout) in enumerate(zip(cfg["stage_blocks"],
                                       cfg["stage_channels"])):
        kc = kb = 0
        for bi in range(n):
            stride = (1 if si == 0 else 2) if bi == 0 else 1
            down = bi == 0 and cout != cin
            s = f"stage{si + 1}_"
            names = []
            for j in range(4 if down else 3):
                names += [f"{s}conv2d{kc}_", f"{s}batchnorm{kb}_"]
                kc += 1
                kb += 1
            x = jax.checkpoint(
                lambda x_, w_, names=tuple(names), stride=stride, down=down:
                _bottleneck(cfg, lin, w_, names, stride, down, x_))(x, w)
            cin = cout
    x = jnp.mean(x, (2, 3))
    logits = lin(lambda a, m: jnp.einsum("bi,oi->bo", a, m))(
        x, w["dense0_weight"]) + w["dense0_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, y[:, None], axis=-1))
