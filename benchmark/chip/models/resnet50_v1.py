"""resnet50_v1: ``model_zoo.vision.resnet50_v1`` + ``SoftmaxCrossEntropyLoss``
as a user writes them, the seeded weights the benchmark hands to the program
and to the plain reference, and the FLOPs of one image from shapes."""
from __future__ import annotations

import math

import numpy as np


def _blocks(cfg):
    """(stage, block, in_channels, mid, out, stride, downsample) per
    bottleneck, as ``ResNetV1._make_layer`` lays them out."""
    cin = cfg["stem_channels"]
    for si, (n, cout) in enumerate(zip(cfg["stage_blocks"],
                                       cfg["stage_channels"])):
        for bi in range(n):
            stride = (1 if si == 0 else 2) if bi == 0 else 1
            yield si + 1, bi, cin, cout // 4, cout, stride, bi == 0 and cout != cin
            cin = cout


def _bn(prefix, c, gamma="ones"):
    return [(prefix + "gamma", (c,), "float32", gamma),
            (prefix + "beta", (c,), "float32", "zeros"),
            (prefix + "running_mean", (c,), "float32", "zeros"),
            (prefix + "running_var", (c,), "float32", "ones")]


def param_specs(cfg):
    dt = cfg["dtype"]
    c0 = cfg["stem_channels"]
    out = [("conv2d0_weight", (c0, 3, 7, 7), dt, "normal")] + _bn("batchnorm0_", c0)
    stage, k_conv, k_bn = None, 0, 0
    for si, bi, cin, mid, cout, stride, down in _blocks(cfg):
        if si != stage:
            stage, k_conv, k_bn = si, 0, 0
        p = f"stage{si}_"

        def conv(shape, bias):
            nonlocal k_conv
            r = [(f"{p}conv2d{k_conv}_weight", shape, dt, "normal")]
            if bias:
                r.append((f"{p}conv2d{k_conv}_bias", (shape[0],), dt, "zeros"))
            k_conv += 1
            return r

        def bn(c, gamma="ones"):
            nonlocal k_bn
            r = _bn(f"{p}batchnorm{k_bn}_", c, gamma)
            k_bn += 1
            return r

        out += conv((mid, cin, 1, 1), True) + bn(mid)
        out += conv((mid, mid, 3, 3), False) + bn(mid)
        out += conv((cout, mid, 1, 1), True) + bn(cout, cfg.get("last_bn_gamma", "ones"))
        if down:
            out += conv((cout, cin, 1, 1), False) + bn(cout)
    out += [("dense0_weight", (cfg["classes"], cfg["stage_channels"][-1]), dt, "normal"),
            ("dense0_bias", (cfg["classes"],), dt, "zeros")]
    return out


def init_std(cfg, name, shape):
    """He normal on the fan-in (He et al. 2015, arXiv:1502.01852)."""
    return math.sqrt(2.0 / float(np.prod(shape[1:])))


def build(cfg, ctxs):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1

    net = ResNetV1(BottleneckV1, list(cfg["stage_blocks"]),
                   [cfg["stem_channels"]] + list(cfg["stage_channels"]),
                   classes=cfg["classes"])
    net.initialize(init=mx.initializer.Zero(), ctx=ctxs)
    net.cast(cfg["dtype"])
    net.hybridize(**cfg.get("hybridize", {}))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()

    def forward(x, y):
        return loss_fn(net(x), y)

    return net, forward


def host_batch(cfg, shape, rng):
    b, s = shape["batch"], cfg["image_size"]
    x = rng.random((b, 3, s, s), dtype=np.float32)
    y = rng.integers(0, cfg["classes"], (b,), dtype=np.int32)
    return x, y


def input_dtypes(cfg):
    return (cfg["dtype"], "int32")


def samples_and_denominator(cfg, shape):
    return shape["batch"], shape["batch"]


def flops_per_sample(cfg, shape):
    """Model FLOPs of one image, forward + backward: 3x the forward's
    multiply-adds x 2 over every convolution and the classifier
    (BatchNorm, ReLU, pooling and the softmax not counted)."""
    s = cfg["image_size"]
    hw = (s // 2) ** 2                      # stem: 7x7 stride 2
    macs = hw * cfg["stem_channels"] * 3 * 49
    side = s // 4                           # after the 3x3 stride-2 max-pool
    for si, bi, cin, mid, cout, stride, down in _blocks(cfg):
        out_side = side // stride
        o = out_side * out_side
        macs += o * mid * cin               # 1x1 (carries the stride)
        macs += o * mid * mid * 9           # 3x3
        macs += o * cout * mid              # 1x1
        if down:
            macs += o * cout * cin
        side = out_side
    macs += cfg["stage_channels"][-1] * cfg["classes"]
    return 3 * 2 * macs
