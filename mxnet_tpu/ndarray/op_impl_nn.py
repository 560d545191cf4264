"""Neural-network operators.

TPU-native implementations of the reference's ``src/operator/nn/``
family (fully_connected.cc, convolution.cc, deconvolution.cc,
pooling.cc, batch_norm.cc, layer_norm.cc, softmax.cc, dropout.cc,
activation.cc, leaky_relu.cc, upsampling.cc, embedding via
indexing_op.cc) and their cuDNN variants (src/operator/nn/cudnn/*) —
here a single XLA path: conv lowers through
``lax.conv_general_dilated`` (cuDNN-autotune's job is done by XLA's
conv emitter on the MXU), pooling through ``lax.reduce_window``,
normalizations as fusable elementwise+reduce graphs. bfloat16 flows
through every op (the AMP/fp16 analog).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..base import dtype_np
from .register import register_op


def _tup(v, n=None):
    if v is None:
        return None
    t = tuple(int(x) for x in np.atleast_1d(v))
    if n is not None and len(t) == 1:
        t = t * n
    return t


# ----------------------------------------------------------------------
# FullyConnected (src/operator/nn/fully_connected.cc) — MXU matmul
# ----------------------------------------------------------------------
@register_op("FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True):
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    out = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ----------------------------------------------------------------------
# Convolution family
# ----------------------------------------------------------------------
_CONV_DN = {1: ("NCW", "OIW", "NCW"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}


@register_op("Convolution")
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                workspace=1024, cudnn_tune=None, cudnn_off=False, layout=None):
    nd_ = len(_tup(kernel))
    stride = _tup(stride, nd_) or (1,) * nd_
    dilate = _tup(dilate, nd_) or (1,) * nd_
    pad = _tup(pad, nd_) or (0,) * nd_
    # bf16 convs accumulate in f32 on the MXU natively; forcing
    # preferred_element_type would break the VJP's dtype contract
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _CONV_DN[nd_])
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd_)
    # remat-policy anchor: under jax.checkpoint with
    # save_only_these_names('conv_out') the forward saves conv outputs
    # and recomputes only the cheap elementwise chain (BN/relu) in the
    # backward (see HybridBlock._remat_trace); a no-op otherwise
    return checkpoint_name(out, "conv_out")


@register_op("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, target_shape=None, num_filter=None,
                  num_group=1, no_bias=True, workspace=512, cudnn_tune=None,
                  cudnn_off=False, layout=None):
    nd_ = len(_tup(kernel))
    k = _tup(kernel)
    stride = _tup(stride, nd_) or (1,) * nd_
    dilate = _tup(dilate, nd_) or (1,) * nd_
    pad = _tup(pad, nd_) or (0,) * nd_
    adj = _tup(adj, nd_) or (0,) * nd_
    # weight layout (C_in, C_out/group, *k); flip spatial, swap in/out via
    # IOHW dimension spec → gradient-of-conv formulation
    spec = {1: "IOW", 2: "IOHW", 3: "IODHW"}[nd_]
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (_CONV_DN[nd_][0], spec, _CONV_DN[nd_][2]))
    padding = [
        (d * (kk - 1) - p, d * (kk - 1) - p + a)
        for kk, p, d, a in zip(k, pad, dilate, adj)
    ]
    wflip = weight
    for ax in range(2, 2 + nd_):
        wflip = jnp.flip(wflip, ax)
    out = lax.conv_general_dilated(
        data, wflip,
        window_strides=(1,) * nd_,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd_)
    return out


# ----------------------------------------------------------------------
# Pooling (src/operator/nn/pooling.cc)
# ----------------------------------------------------------------------
@register_op("Pooling")
def pooling(data, kernel=None, pool_type="max", global_pool=False,
            pooling_convention="valid", stride=None, pad=None,
            count_include_pad=True, cudnn_off=False, layout=None):
    nd_ = data.ndim - 2
    if global_pool:
        ax = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        if pool_type == "sum":
            return jnp.sum(data, axis=ax, keepdims=True)
        return jnp.mean(data, axis=ax, keepdims=True)
    k = _tup(kernel, nd_)
    stride = _tup(stride, nd_) or (1,) * nd_
    pad = _tup(pad, nd_) or (0,) * nd_
    window = (1, 1) + k
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad high edge so the last partial window is included
        pads = []
        for i in range(nd_):
            in_sz = data.shape[2 + i]
            out_sz = int(np.ceil((in_sz + 2 * pad[i] - k[i]) / stride[i])) + 1
            needed = (out_sz - 1) * stride[i] + k[i] - in_sz - pad[i]
            pads.append((pad[i], max(needed, pad[i])))
    else:
        pads = [(p, p) for p in pad]
    padding = ((0, 0), (0, 0)) + tuple(pads)

    # init values MUST be concrete numpy scalars: under an outer jit a
    # jnp constant becomes a tracer and lax can no longer recognize the
    # max/add monoid → falls to generic reduce_window with no VJP rule
    if pool_type == "max":
        if jnp.issubdtype(data.dtype, jnp.floating):
            # NOTE: an equality-mask custom VJP (k*k shifted compares +
            # interior-padded scatter-back) was measured at b128 ResNet:
            # 1813 img/s vs 2542 with select_and_scatter — XLA does NOT
            # fuse the 9 strided-slice/pad branches and the 112^2
            # activations round-trip HBM per tap. select_and_scatter
            # stays (2.2 ms of a 46 ms step; revisit only with a real
            # Pallas window kernel).
            init = np.asarray(-np.inf, data.dtype)
        else:
            init = np.asarray(np.iinfo(data.dtype).min, data.dtype)
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    zero = np.asarray(0, data.dtype)
    summed = lax.reduce_window(data, zero, lax.add, window, strides, padding)
    if pool_type == "sum":
        return summed
    # avg
    if count_include_pad:
        denom = np.prod(k)
        return summed / np.asarray(denom, data.dtype)
    ones = jnp.ones_like(data)
    counts = lax.reduce_window(ones, zero, lax.add, window, strides, padding)
    return summed / counts


@register_op("UpSampling")
def upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0,
               multi_input_mode="concat", workspace=512):
    data = args[0]
    s = int(scale)
    out = jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
    return out


# ----------------------------------------------------------------------
# Activations
# ----------------------------------------------------------------------
_ACT = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "log_sigmoid": jax.nn.log_sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "gelu_tanh": functools.partial(jax.nn.gelu, approximate=True),
    "silu": jax.nn.silu,
}


@register_op("Activation")
def activation(data, act_type="relu"):
    return _ACT[act_type](data)


@register_op("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334, _rng_key=None):
    """LeakyReLU family (src/operator/leaky_relu.cc): leaky/prelu/elu/
    selu/gelu/rrelu. GELU is the BERT-critical one (v≥1.5)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        return jnp.where(data > 0, data, gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * (jnp.exp(data) - 1.0))
    if act_type == "selu":
        alpha, lam = 1.6732632423543772, 1.0507009873554805
        return lam * jnp.where(data > 0, data, alpha * (jnp.exp(data) - 1.0))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise ValueError(f"unknown act_type {act_type}")


@register_op("gelu")
def gelu(data, approximate=False):
    return jax.nn.gelu(data, approximate=bool(approximate))


@register_op("swish")
def swish(data, beta=1.0):
    return data * jax.nn.sigmoid(beta * data)


# ----------------------------------------------------------------------
# softmax family (src/operator/nn/softmax.cc)
# ----------------------------------------------------------------------
@register_op("softmax")
def softmax(data, axis=-1, temperature=None, length=None, dtype=None, use_length=False):
    x = data if temperature in (None, 1.0) else data / temperature
    if length is not None:
        T = x.shape[int(axis)]
        steps = jnp.arange(T)
        mask_shape = [1] * x.ndim
        mask_shape[int(axis)] = T
        lens = length.reshape(tuple(length.shape) + (1,) * (x.ndim - length.ndim))
        mask = steps.reshape(mask_shape) < lens
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=int(axis))
        return jnp.where(mask, out, 0.0)
    out = jax.nn.softmax(x, axis=int(axis))
    if dtype is not None:
        out = out.astype(dtype_np(dtype))
    return out


@register_op("log_softmax")
def log_softmax(data, axis=-1, temperature=None, dtype=None, use_length=False):
    x = data if temperature in (None, 1.0) else data / temperature
    out = jax.nn.log_softmax(x, axis=int(axis))
    if dtype is not None:
        out = out.astype(dtype_np(dtype))
    return out


@register_op("softmin")
def softmin(data, axis=-1, temperature=None, dtype=None):
    return softmax(-data, axis=axis, temperature=temperature, dtype=dtype)


@register_op("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register_op("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   use_ignore=False, preserve_shape=False, multi_output=False,
                   out_grad=False, normalization="null", smooth_alpha=0.0):
    """Legacy Module-API loss head: forward=softmax, backward=p−onehot
    (reference src/operator/softmax_output.cc). Non-tensor params are
    closed over (custom_vjp args must be JAX types)."""
    ax = 1 if multi_output else -1

    @jax.custom_vjp
    def fwd(d, l):
        return jax.nn.softmax(d, axis=ax)

    def f(d, l):
        out = jax.nn.softmax(d, axis=ax)
        return out, (out, l)

    def b(res, g):
        out, l = res
        n_class = out.shape[ax]
        if multi_output and l.shape != out.shape[:1] + out.shape[2:]:
            # reference convention: flattened spatial label (n, d1*...*dk)
            l = l.reshape(out.shape[:1] + out.shape[2:])
        oh = jax.nn.one_hot(l.astype(jnp.int32), n_class, axis=ax,
                            dtype=out.dtype)
        if smooth_alpha:
            oh = oh * (1.0 - smooth_alpha) \
                + smooth_alpha / (n_class - 1) * (1.0 - oh)
        grad = out - oh
        if use_ignore:
            keep = (l != ignore_label).astype(out.dtype)
            keep = jnp.expand_dims(keep, ax) if keep.ndim < out.ndim else keep
            grad = grad * keep
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid" and use_ignore:
            cnt = jnp.maximum(jnp.sum(l != ignore_label), 1)
            grad = grad / cnt
        return (grad * grad_scale, jnp.zeros_like(l))

    fwd.defvjp(f, b)
    return fwd(data, label)


@register_op("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    """Summed -log softmax(data)[label]; data (N, V), label (N,).

    Produce ``data`` with a 2-D product (``FullyConnected`` of a 2-D
    input): the kernel reads the logits vocabulary-major and XLA writes
    them so only behind a 2-D matmul. A 3-D product reshaped to (N, V)
    costs a copy of the logits (``ops/pallas/softmax_xent.py``)."""
    from ..ops import pallas as _pallas

    if (_pallas.pallas_ok_for(data)
            and data.dtype in (jnp.float32, jnp.bfloat16, jnp.float16)):
        loss = _pallas.softmax_xent_fused(data, label)
        return jnp.sum(loss).reshape(1).astype(data.dtype)
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked).reshape(1)


# ----------------------------------------------------------------------
# Attention helpers for the composed (masked) path — the 4D batched
# forms of the reference-era batch_dot attention (dot-inl.h + softmax.cc)
# ----------------------------------------------------------------------
@register_op("batch_dot_attention_scores")
def batch_dot_attention_scores(query, key):
    """(B,H,Sq,D),(B,H,Sk,D) -> (B,H,Sq,Sk) score matrix (unscaled)."""
    return jnp.einsum("bhqd,bhkd->bhqk", query, key)


@register_op("batch_dot_attention_apply")
def batch_dot_attention_apply(probs, value):
    """(B,H,Sq,Sk),(B,H,Sk,D) -> (B,H,Sq,D)."""
    return jnp.einsum("bhqk,bhkd->bhqd", probs, value)


@register_op("ctc_loss", aliases=("CTCLoss", "_contrib_ctc_loss"))
def ctc_loss_op(data, label, data_lengths=None, label_lengths=None,
                use_data_lengths=False, use_label_lengths=False,
                blank_label="first"):
    """Connectionist temporal classification loss (reference
    src/operator/nn/ctc_loss.cc / warp-ctc). data (T, N, C)
    unnormalized, label (N, L). blank_label='first': index 0 is blank
    and labels use 1..C-1 (the math in ops/ctc.py); 'last': index C-1
    is blank and labels use 0..C-2 (mapped by rolling the alphabet).
    Returns (N,) losses; gradients via autodiff of the lax.scan alpha
    recursion."""
    from ..ops.ctc import ctc_loss as _ctc

    if blank_label not in ("first", "last"):
        raise ValueError(f"blank_label must be first|last, got {blank_label}")
    if blank_label == "last":
        # move blank C-1 -> 0; real classes 0..C-2 -> 1..C-1. Padding in
        # `label` for 'last' mode is -1 (reference convention) -> 0.
        data = jnp.concatenate([data[..., -1:], data[..., :-1]], axis=-1)
        label = jnp.where(label < 0, -1, label) + 1
    dl = data_lengths if use_data_lengths else None
    ll = label_lengths if use_label_lengths else None
    return _ctc(data, label, dl, ll)


@register_op("attention_length_mask")
def attention_length_mask(scores, valid_len):
    """Mask score columns at/after each example's valid length with
    -1e30 (additive-mask form of kv_lens, for the composed attention
    path; scores (B, H|1, Sq, Sk), valid_len (B,))."""
    sk = scores.shape[-1]
    m = jnp.arange(sk)[None, None, None, :] \
        < valid_len.astype(jnp.int32).reshape(-1)[:, None, None, None]
    return jnp.where(m, scores, jnp.asarray(-1e30, scores.dtype))


@register_op("attention_zero_empty_rows")
def attention_zero_empty_rows(probs, valid_len):
    """Zero the attention probs of examples whose valid_len == 0:
    softmax over an all-masked row is uniform (every score is the same
    -1e30), which would attend the padding — the flash kernel emits
    exact zeros there (l==0 guard), and the composed path must agree."""
    ok = valid_len.astype(jnp.int32).reshape(-1) > 0
    return probs * ok[:, None, None, None].astype(probs.dtype)


@register_op("attention_segment_mask")
def attention_segment_mask(scores, segment_ids):
    """Mask cross-segment score pairs with -1e30 (additive-mask form of
    the packed block-diagonal attention, for the composed path; scores
    (B, H|1, Sq, Sk), segment_ids (B, S) with Sq == Sk == S). Tokens
    attend only same-segment tokens — padding slots (id 0) are their own
    'segment', so mask them via attention_length_mask / loss masking."""
    seg = segment_ids.astype(jnp.int32)
    m = seg[:, None, :, None] == seg[:, None, None, :]
    return jnp.where(m, scores, jnp.asarray(-1e30, scores.dtype))


@register_op("attention_zero_pad_rows")
def attention_zero_pad_rows(probs, segment_ids):
    """Zero attention probs of PADDING query rows (segment id 0) in a
    packed batch: every real key is cross-segment for them, so their
    all-masked scores softmax to uniform on the composed path — the
    flash kernel emits exact zeros there (l==0 guard) and the composed
    path must agree."""
    ok = segment_ids.astype(jnp.int32) > 0
    return probs * ok[:, None, :, None].astype(probs.dtype)


@register_op("segment_valid_len", differentiable=False)
def segment_valid_len(segment_ids):
    """(B,) count of non-padding (id > 0) slots per packed row — the
    kv_lens companion a packed batch needs on the flash path (packers
    lay segments contiguously from position 0, so the count IS the used
    length)."""
    return jnp.sum((segment_ids.astype(jnp.int32) > 0)
                   .astype(jnp.int32), axis=-1)


@register_op("causal_mask_scores")
def causal_mask_scores(scores):
    """End-aligned causal mask over the last two axes of (…,Sq,Sk)."""
    sq, sk = scores.shape[-2], scores.shape[-1]
    cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
    return jnp.where(cm, scores, -1e30)


# ----------------------------------------------------------------------
# Fused scaled-dot-product attention — NEW op, no reference analog
# (SURVEY §5.7: upstream composes attention from batch_dot+softmax).
# Exposed as mx.nd.flash_attention.
# ----------------------------------------------------------------------
@register_op("flash_attention")
def flash_attention_op(query, key, value, valid_len=None, segment_ids=None,
                       causal=False, sm_scale=None, window=None,
                       name_scope=None, pair_mask=None):
    """softmax(Q K^T * scale) V over (B, H, S, D) inputs.

    Pallas flash kernel on TPU (O(S) memory); jnp fallback elsewhere.
    ``valid_len`` (B,) int masks keys at/after each example's length
    (padded batches) — the kernel handles it natively (per-example
    length in SMEM, fully-masked tiles skipped; see
    ops/pallas/flash_attention.py). ``segment_ids`` (B, S) int makes
    attention block-diagonal over packed sequences (sequence packing,
    io/packing.py; requires Sq == Skv): tokens attend only tokens with
    the same segment id, cross-block tiles with disjoint id ranges are
    skipped whole.

    The q.k width and the v width may differ (latent attention: 192 and
    128). The default scale is 1/sqrt of the q.k width. The kernel has one
    head width, so the Pallas path zero-pads q, k and v to the next
    multiple of 128 of the wider one and cuts the output back to v's: the
    pad adds nothing to q.k and its output columns are dropped. The jnp
    fallback needs no pad.

    ``window=W`` (with ``causal``; static): a token sees itself and the
    W - 1 keys before it. The kernel walks only the tiles the band crosses.
    ``key`` and ``value`` may have FEWER HEADS than ``query`` ((B, Hk, S, D)
    with Hk dividing H): query head h reads key/value head h // (H / Hk)
    through the kernel's index maps (nothing is repeated in HBM), and their
    gradients come back with Hk heads. They may also be another layer's
    (cross-attention to keys and values a source layer projected): the op
    only asks that the lengths agree with the mask wanted.
    ``name_scope``: a ``jax.named_scope`` for the call, forward and
    backward, so that a device trace can tell one kind of attention layer
    from another (``mxtpu_swa``, ``mxtpu_yoco``).
    ``pair_mask`` (B, Sq, Skv) int8, an ARRAY (data, not a static argument;
    no gradient): query t sees key s only where it is nonzero, besides what
    ``causal`` allows; one mask for all heads. The kernel reads it a tile at
    a time and skips the tiles it leaves empty; it goes with neither
    ``valid_len``, ``segment_ids`` nor ``window``.
    """
    if name_scope is not None:
        with jax.named_scope(name_scope):
            return flash_attention_op(query, key, value, valid_len,
                                      segment_ids, causal, sm_scale, window,
                                      None, pair_mask)
    from ..ops import pallas as _pallas

    if window is not None and not causal:
        raise ValueError("flash_attention: window needs causal=True")
    if pair_mask is not None:
        if valid_len is not None or segment_ids is not None or window is not None:
            raise ValueError("flash_attention: pair_mask goes with neither "
                             "valid_len, segment_ids nor window")
        pair_mask = pair_mask.astype(jnp.int8)

    if valid_len is not None:
        valid_len = valid_len.astype(jnp.int32).reshape(-1)
    if segment_ids is not None:
        segment_ids = segment_ids.astype(jnp.int32)
    d, dv = query.shape[-1], value.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    if (_pallas.pallas_ok_for(query)
            and query.dtype in (jnp.float32, jnp.bfloat16, jnp.float16)
            and query.ndim == 4):
        # end-aligned causal mask for sq != skv (KV-cache decode): q row
        # 0 is global position skv - sq, matching the tril(k=sk-sq)
        # fallback below
        q_off = key.shape[2] - query.shape[2] if causal else 0
        if d != dv:
            wide = -(-max(d, dv) // 128) * 128
            query, key, value = (
                jnp.pad(t, ((0, 0),) * 3 + ((0, wide - t.shape[-1]),))
                for t in (query, key, value))
            sm_scale = scale
        out = _pallas.flash_attention(query, key, value, sm_scale,
                                      bool(causal), q_off, None, valid_len,
                                      segment_ids,
                                      None if window is None else int(window),
                                      pair_mask)
        return out[..., :dv]
    group = query.shape[1] // key.shape[1]
    if group > 1:
        key, value = (jnp.repeat(t, group, axis=1) for t in (key, value))
    s = jnp.einsum("bhqd,bhkd->bhqk",
                   query.astype(jnp.float32),
                   key.astype(jnp.float32)) * scale
    sq, sk = s.shape[-2], s.shape[-1]
    mask = None
    if valid_len is not None:
        mask = jnp.arange(sk)[None, None, None, :] \
            < valid_len[:, None, None, None]
    if segment_ids is not None:
        sm = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = sm if mask is None else jnp.logical_and(mask, sm)
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            cm = jnp.logical_and(cm, jnp.triu(jnp.ones((sq, sk), bool),
                                              k=sk - sq - int(window) + 1))
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    if pair_mask is not None:
        pm = pair_mask[:, None] != 0
        mask = pm if mask is None else jnp.logical_and(mask, pm)
    if mask is not None:
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        # fully-masked rows: emit zeros, matching the Pallas kernel's
        # l==0 guard
        p = jnp.where(
            jnp.broadcast_to(mask, s.shape).any(-1, keepdims=True), p, 0.0)
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      value.astype(jnp.float32)).astype(query.dtype)


# ----------------------------------------------------------------------
# normalization (batch_norm.cc, layer_norm.cc, instance_norm.cc, l2_norm)
# ----------------------------------------------------------------------
# ----------------------------------------------------------------------
# Fused training-mode BatchNorm with a hand-written VJP.
#
# The composed graph (mean pass -> centered-diff var pass -> normalize,
# autodiffed) costs ~6 full passes over the activation in backward; on
# ResNet-50 b128 the xprof trace shows every one of those fusions
# HBM-BOUND at 630-695 GB/s, so the ONLY lever is traffic. This op does
# forward in 2 passes (one fused sum/sum-of-squares reduce, one
# normalize using the E[x^2]-E[x]^2 form — the cuDNN/batch_norm.cc
# stat form — so the centered diff never materializes) and backward in
# 2 passes (one fused dbeta/dgamma reduce over (do, x), one dx pass).
# ----------------------------------------------------------------------
def _bn_red_axes(ndim, ax):
    return tuple(i for i in range(ndim) if i != ax)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bn_train_core(x, gamma, beta, shift, eps, ax, fix_gamma):
    return _bn_train_fwd_math(x, gamma, beta, shift, eps, ax, fix_gamma)


def _bn_train_fwd_math(x, gamma, beta, shift, eps, ax, fix_gamma):
    red = _bn_red_axes(x.ndim, ax)
    n = float(np.prod([x.shape[i] for i in red]))
    shp0 = [1] * x.ndim
    shp0[ax] = -1
    # the E[u^2]-E[u]^2 form cancels catastrophically when |mean| >>
    # std; shifting u = x - shift by a per-channel estimate of the mean
    # (the layer passes the running mean — exact-identity math, zero
    # extra passes since the subtract fuses into the reduce) keeps u
    # near-centered in steady state
    xf = x.astype(jnp.float32) - shift.astype(jnp.float32).reshape(shp0)
    s1 = jnp.sum(xf, red)
    s2 = jnp.sum(xf * xf, red)  # fuses with s1: one pass, two outputs
    mean_c = s1 / n
    var = jnp.maximum(s2 / n - mean_c * mean_c, 0.0)
    mean = mean_c + shift.astype(jnp.float32)
    ivar = lax.rsqrt(var + eps)
    g32 = (jnp.ones_like(mean) if fix_gamma
           else gamma.astype(jnp.float32))
    scale = g32 * ivar
    off = beta.astype(jnp.float32) - mean_c * scale  # xf is pre-shifted
    out = (xf * scale.reshape(shp0) + off.reshape(shp0)).astype(x.dtype)
    return out, mean, var


def _bn_train_vjp_fwd(x, gamma, beta, shift, eps, ax, fix_gamma):
    out, mean, var = _bn_train_fwd_math(x, gamma, beta, shift, eps, ax,
                                        fix_gamma)
    return (out, mean, var), (x, gamma, beta, mean, var)


def _bn_train_vjp_bwd(eps, ax, fix_gamma, res, cts):
    x, gamma, beta, mean, var = res
    do, dm_out, dv_out = cts  # mean/var outputs feed (stop-gradiented)
    #                           running-stat updates; usually zero cts
    red = _bn_red_axes(x.ndim, ax)
    n = float(np.prod([x.shape[i] for i in red]))
    shp = [1] * x.ndim
    shp[ax] = -1
    ivar = lax.rsqrt(var + eps)
    g32 = (jnp.ones_like(mean) if fix_gamma
           else gamma.astype(jnp.float32))
    xf = x.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    mean_b = mean.reshape(shp)
    # pass 1 (fused): dbeta and the centered correlation in one sweep
    dbeta = jnp.sum(dof, red)
    t = jnp.sum(dof * (xf - mean_b), red)
    dgamma = t * ivar
    # pass 2: dx = a*do + c*(x - mean) + b   (per-channel a, b, c);
    # external mean/var cotangents fold into the same form:
    # d mean/dx = 1/n, d var/dx = 2(x - mean)/n
    a = g32 * ivar
    c = -a * ivar * ivar * t / n + 2.0 * dv_out.astype(jnp.float32) / n
    b = -a * dbeta / n + dm_out.astype(jnp.float32) / n
    dx = (a.reshape(shp) * dof + c.reshape(shp) * (xf - mean_b)
          + b.reshape(shp)).astype(x.dtype)
    dgamma = (jnp.zeros_like(gamma) if fix_gamma
              else dgamma.astype(gamma.dtype))
    # the stat shift is an exact mathematical no-op (and comes from the
    # non-differentiable running mean): zero cotangent
    return dx, dgamma, dbeta.astype(beta.dtype), jnp.zeros_like(mean)


_bn_train_core.defvjp(_bn_train_vjp_fwd, _bn_train_vjp_bwd)


@register_op("BatchNormTrain", wrap=False, num_visible_outputs=3)
def batch_norm_train(data, gamma, beta, shift=None, eps=1e-5, axis=1,
                     fix_gamma=False, momentum=0.9):
    """Training-mode BN: returns (out, batch_mean, batch_var) with the
    fused 2-pass forward / 2-pass backward (reference
    src/operator/nn/batch_norm.cc computes the same batch stats; the
    running-stat EMA update stays in the Gluon layer). ``shift`` is a
    per-channel mean estimate (the running mean) that re-centers the
    one-pass variance against cancellation — exact-identity math."""
    ax = int(axis) % data.ndim
    if shift is None:
        shift = jnp.zeros(data.shape[ax], jnp.float32)
    return _bn_train_core(data, gamma, beta, shift, float(eps), ax,
                          bool(fix_gamma))


@register_op("BatchNorm", wrap=False)
def batch_norm(data, gamma, beta, mean, var, eps=1e-5, momentum=0.9,
               fix_gamma=True, use_global_stats=False, output_mean_var=False,
               axis=1, cudnn_off=False):
    """Normalize with the given stats (stat selection/update is done by
    the eager wrapper or the Gluon layer — see gluon/nn/basic_layers.py)."""
    ax = int(axis) % data.ndim
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    # stats/scale may be fp32 while data is bf16 (mixed precision: the
    # cudnn path does the same) — normalize in fp32, emit data's dtype
    x_hat = (data.astype(jnp.float32)
             - mean.astype(jnp.float32).reshape(shape)) * \
        lax.rsqrt(var.astype(jnp.float32).reshape(shape) + eps)
    out = x_hat * g.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register_op("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    ax = int(axis) % data.ndim
    # Pallas fused path (cuDNN-analog): last-axis norm, TPU dtypes only
    if (not output_mean_var and ax == data.ndim - 1
            and data.dtype in (jnp.float32, jnp.bfloat16, jnp.float16)):
        from ..ops import pallas as _pallas

        if _pallas.pallas_ok_for(data):
            return _pallas.layer_norm_fused(
                data, gamma, beta, float(eps))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    x_hat = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = x_hat * gamma.reshape(shape) + beta.reshape(shape)
    if output_mean_var:
        return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)
    return out


@register_op("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    ax = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) + beta.reshape(shape)


@register_op("GroupNorm")
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    n, c = data.shape[:2]
    g = int(num_groups)
    x = data.reshape((n, g, c // g) + data.shape[2:])
    ax = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=ax, keepdims=True)
    var = jnp.var(x, axis=ax, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


# ----------------------------------------------------------------------
# Dropout (src/operator/nn/dropout.cc) — functional RNG via random.py
# ----------------------------------------------------------------------
@register_op("Dropout", wrap=False)
def dropout(data, p=0.5, mode="training", axes=None, _training=True, _rng_key=None):
    if not _training and mode != "always":
        return data + 0
    if p <= 0.0:
        return data + 0
    if _rng_key is None:
        from .. import random as _random
        _rng_key = _random._next_key()
    shape = list(data.shape)
    if axes:
        for a in np.atleast_1d(axes):
            shape[int(a)] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(_rng_key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ----------------------------------------------------------------------
# Embedding (src/operator/tensor/indexing_op.cc Embedding)
# ----------------------------------------------------------------------
@register_op("Embedding")
def embedding(data, weight, input_dim=None, output_dim=None, dtype="float32",
              sparse_grad=False):
    idx = data.astype(jnp.int32)
    return jnp.take(weight, idx, axis=0)


# ----------------------------------------------------------------------
# losses as ops
# ----------------------------------------------------------------------
@register_op("MakeLoss")
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return data * 1.0


@register_op("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0):
    return _regression_out(data, label, grad_scale, "linear")


@register_op("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0):
    return _regression_out(data, label, grad_scale, "mae")


@register_op("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0):
    return _regression_out(data, label, grad_scale, "logistic")


def _regression_out(data, label, grad_scale, kind):
    @jax.custom_vjp
    def fwd(d, l):
        return jax.nn.sigmoid(d) if kind == "logistic" else d + 0

    def f(d, l):
        return fwd(d, l), (d, l)

    def b(res, g):
        d, l = res
        out = jax.nn.sigmoid(d) if kind == "logistic" else d
        if kind == "mae":
            grad = jnp.sign(out - l)
        else:
            grad = out - l
        return (grad * grad_scale / d.shape[0] * 1.0, jnp.zeros_like(l))

    fwd.defvjp(f, b)
    return fwd(data, label)


# ----------------------------------------------------------------------
# correlation-ish / misc nn
# ----------------------------------------------------------------------
@register_op("BilinearSampler")
def bilinear_sampler(data, grid, cudnn_off=False):
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    x1, y1 = x0 + 1, y0 + 1
    wx1 = gx - x0; wy1 = gy - y0
    wx0 = 1.0 - wx1; wy0 = 1.0 - wy1

    def sample(y, x):
        xi = jnp.clip(x, 0, w - 1).astype(jnp.int32)
        yi = jnp.clip(y, 0, h - 1).astype(jnp.int32)
        bidx = jnp.arange(n)[:, None, None]
        return data[bidx, :, yi, xi].transpose(0, 3, 1, 2)

    out = (sample(y0, x0) * (wy0 * wx0)[:, None] + sample(y0, x1) * (wy0 * wx1)[:, None]
           + sample(y1, x0) * (wy1 * wx0)[:, None] + sample(y1, x1) * (wy1 * wx1)[:, None])
    return out


@register_op("LRN", aliases=["lrn"])
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response normalization (reference src/operator/nn/lrn.cc —
    AlexNet-era cross-channel normalization):
    ``y = x / (knorm + alpha/nsize * sum_window x^2)^beta`` with the sum
    over an ``nsize`` channel window. TPU-first: the window sum is a
    conv-free cumulative-sum difference along C (one pass, XLA-fusable),
    not the reference's explicit channel loop."""
    n, c, h, w = data.shape
    half = int(nsize) // 2
    sq = (data * data).astype(jnp.float32)
    # windowed channel sum via padded cumsum difference
    cs = jnp.cumsum(jnp.pad(sq, ((0, 0), (half + 1, half), (0, 0), (0, 0))),
                    axis=1)
    win = (cs[:, nsize:] - cs[:, :-nsize])[:, :c]
    norm = (knorm + (alpha / nsize) * win) ** beta
    return (data.astype(jnp.float32) / norm).astype(data.dtype)


@register_op("ROIPooling")
def roi_pooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0):
    """ROI max pooling (reference src/operator/roi_pooling.cc).
    data (N,C,H,W); rois (R,5) rows ``[batch_idx, x1, y1, x2, y2]`` in
    image coordinates. TPU-first: per-bin membership masks reduce along
    H then W as two masked maxes (static shapes, no per-roi dynamic
    slicing — XLA sees one fused program for all rois)."""
    ph, pw = (int(p) for p in pooled_size)
    n, c, h, w = data.shape
    r = rois.shape[0]
    b = rois[:, 0].astype(jnp.int32)

    def _round_c(v):
        # std::round semantics (half away from zero) — jnp.round is
        # banker's rounding and disagrees at *.5 coordinates
        return (jnp.sign(v) * jnp.floor(jnp.abs(v) + 0.5)).astype(jnp.int32)

    x1 = _round_c(rois[:, 1] * spatial_scale)
    y1 = _round_c(rois[:, 2] * spatial_scale)
    x2 = _round_c(rois[:, 3] * spatial_scale)
    y2 = _round_c(rois[:, 4] * spatial_scale)
    rh = jnp.maximum(y2 - y1 + 1, 1).astype(jnp.float32)
    rw = jnp.maximum(x2 - x1 + 1, 1).astype(jnp.float32)

    def bin_mask(start, extent, nbins, size):
        # mask[r, i, s]: spatial index s inside bin i of roi r
        i = jnp.arange(nbins)[None, :, None].astype(jnp.float32)
        s = jnp.arange(size)[None, None, :]
        lo = start[:, None, None] + jnp.floor(i * extent[:, None, None] / nbins)
        hi = start[:, None, None] + jnp.ceil((i + 1) * extent[:, None, None] / nbins)
        # reference clips bins to the feature map and forces >=1 cell
        hi = jnp.maximum(hi, lo + 1)
        return (s >= lo) & (s < hi) & (s >= 0) & (s < size)

    mh = bin_mask(y1, rh, ph, h)          # (R, ph, H)
    mw = bin_mask(x1, rw, pw, w)          # (R, pw, W)
    xr = data.astype(jnp.float32)[b]      # (R, C, H, W)
    neg = jnp.float32(-3.4e38)
    t = jnp.where(mh[:, None, :, :, None], xr[:, :, None], neg)  # (R,C,ph,H,W)
    t = t.max(axis=3)                     # (R, C, ph, W)
    out = jnp.where(mw[:, None, None], t[:, :, :, None], neg).max(axis=4)
    # empty rois (all cells clipped away) return 0, matching reference
    out = jnp.where(out <= neg / 2, 0.0, out)
    return out.astype(data.dtype)


@register_op("GridGenerator")
def grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    """Affine/warp sampling-grid generation (reference
    src/operator/spatial_transformer.cc GridGenerator): produces the
    normalized (x, y) grid BilinearSampler consumes."""
    th, tw = (int(t) for t in target_shape)
    if transform_type == "affine":
        if th <= 0 or tw <= 0:
            raise ValueError("GridGenerator(transform_type='affine') "
                             "requires target_shape (reference: mandatory "
                             "param)")
        n = data.shape[0]
        theta = data.reshape(n, 2, 3).astype(jnp.float32)
        ys = jnp.linspace(-1.0, 1.0, th)
        xs = jnp.linspace(-1.0, 1.0, tw)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        src = jnp.stack([gx, gy, ones], 0).reshape(3, -1)   # (3, th*tw)
        out = jnp.einsum("nij,jk->nik", theta, src)          # (n, 2, th*tw)
        return out.reshape(n, 2, th, tw)
    if transform_type == "warp":
        # data is (n, 2, h, w) flow; add to the identity pixel grid and
        # normalize to [-1, 1]
        n, _, h, w = data.shape
        gy, gx = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
        fx = (data[:, 0] + gx).astype(jnp.float32)
        fy = (data[:, 1] + gy).astype(jnp.float32)
        nx = 2.0 * fx / jnp.maximum(w - 1, 1) - 1.0
        ny = 2.0 * fy / jnp.maximum(h - 1, 1) - 1.0
        return jnp.stack([nx, ny], 1)
    raise ValueError(f"unknown transform_type {transform_type!r}")


@register_op("SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear",
                        cudnn_off=False):
    """Spatial transformer network op (reference
    src/operator/spatial_transformer.cc): affine GridGenerator feeding
    the bilinear sampler, end-to-end differentiable."""
    if transform_type != "affine" or sampler_type != "bilinear":
        raise ValueError("SpatialTransformer supports affine/bilinear only "
                         "(matches the reference)")
    grid = grid_generator(loc, transform_type="affine",
                          target_shape=target_shape)
    return bilinear_sampler(data, grid)


# ----------------------------------------------------------------------
# Blocks of current open decoders — NEW ops, no reference analog: RMS
# normalisation, the SiLU-gated product, a depthwise causal short
# convolution, the gated delta rule (KDA) in chunks, and the experts a
# chip holds (ops/pallas/kda.py, ops/pallas/moe.py).
#
# The elementwise ones compute in float32 and return the input's type.
# Differentiated as written, jax would keep their float32 intermediates for
# the backward (two to four times the bfloat16 input, per op; 4.5 GB of a
# KDA mixer's 5.5 at 2 x 8,192 tokens): `_lean` keeps the inputs only and
# rebuilds the intermediates in the backward.
# ----------------------------------------------------------------------
def _lean(fn):
    @functools.wraps(fn)
    def wrapped(*arrays, **static):
        return jax.checkpoint(functools.partial(fn, **static))(*arrays)

    return wrapped


@register_op("RMSNorm")
@_lean
def rms_norm(data, gamma, eps=1e-5):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis, in float32."""
    x = data.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


@register_op("swiglu")
@_lean
def swiglu(data):
    """SiLU(first half) * second half of the last axis."""
    f = data.shape[-1] // 2
    return (jax.nn.silu(data[..., :f].astype(jnp.float32))
            * data[..., f:].astype(jnp.float32)).astype(data.dtype)


# the twin of `causal_conv1d` (at the end of this file; see the note there)
@_lean
def _causal_conv1d_twin(data, weight, bias=None, activation=None):
    """Depthwise causal convolution over time: data (B, S, C), weight
    (C, K); y_t = sum_i weight[:, i] * x_{t-(K-1)+i} (the last tap is the
    current token), zeros before the row's start, + ``bias`` (C,) where
    given. ``activation='silu'`` applies SiLU to the result. A sum of K
    shifted products in float32."""
    k = weight.shape[1]
    s = data.shape[1]
    x = jnp.pad(data.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = sum(x[:, i:i + s] * w[:, i] for i in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation == "silu":
        y = jax.nn.silu(y)
    elif activation is not None:
        raise ValueError(f"causal_conv1d: unknown activation {activation!r}")
    return y.astype(data.dtype)


@register_op("silu_mul")
@_lean
def silu_mul(gate, data):
    """SiLU(gate) * data, in float32; returns data's type."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * data.astype(jnp.float32)).astype(data.dtype)


@register_op("selective_scan")
def selective_scan_op(data, delta, a_log, b, c, skip):
    """Mamba's selective scan (ops/pallas/ssm.py): data u and delta (B, L, C),
    a_log (C, N), b and c (B, L, N), skip (C,). Per channel and state, in
    float32, h_t = exp(D_t A) h_{t-1} + D_t b_t u_t with D_t = softplus(delta_t)
    and A = -exp(a_log), h_0 = 0; returns y_t = sum_n c_t[n] h_t[:, n] +
    skip * u_t, (B, L, C) in data's type. On the chip a Pallas kernel pair
    with a hand-written backward (``mxtpu_ssm_fwd`` / ``mxtpu_ssm_bwd``: the
    state stays in VMEM, the backward rebuilds it a time block at a time);
    elsewhere a ``lax.scan`` over the tokens, differentiated by jax. The
    call runs under ``jax.named_scope("mxtpu_ssm")``."""
    from ..ops import pallas as _pallas
    from ..ops.pallas import ssm as _ssm

    use_kernel = (_pallas.pallas_ok_for(data)
                  and data.dtype in (jnp.float32, jnp.bfloat16))
    with jax.named_scope("mxtpu_ssm"):
        return _ssm.selective_scan(data, delta, a_log, b, c, skip,
                                   use_kernel=use_kernel)


@register_op("diff_attention_combine")
@_lean
def diff_attention_combine(first, second, lambda_q1, lambda_k1, lambda_q2,
                           lambda_k2, gamma, lambda_init=0.8, eps=1e-5):
    """Differential attention's combination (arXiv:2410.05258), float32:
    with lambda = exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2) +
    lambda_init, (1 - lambda_init) * RMSNorm(first - lambda * second) over
    the last axis with gain ``gamma``. ``first`` and ``second`` (B, H, S, 2d)
    are the two softmax maps' products with the paired values."""
    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(lambda_q1.astype(f32) * lambda_k1.astype(f32)))
           - jnp.exp(jnp.sum(lambda_q2.astype(f32) * lambda_k2.astype(f32)))
           + lambda_init)
    x = first.astype(f32) - lam * second.astype(f32)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * gamma.astype(f32) * (1.0 - lambda_init)).astype(first.dtype)


@register_op("kda_gate")
@_lean
def kda_gate(data, a_log, dt_bias, num_heads=1):
    """KDA's per-channel log-decay, float32: -exp(a_log[head]) *
    softplus(data + dt_bias). data (B, S, H*d), a_log (H,), dt_bias (H*d,)."""
    f32 = jnp.float32
    d = data.shape[-1] // num_heads
    rate = jnp.repeat(jnp.exp(a_log.astype(f32)), d)
    return -rate * jax.nn.softplus(data.astype(f32) + dt_bias.astype(f32))


@register_op("kda_chunked")
def kda_chunked_op(query, key, value, log_decay, beta, chunk_size=64,
                   scale=None, qk_l2norm=True):
    """Gated delta-rule linear attention over (B, H, S, d) heads, in chunks
    (ops/pallas/kda.py): state S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    S_{t-1} + beta_t k_t v_t^T, output S_t^T q_t * scale (default d_k^-1/2).
    ``log_decay`` (B, H, S, d_k) is float32 and <= 0; ``beta`` (B, H, S).
    ``qk_l2norm`` normalises q and k over d first (KDA's definition). On
    the chip both phases are Pallas kernel pairs with hand-written
    backwards: a chunk's terms in VMEM (``mxtpu_kda_chunk_fwd`` /
    ``mxtpu_kda_chunk_bwd``) and the chunk-to-chunk walk (``mxtpu_kda_fwd``
    / ``mxtpu_kda_bwd``); elsewhere ``jax.numpy`` and a ``lax.scan``,
    differentiated by jax."""
    from ..ops import pallas as _pallas
    from ..ops.pallas import kda as _kda

    if qk_l2norm:
        @jax.checkpoint
        def unit(x):
            x32 = x.astype(jnp.float32)
            return (x32 * lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True)
                                    + 1e-6)).astype(x.dtype)
        query, key = unit(query), unit(key)
    use_kernel = (_pallas.pallas_ok_for(query)
                  and query.dtype in (jnp.float32, jnp.bfloat16))
    return _kda.kda_chunked(query, key, value, log_decay, beta, scale=scale,
                            chunk_size=int(chunk_size), use_kernel=use_kernel)


@register_op("moe_experts_held")
def moe_experts_held(data, router_weight, gate_up, down, score_bias=None,
                     top_k=8, routed_scaling_factor=1.0, renormalize=True,
                     first_held=0, score="sigmoid"):
    """The part of a routed expert layer that the experts held here give
    (ops/pallas/moe.py): data (T, D); router_weight (E, D) over ALL experts,
    ``score`` (``sigmoid`` of each, or a ``softmax`` over them) in float32,
    the top ``top_k`` of score + score_bias (None: no bias);
    gate_up (E_held, 2F, D) and down (E_held, D, F) of the experts
    [first_held, first_held + E_held). Dropless at static shapes: a row
    buffer of T rows, or of twice the balanced load T * top_k * E_held / E
    where that is more (+ a tile an expert), and the
    dense branch of one ``lax.cond`` for a step that needs more. Returns
    (partial sum (T, D), [slots per held expert..., unplaced slots] float32
    (E_held + 1,): what the layer adds to its running count)."""
    from ..ops import pallas as _pallas
    from ..ops.pallas import moe as _moe

    ids, weights = _moe.route(data, router_weight, score_bias, int(top_k),
                              float(routed_scaling_factor), bool(renormalize),
                              score)
    use_kernel = (_pallas.pallas_ok_for(data)
                  and data.dtype in (jnp.float32, jnp.bfloat16))
    tokens, held = data.shape[0], gate_up.shape[0]
    balanced = tokens * int(top_k) * held // router_weight.shape[0]
    y, counts, unplaced = _moe.experts_held(
        data, ids, weights, gate_up, down, int(first_held),
        use_kernel=use_kernel, capacity_rows=max(tokens, 2 * balanced))
    seen = jnp.concatenate([counts, unplaced[None]]).astype(jnp.float32)
    return y, lax.stop_gradient(seen)


@register_op("rope")
@_lean
def rope(data, positions, theta=10000.0, sections=None):
    """Rotary positions, rotate-half, in float32: data (B, H, S, d) or (B, S,
    d); the pair (x[i], x[i + d/2]) of frequency i in [0, d/2) turns by
    theta^(-2i/d) * p(t). ``positions`` (B, S), or (B, n, S) for n position
    streams with ``sections`` (n counts that add up to d/2): frequency i
    reads the stream whose section holds i, sections side by side in order
    (the multimodal form; equal streams give plain rotary positions)."""
    half = data.shape[-1] // 2
    freq = jnp.asarray(float(theta) ** (-np.arange(half) / half), jnp.float32)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 3:
        if sections is None or sum(sections) != half \
                or len(sections) != pos.shape[1]:
            raise ValueError(f"rope: sections {sections!r} do not split the "
                             f"{half} frequencies over {pos.shape[1]} streams")
        stream = np.repeat(np.arange(len(sections)), sections)
        pos = jnp.moveaxis(pos[:, stream, :], 1, 2)            # (B, S, d/2)
    else:
        pos = pos[..., None]
    angle = pos * freq
    if data.ndim == 4:
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = data.astype(jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(data.dtype)


# ----------------------------------------------------------------------
# Sparse attention with a learned indexer (ops/pallas/dsa.py): the indexer's
# scores, the selection, the main attention over the selection with the head
# mean of its probabilities, and the indexer's loss. Each runs under the name
# scope a device trace finds it by, forward and backward.
# ----------------------------------------------------------------------
@register_op("dsa_index_scores")
def dsa_index_scores(query, key, weights):
    """The indexer's scores I[t, s] = (H_i d_i)^-1/2 sum_j weights[t, j]
    ReLU(query[t, j] . key[s]) in float32: query (B, H_i, S, d_i), key (B, S,
    d_i) one head for all, weights (B, S, H_i); (B, S, S), -inf above the
    diagonal. On the chip the Pallas kernels ``mxtpu_dsa_index_fwd`` and, for
    the backward, ``mxtpu_dsa_index_bwd_dq`` / ``_dk`` (the heads' products
    never leave VMEM); everywhere else query-row-blocked XLA. Scope
    ``mxtpu_dsa_index``."""
    from ..ops import pallas as _pallas
    from ..ops.pallas import dsa as _dsa

    use_kernel = (_pallas.pallas_ok_for(query)
                  and query.dtype in (jnp.float32, jnp.bfloat16))
    with jax.named_scope("mxtpu_dsa_index"):
        return _dsa.index_scores(query, key, weights, use_kernel=use_kernel)


@register_op("dsa_topk_mask")
def dsa_topk_mask(scores, top_k=2048):
    """Each query's min(top_k, t + 1) best causal keys by ``scores`` (B, S,
    S), the lower index first among equals: an int8 (B, S, S) mask, and
    [pairs kept, causal pairs] float32 (2,) for the layer's running tally.
    On the chip, at lengths its tiles divide, the Pallas kernel
    ``mxtpu_dsa_topk`` (a block of query rows' keys in VMEM; a counting pass
    stops at the block's last causal column and the passes stop once every
    row's count is met, a block whose rows all have at most ``top_k`` causal
    keys writes the causal mask, and the running count among equals runs
    only where a row has more equals than room); everywhere else
    query-row-blocked XLA. No gradient. Scope ``mxtpu_dsa_topk``."""
    from ..ops import pallas as _pallas
    from ..ops.pallas import dsa as _dsa

    b, s, _ = scores.shape
    use_kernel = _pallas.pallas_ok_for(scores) and scores.dtype == jnp.float32
    with jax.named_scope("mxtpu_dsa_topk"):
        mask = _dsa.topk_mask(scores, int(top_k), use_kernel=use_kernel)
        tally = jnp.stack([jnp.sum(mask, dtype=jnp.int32).astype(jnp.float32),
                           jnp.float32(b * (s * (s + 1) // 2))])
    return mask, tally


@register_op("dsa_attention")
def dsa_attention(query, key, value, pair_mask):
    """Causal attention over the pairs ``pair_mask`` (B, S, S) int8 keeps,
    grouped key/value heads as ``flash_attention``: the output (B, H, S, d)
    and p_bar (B, S, S) float32, the head mean of the probabilities on the
    kept pairs (no gradient through it). On the chip the flash kernel with
    the mask as an operand; p_bar is query-row-blocked XLA on every backend;
    scope ``mxtpu_dsa_attn``, p_bar's part of it ``mxtpu_dsa_pbar``."""
    from ..ops import pallas as _pallas
    from ..ops.pallas import dsa as _dsa

    scale = 1.0 / (query.shape[-1] ** 0.5)
    pair_mask = pair_mask.astype(jnp.int8)
    use_kernel = (_pallas.pallas_ok_for(query)
                  and query.dtype in (jnp.float32, jnp.bfloat16))
    with jax.named_scope("mxtpu_dsa_attn"):
        if use_kernel:
            out, lse = _pallas.flash_attention_with_lse(
                query, key, value, scale, True, 0, None, None, None, None,
                pair_mask)
        else:
            out, lse = _masked_attention_with_lse(query, key, value, pair_mask,
                                                  scale)
        with jax.named_scope("mxtpu_dsa_pbar"):
            p_bar = _dsa.head_mean_probs(query, key, lse, pair_mask,
                                         sm_scale=scale)
    return out, p_bar


def _masked_attention_with_lse(query, key, value, pair_mask, scale):
    """The jnp twin of the masked flash call: (out, lse (B, H, S))."""
    group = query.shape[1] // key.shape[1]
    key, value = (jnp.repeat(t, group, axis=1) for t in (key, value))
    s = jnp.einsum("bhqd,bhkd->bhqk", (query * scale).astype(query.dtype), key,
                   preferred_element_type=jnp.float32)
    seen = jnp.logical_and(pair_mask[:, None] != 0,
                           jnp.tril(jnp.ones(s.shape[-2:], bool)))
    s = jnp.where(seen, s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(seen, jnp.exp(s - lse[..., None]), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, value.astype(jnp.float32))
    return out.astype(query.dtype), lse


@register_op("dsa_index_loss")
def dsa_index_loss(scores, pair_mask, p_bar):
    """The indexer's loss, sum over the kept pairs of p_bar (log p_bar - log
    softmax_kept(scores)): a float32 scalar (no axes) whose gradient to
    ``scores`` is softmax_kept(scores) - p_bar. Scope ``mxtpu_dsa_index``."""
    from ..ops.pallas import dsa as _dsa

    with jax.named_scope("mxtpu_dsa_index"):
        return _dsa.index_loss(scores, pair_mask, p_bar)


# ----------------------------------------------------------------------
# `causal_conv1d` stands at the END of this file, away from its twin above:
# a Pallas call's serialized module carries the line numbers of the frames
# that called it, this file's ops among them, so a line added above
# `kda_chunked`, `moe_experts_held` or the `dsa_*` ops would re-key every
# compiled program that holds their kernels (PERF.md section 6, PRs 33, 36).
# ----------------------------------------------------------------------
@register_op("causal_conv1d")
def causal_conv1d(data, weight, bias=None, activation=None):
    """Depthwise causal convolution over time: data (B, S, C), weight
    (C, K); y_t = sum_i weight[:, i] * x_{t-(K-1)+i} (the last tap is the
    current token), zeros before the row's start, + ``bias`` (C,) where
    given. ``activation='silu'`` applies SiLU to the result. Float32
    arithmetic, the taps summed in the order i = 0 .. K-1; returns data's
    type. On the chip, for bfloat16 or float32 data whose channel count is
    a multiple of 128 and whose length a token block divides
    (``ops/pallas/conv1d.py`` ``tiles``), a Pallas kernel pair with a
    hand-written backward (``mxtpu_conv1d_fwd`` / ``mxtpu_conv1d_bwd``: x
    read once in its own layout, the K - 1 rows before a block carried in
    VMEM; the backward keeps the inputs only and sums the taps' and the
    bias's gradients in float32); everywhere else `_causal_conv1d_twin`, a
    sum of K shifted slices of a padded float32 copy, differentiated by jax
    under ``jax.checkpoint``. Both run under
    ``jax.named_scope("mxtpu_conv1d")`` and tally, as they are traced,
    ``conv1d_calls`` (and, the kernel taken, ``conv1d_kernel_calls``) in
    ``profiler.counters()``."""
    from .. import profiler as _profiler
    from ..ops import pallas as _pallas
    from ..ops.pallas import conv1d as _conv1d

    use_kernel = (_pallas.pallas_ok_for(data) and _conv1d.tiles(
        data.shape, weight.shape[1], data.dtype) is not None)
    _profiler.count("conv1d_calls")
    if use_kernel:
        _profiler.count("conv1d_kernel_calls")
    with jax.named_scope("mxtpu_conv1d"):
        if use_kernel:
            return _conv1d.causal_conv1d(data, weight, bias, activation)
        return _causal_conv1d_twin(data, weight, bias, activation=activation)
