"""Plain reference for bert_base: BERT (Devlin et al. 2018) encoder + MLM
head + summed token cross-entropy, straightforward ``jax.numpy`` in float32.
No kernels, no cache, nothing of the program. Post-LN blocks, exact (erf)
GELU, learned position and segment embeddings, biased LayerNorm variance.
Departures (as the configuration's file lists them): loss over every
position; no dropout; decoder weights NOT tied to the word embedding (the
program's ``BERTMLMHead`` has its own decoder matrix).

``loss_sum(weights, batch, lin)`` gives the summed loss of the rows in
``batch``; rows are independent, so the caller may feed blocks of rows and
add losses and gradients. ``lin`` wraps every matmul (``lowp.exact`` for the
reference, ``lowp.fp8`` for the control)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS_INDEPENDENT = True


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _dense(lin, x, w, b):
    return lin(lambda a, m: jnp.einsum("...i,oi->...o", a, m))(x, w) + b


def _layer(cfg, lin, w, p, x):
    B, S, H = x.shape
    A = cfg["num_attention_heads"]
    D = H // A
    eps = cfg["layer_norm_eps"]
    qkv = _dense(lin, x, w[p + "attn_qkv_weight"], w[p + "attn_qkv_bias"])
    q, k, v = (t.reshape(B, S, A, D).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = lin(lambda a, m: jnp.einsum("bhqd,bhkd->bhqk", a, m))(q, k) \
        / jnp.sqrt(jnp.float32(D))
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = lin(lambda a, m: jnp.einsum("bhqk,bhkd->bhqd", a, m))(probs, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H)
    h = _dense(lin, ctx, w[p + "attn_out_weight"], w[p + "attn_out_bias"])
    x = _ln(x + h, w[p + "attn_ln_gamma"], w[p + "attn_ln_beta"], eps)
    h = _dense(lin, x, w[p + "ffn_ffn1_weight"], w[p + "ffn_ffn1_bias"])
    h = jax.nn.gelu(h, approximate=False)
    h = _dense(lin, h, w[p + "ffn_ffn2_weight"], w[p + "ffn_ffn2_bias"])
    return _ln(x + h, w[p + "ffn_ln_gamma"], w[p + "ffn_ln_beta"], eps)


def loss_sum(cfg, w, batch, lin):
    ids, token_types, labels = batch
    eps = cfg["layer_norm_eps"]
    S = ids.shape[1]
    x = (w["bert_embed_word_weight"][ids]
         + w["bert_embed_type_weight"][token_types]
         + w["bert_embed_pos_weight"][:S][None])
    x = _ln(x, w["bert_embed_ln_gamma"], w["bert_embed_ln_beta"], eps)
    for i in range(cfg["num_hidden_layers"]):
        # per-layer checkpoint: only so that a block of rows fits the chip
        x = jax.checkpoint(
            lambda x_, w_, p=f"bert_enc_layer{i}_": _layer(cfg, lin, w_, p, x_)
        )(x, w)
    h = _dense(lin, x, w["head_transform_weight"], w["head_transform_bias"])
    h = jax.nn.gelu(h, approximate=False)
    h = _ln(h, w["head_ln_gamma"], w["head_ln_beta"], eps)
    logits = _dense(lin, h, w["head_decoder_weight"], w["head_decoder_bias"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.sum(picked)
