"""Plain reference for sdar: SDAR's decoder (JetLM, ``model_type`` sdar_moe)
trained by block diffusion (BD3-LM's objective), straightforward
``jax.numpy`` in float32. No kernels, no bit tricks, nothing of the program.

Pre-norm residual layers (h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h))),
a final RMSNorm, an untied head, no bias anywhere.

- The row: clean ids x0 (B, L) and their noised copy xt run as ONE sequence of
  2L positions [x0; xt], one embedding, both copies at ``positions`` (B, L).
- Attention: q = W_q u (H heads of d), k = W_k u, v = W_v u (G heads; query
  head h reads key/value head h // (H / G)); q and k RMS-normalised a head
  with one gain each; rotary positions, rotate-half: frequency i in [0, d/2)
  is w_i = theta^(-2i/d) and turns the pair (x[i], x[i + d/2]) by w_i * p.
- The mask, with blk(t) = (t mod L) // block (``_visible``): a clean query t
  sees the clean keys s with blk(s) <= blk(t); a noisy query the clean keys
  with blk(s) < blk(t) and the noisy keys with blk(s) = blk(t); a clean query
  no noisy key. Built explicitly, in blocks of query rows only so that the
  scores fit; A = softmax over the visible s of q . k / sqrt(d); o = A v; W_o.
- Experts: g = softmax(W_r u) over ALL ``router_experts`` in float32; the top
  k; their weights over their sum (``norm_topk_prob``); y = sum over the
  chosen experts THAT ARE HELD HERE (``experts_held``) of g_e E_e(u), a
  ``lax.scan`` over the held experts. This chip's partial sum; no shared
  expert.
- The loss: the final RMSNorm and the head over the noisy half; sum_i w_i
  (-log softmax(logits_i)[target_i]), the weights ``_loss_weights`` reads
  from the batch (1/t where a token was masked, else 0).

Rows are independent, so the caller feeds one row a block. ``lin`` wraps
every matmul with a weight and the attention's two products (``lowp.exact``
for the reference, ``lowp.fp8`` for the control). ``batch`` = (clean ids,
noised ids, positions, targets, weights), the arrays the program reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS_INDEPENDENT = True
_Q_BLOCK = 256      # query rows per block of attention scores


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _mm(lin, x, w):
    """x (..., i) by w (o, i): the framework's (out, in) weight layout."""
    return lin(lambda a, m: jnp.einsum("...i,oi->...o", a, m))(x, w)


def _rotate(x, positions, theta):
    """x (B, S, heads, d); positions (B, S)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (positions.astype(jnp.float32)[..., None] * freq)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def _visible(t, s, half, block):
    """The block-diffusion mask of query positions t (..., 1) and key
    positions s (1, ...) of the 2 * half positions, the clean copy first."""
    t_noisy, s_noisy = t >= half, s >= half
    bt = jnp.where(t_noisy, t - half, t) // block
    bs = jnp.where(s_noisy, s - half, s) // block
    clean_query = jnp.logical_and(jnp.logical_not(s_noisy), bs <= bt)
    noisy_query = jnp.where(s_noisy, bs == bt, bs < bt)
    return jnp.where(t_noisy, noisy_query, clean_query)


def _loss_weights(weights):
    return weights


def _attention(cfg, lin, w, p, u, positions):
    b, s, _ = u.shape                                   # s = 2L
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _rms(_mm(lin, u, w[p + "q_weight"]).reshape(b, s, h, d),
             w[p + "q_norm_gamma"], eps)
    k = _rms(_mm(lin, u, w[p + "k_weight"]).reshape(b, s, g, d),
             w[p + "k_norm_gamma"], eps)
    v = _mm(lin, u, w[p + "v_weight"]).reshape(b, s, g, d)
    q, k = _rotate(q, positions, theta), _rotate(k, positions, theta)
    blk = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    keys = jnp.arange(s)[None, :]
    kk = jnp.repeat(k, h // g, axis=2)
    vv = jnp.repeat(v, h // g, axis=2)

    @jax.checkpoint
    def rows(first, q_blk):
        seen = _visible(first + jnp.arange(blk)[:, None], keys, s // 2,
                        cfg["block_length"])                   # (blk, 2L)
        scores = lin(lambda a, m: jnp.einsum("bqhd,bkhd->bhqk", a, m))(q_blk, kk) \
            * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return lin(lambda a, m: jnp.einsum("bhqk,bkhd->bqhd", a, m))(probs, vv)

    split = jnp.moveaxis(q.reshape(b, s // blk, blk, h, d), 1, 0)
    out = jax.lax.map(lambda a: rows(*a), (jnp.arange(0, s, blk), split))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)
    return _mm(lin, out, w[p + "o_weight"])


def _gated(lin, x, gate_up, down):
    hcat = _mm(lin, x, gate_up)
    f = hcat.shape[-1] // 2
    return _mm(lin, jax.nn.silu(hcat[..., :f]) * hcat[..., f:], down)


def _experts(cfg, lin, w, p, x):
    top_k = cfg["num_experts_per_tok"]
    lo, hi = cfg["experts_held"]
    logits = jnp.einsum("...i,oi->...o", x, w[p + "router_weight"])     # float32, as is
    scores = jax.nn.softmax(logits, axis=-1)
    picked, chosen = jax.lax.top_k(scores, top_k)
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, -1, keepdims=True)

    @jax.checkpoint
    def held(y, xs):        # one held expert's term, one after the other
        e, gate_up, down = xs
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), -1, keepdims=True)
        return y + weight * _gated(lin, x, gate_up, down), None

    y, _ = jax.lax.scan(held, jnp.zeros_like(x),
                        (jnp.arange(lo, hi), w[p + "experts_gate_up_weight"],
                         w[p + "experts_down_weight"]))
    return y


def _layer(cfg, lin, w, i, x, positions):
    p = f"sdar_layer{i}_"
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, lin, w, p + "attn_", _rms(x, w[p + "attn_norm_gamma"], eps),
                       positions)
    return x + _experts(cfg, lin, w, p + "moe_", _rms(x, w[p + "ffn_norm_gamma"], eps))


def stages(cfg, lin):
    """The model as a chain of pieces, ``[(leaf-name prefixes, fn)]`` with
    ``fn(weights of the piece, carry, batch) -> carry`` (``reference/
    kimi_linear.py``'s); the carry is the activation of the 2L positions and
    the last piece returns the weighted loss."""
    lo, hi = cfg["layers_held"]

    def embed(w, carry, batch):
        return w["sdar_embed_weight"][jnp.concatenate(batch[:2], axis=1)]

    def layer(i):
        def fn(w, x, batch):
            return _layer(cfg, lin, w, i, x, jnp.concatenate([batch[2]] * 2, axis=1))
        return fn

    def head(w, x, batch):
        x = _rms(x[:, batch[0].shape[1]:], w["sdar_final_norm_gamma"],
                 cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(lin, x, w["sdar_head_weight"]), axis=-1)
        nll = -jnp.take_along_axis(logp, batch[3][..., None], axis=-1)[..., 0]
        return jnp.sum(_loss_weights(batch[4]) * nll)

    return ([(("sdar_embed_",), embed)]
            + [((f"sdar_layer{i}_",), layer(i)) for i in range(lo, hi)]
            + [(("sdar_final_norm_", "sdar_head_"), head)])


def loss_sum(cfg, w, batch, lin):
    x = None
    for prefixes, fn in stages(cfg, lin):
        x = fn({k: v for k, v in w.items() if k.startswith(prefixes)}, x, batch)
    return x
