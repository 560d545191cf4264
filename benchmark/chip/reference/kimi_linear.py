"""Plain reference for kimi_linear: the Kimi Linear decoder (moonshotai,
arXiv:2510.26692; ``model_type`` kimi_linear) + summed next-token
cross-entropy, straightforward ``jax.numpy`` in float32. No kernels, no
chunking, no sort, nothing of the program.

Pre-norm residual layers (h = x + Mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h))),
final RMSNorm, untied head, no positional encoding anywhere.

- KDA (``kda_layers``): q, k, v = SiLU(conv4(W x)) (depthwise, causal), q and
  k L2-normalised per head; decay a_t = exp(-exp(A_log) softplus(W_f2 W_f1 x
  + dt_bias)) per channel; beta_t = sigmoid(W_b x); the state walks TOKEN BY
  TOKEN, S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T,
  o_t = S_t^T q_t / sqrt(d_k) (a ``lax.scan`` over time, checkpointed in
  segments only so that it fits); output W_o [RMSNorm_head(o) * sigmoid(W_g2
  W_g1 x)].
- MLA (``full_attn_layers``): q = W_q x; [c, k_r] = W_kva x; [k_n, v] = W_kvb
  RMSNorm(c); k = [k_n, k_r for every head], never rotated (NoPE); causal
  softmax(q k^T / sqrt(192)) v, the scores taken in blocks of query rows only
  so that they fit; then W_o.
- Dense MLP (layers <= ``first_k_dense_replace``): W_d (SiLU(W_g x) * W_u x).
- Expert layer: s = sigmoid(W_r x) in float32 over ALL ``router_experts``;
  the top k of s + bias; weights s_i / sum_chosen s * scaling; y = sum over
  the chosen experts THAT ARE HELD HERE (``experts_held``) of w_i E_i(x), as a
  loop over the held experts with a mask, + the shared expert. The same share
  as the program: what the absent experts would add is left out.

Rows are independent (no auxiliary loss, no capacity), so the caller feeds
one row a block. ``lin`` wraps every matmul with a weight and the attention
products (``lowp.exact`` for the reference, ``lowp.fp8`` for the control);
the three products of the token recurrence stay float32 in the control too
(no float8 recipe quantises a recurrent state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS_INDEPENDENT = True
_SEGMENT = 128      # tokens per checkpointed stretch of the recurrence
_Q_BLOCK = 512      # query rows per block of attention scores


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _mm(lin, x, w):
    """x (..., i) by w (o, i): the framework's (out, in) weight layout."""
    return lin(lambda a, m: jnp.einsum("...i,oi->...o", a, m))(x, w)


def _conv_silu(x, w):
    """Depthwise causal convolution, taps (C, K), the last tap the current
    token; then SiLU. x (B, S, C)."""
    k = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, i:i + s] * w[:, i] for i in range(k)))


def _heads(x, h):
    b, s, _ = x.shape
    return x.reshape(b, s, h, -1)


def _delta_rule(q, k, v, log_a, beta):
    """Token by token. q, k, log_a (B, S, H, dk); v (B, S, H, dv); beta
    (B, S, H). Returns o (B, S, H, dv), unscaled."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = jnp.exp(a_t)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, (v_t - seen) * b_t[..., None])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def stretch(state, xs):
        return jax.lax.scan(token, state, xs)

    seg = _SEGMENT if s % _SEGMENT == 0 else s
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((s // seg, seg) + a.shape[:1] + a.shape[2:])
               for a in (q, k, v, log_a, beta))
    _, o = jax.lax.scan(stretch, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((s, b, h, dv)), 0, 1)


def _kda(cfg, lin, w, p, x):
    lc = cfg["linear_attn_config"]
    h, d = lc["num_heads"], lc["head_dim"]
    q = _heads(_conv_silu(_mm(lin, x, w[p + "q_weight"]), w[p + "qconv_weight"]), h)
    k = _heads(_conv_silu(_mm(lin, x, w[p + "k_weight"]), w[p + "kconv_weight"]), h)
    v = _heads(_conv_silu(_mm(lin, x, w[p + "v_weight"]), w[p + "vconv_weight"]), h)
    q = q * jax.lax.rsqrt(jnp.sum(jnp.square(q), -1, keepdims=True) + 1e-6)
    k = k * jax.lax.rsqrt(jnp.sum(jnp.square(k), -1, keepdims=True) + 1e-6)
    f = _mm(lin, _mm(lin, x, w[p + "f_a_weight"]), w[p + "f_b_weight"])
    rate = jnp.exp(w[p + "a_log"])[:, None]                            # (H, 1)
    log_a = -rate * jax.nn.softplus(_heads(f + w[p + "dt_bias"], h))
    beta = jax.nn.sigmoid(_mm(lin, x, w[p + "b_weight"]))               # (B, S, H)
    o = _delta_rule(q, k, v, log_a, beta) * d ** -0.5
    gate = jax.nn.sigmoid(_heads(
        _mm(lin, _mm(lin, x, w[p + "g_a_weight"]), w[p + "g_b_weight"]), h))
    o = _rms(o, w[p + "o_norm_gamma"], cfg["rms_norm_eps"]) * gate
    return _mm(lin, o.reshape(x.shape[:2] + (-1,)), w[p + "o_weight"])


def _mla(cfg, lin, w, p, x):
    b, s, _ = x.shape
    h = cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope = cfg["qk_rope_head_dim"]
    q = _heads(_mm(lin, x, w[p + "q_weight"]), h)                       # (B,S,H,192)
    kva = _mm(lin, x, w[p + "kva_weight"])
    latent, k_rope = kva[..., :rank], kva[..., rank:]
    kv = _heads(_mm(lin, _rms(latent, w[p + "kv_norm_gamma"], cfg["rms_norm_eps"]),
                    w[p + "kvb_weight"]), h)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    blk = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def rows(q_blk, first):
        scores = lin(lambda a, m: jnp.einsum("bqhd,bkhd->bhqk", a, m))(q_blk, k) * scale
        seen = (first + jnp.arange(blk))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return lin(lambda a, m: jnp.einsum("bhqk,bkhd->bqhd", a, m))(probs, v)

    # one block of query rows after the other (a lax.map: one block's scores
    # are live, in the forward and in the backward)
    q_blocks = jnp.moveaxis(q.reshape(b, s // blk, blk, h, nope + rope), 1, 0)
    out = jax.lax.map(lambda a: rows(*a), (q_blocks, jnp.arange(0, s, blk)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, -1)
    return _mm(lin, out, w[p + "o_weight"])


def _gated(lin, x, gate_up, down):
    hcat = _mm(lin, x, gate_up)
    f = hcat.shape[-1] // 2
    return _mm(lin, jax.nn.silu(hcat[..., :f]) * hcat[..., f:], down)


def _experts(cfg, lin, w, p, x):
    top_k = cfg["num_experts_per_token"]
    lo, hi = cfg["experts_held"]
    logits = jnp.einsum("...i,oi->...o", x, w[p + "router_weight"])     # float32, as is
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + w[p + "router_running_bias"], top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["moe_renormalize"]:
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    picked = picked * cfg["routed_scaling_factor"]
    y = _gated(lin, x, w[p + "shared_gate_up_weight"], w[p + "shared_down_weight"])
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(chosen == e, picked, 0.0), -1, keepdims=True)
        y = y + weight * _gated(lin, x, w[p + "experts_gate_up_weight"][e - lo],
                                w[p + "experts_down_weight"][e - lo])
    return y


def _layer(cfg, lin, w, i, x):
    p = f"kimi_layer{i}_"
    eps = cfg["rms_norm_eps"]
    lc = cfg["linear_attn_config"]
    h = _rms(x, w[p + "attn_norm_gamma"], eps)
    if i + 1 in lc["kda_layers"]:
        x = x + _kda(cfg, lin, w, p + "kda_", h)
    else:
        x = x + _mla(cfg, lin, w, p + "mla_", h)
    h = _rms(x, w[p + "ffn_norm_gamma"], eps)
    if i + 1 <= cfg["first_k_dense_replace"]:
        return x + _gated(lin, h, w[p + "mlp_gate_up_weight"], w[p + "mlp_down_weight"])
    return x + _experts(cfg, lin, w, p + "moe_", h)


def stages(cfg, lin):
    """The model as a chain of pieces, ``[(leaf-name prefixes, fn)]`` with
    ``fn(weights of the piece, carry, batch) -> carry`` (the first takes no
    carry, the last returns the summed loss): ``loss_sum`` is their
    composition, and a follower that cannot hold the whole backward at once
    (``train_lean``) differentiates them one by one."""
    def embed(w, x, batch):
        return w["kimi_embed_weight"][batch[0]]

    def layer(i):
        return lambda w, x, batch: _layer(cfg, lin, w, i, x)

    def head(w, x, batch):
        x = _rms(x, w["kimi_final_norm_gamma"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(lin, x, w["kimi_head_weight"]), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, batch[1][..., None], axis=-1))

    return ([(("kimi_embed_",), embed)]
            + [((f"kimi_layer{i}_",), layer(i))
               for i in range(cfg["num_hidden_layers"])]
            + [(("kimi_final_norm_", "kimi_head_"), head)])


def loss_sum(cfg, w, batch, lin):
    x = None
    for prefixes, fn in stages(cfg, lin):
        x = fn({k: v for k, v in w.items() if k.startswith(prefixes)}, x, batch)
    return x
