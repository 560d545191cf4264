"""Plain reference for keye_vl2: Keye-VL-2.0's language model (Kwai-Keye,
``model_type`` KeyeVL2) + summed next-token cross-entropy + the indexer's
loss, straightforward ``jax.numpy`` in float32. No kernels, no bit tricks,
nothing of the program.

Pre-norm residual layers (h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h))),
a final RMSNorm, an untied head, no bias anywhere.

- Attention: q = W_q u (H heads of d), k = W_k u, v = W_v u (G heads; query
  head h reads key/value head h // (H / G)); q and k RMS-normalised a head
  with one gain each; rotary positions, rotate-half: frequency i in [0, d/2)
  is w_i = theta^(-2i/d) and turns the pair (x[i], x[i + d/2]) by w_i *
  p^j(t), j the position stream whose ``mrope_section`` holds i.
- Indexer, on stop_gradient(u): q_I = W_qI u (``indexer_num_heads`` heads of
  ``indexer_head_dim``), k_I = LayerNorm(W_kI u) (one key head), w = W_w u,
  q_I and k_I rotated the same way over all their columns (the sections
  halved with the width). The heads ONE BY ONE, in float32, outside ``lin``
  (the control's float8 does not reach the scores): I[t, s] = (heads *
  dim)^-1/2 sum_j w[t, j] ReLU(q_I[t, j] . k_I[s]) for s <= t.
- Selection: ``lax.top_k`` on the causal scores, min(topk, t + 1) keys a
  query, as an explicit boolean mask. ``selection`` ({layer: bool (B, S,
  S)}) replaces it where given (a test injects the program's own, to compare
  everything else tightly).
- A_h = softmax over the selected s of q_h[t] . k_g[s] / sqrt(d); o_h = A_h
  v_g; output W_o. Scores explicit, in blocks of query rows only so that
  they fit.
- Indexer loss: p_bar = stop_gradient(mean_h A_h); L_I = sum_t sum_selected
  p_bar (log p_bar - log softmax_selected(I[t]))), 0 log 0 = 0.
- Experts: g = softmax(W_r u) over ALL ``router_experts`` in float32; the
  top k; their weights over their sum (``norm_topk_prob``); y = sum over the
  chosen experts THAT ARE HELD HERE (``experts_held``) of g_e E_e(u), a loop
  over the held experts (a ``lax.scan``, as the indexer's heads are: sixteen
  unrolled copies of either cost minutes of compiling a layer). This chip's
  partial sum; no shared expert.

The step's loss: cross-entropy + L_I (weight 1), both summed. Rows are
independent, so the caller feeds one row a block. ``lin`` wraps every
matmul with a weight and the main attention's two products (``lowp.exact``
for the reference, ``lowp.fp8`` for the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS_INDEPENDENT = True
_Q_BLOCK = 256      # query rows per block of attention scores


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _mm(lin, x, w):
    """x (..., i) by w (o, i): the framework's (out, in) weight layout."""
    return lin(lambda a, m: jnp.einsum("...i,oi->...o", a, m))(x, w)


def _rotate(x, positions, theta, sections):
    """x (B, S, heads, d); positions (B, 3, S); ``sections`` frequencies a
    stream, side by side."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    stream = np.repeat(np.arange(len(sections)), sections)
    pos = jnp.moveaxis(positions.astype(jnp.float32)[:, stream, :], 1, 2)  # (B,S,half)
    angle = (pos * freq)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def _attention(cfg, lin, w, p, u, positions, selection=None):
    """(W_o output (B, S, D), the layer's indexer loss)."""
    b, s, _ = u.shape
    sa = cfg["sa_config"]
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sections = cfg["rope_scaling"]["mrope_section"]
    q = _rms(_mm(lin, u, w[p + "q_weight"]).reshape(b, s, h, d),
             w[p + "q_norm_gamma"], eps)
    k = _rms(_mm(lin, u, w[p + "k_weight"]).reshape(b, s, g, d),
             w[p + "k_norm_gamma"], eps)
    v = _mm(lin, u, w[p + "v_weight"]).reshape(b, s, g, d)
    q = _rotate(q, positions, theta, sections)
    k = _rotate(k, positions, theta, sections)

    ui = jax.lax.stop_gradient(u)
    index_sections = [n * di // d for n in sections]
    qi = _rotate(_mm(lin, ui, w[p + "index_q_weight"]).reshape(b, s, hi, di),
                 positions, theta, index_sections)
    ki = _layer_norm(_mm(lin, ui, w[p + "index_k_weight"]),
                     w[p + "index_k_norm_gamma"], w[p + "index_k_norm_beta"], eps)
    ki = _rotate(ki[:, :, None, :], positions, theta, index_sections)[:, :, 0]
    wi = _mm(lin, ui, w[p + "index_w_weight"])

    blk = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    pos = jnp.arange(s)
    group = h // g
    keep = min(topk, s)

    @jax.checkpoint
    def rows(first, q_blk, qi_blk, wi_blk, chosen_blk):
        causal = (first + jnp.arange(blk))[:, None] >= pos[None, :]     # (blk, S)
        # the indexer, head by head, float32
        def head(index, xs):        # one indexer head's term
            q_j, w_j = xs
            prod = jnp.einsum("bqd,bkd->bqk", q_j, ki)
            return index + w_j[..., None] * jax.nn.relu(prod), None

        index, _ = jax.lax.scan(head, jnp.zeros((b, blk, s), jnp.float32),
                                (jnp.moveaxis(qi_blk, 2, 0), jnp.moveaxis(wi_blk, 2, 0)))
        index = jnp.where(causal, index * (hi * di) ** -0.5, -jnp.inf)
        if chosen_blk is None:
            _, idx = jax.lax.top_k(jax.lax.stop_gradient(index), keep)
            chosen = jnp.zeros((b, blk, s), bool).at[
                jnp.arange(b)[:, None, None], jnp.arange(blk)[None, :, None],
                idx].set(True)
        else:
            chosen = chosen_blk
        chosen = jnp.logical_and(chosen, causal)
        # the main attention over the selection, every head's scores explicit
        kk = jnp.repeat(k, group, axis=2)
        vv = jnp.repeat(v, group, axis=2)
        scores = lin(lambda a, m: jnp.einsum("bqhd,bkhd->bhqk", a, m))(q_blk, kk) \
            * d ** -0.5
        probs = jax.nn.softmax(jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1)
        out = lin(lambda a, m: jnp.einsum("bhqk,bkhd->bqhd", a, m))(probs, vv)
        # the indexer's loss on the selection
        p_bar = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
        logp = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
        live = jnp.logical_and(chosen, p_bar > 0)
        term = jnp.where(live, p_bar * (jnp.log(jnp.where(live, p_bar, 1.0))
                                        - jnp.where(live, logp, 0.0)), 0.0)
        return out, jnp.sum(term)

    def split(a):
        return jnp.moveaxis(a.reshape((b, s // blk, blk) + a.shape[2:]), 1, 0)

    firsts = jnp.arange(0, s, blk)
    if selection is None:
        out, terms = jax.lax.map(lambda a: rows(*a, None),
                                 (firsts, split(q), split(qi), split(wi)))
    else:
        out, terms = jax.lax.map(lambda a: rows(*a),
                                 (firsts, split(q), split(qi), split(wi),
                                  split(jnp.asarray(selection, bool))))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)
    return _mm(lin, out, w[p + "o_weight"]), jnp.sum(terms)


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


def _layer(cfg, lin, w, i, x, positions, selection=None):
    p = f"keye_layer{i}_"
    eps = cfg["rms_norm_eps"]
    mixed, index_loss = _attention(cfg, lin, w, p + "attn_",
                                   _rms(x, w[p + "attn_norm_gamma"], eps),
                                   positions, selection)
    x = x + mixed
    return x + _experts(cfg, lin, w, p + "moe_",
                        _rms(x, w[p + "ffn_norm_gamma"], eps)), index_loss


def stages(cfg, lin, selection=None):
    """The model as a chain of pieces, ``[(leaf-name prefixes, fn)]`` with
    ``fn(weights of the piece, carry, batch) -> carry`` (``reference/
    kimi_linear.py``'s); the carry is (activation, indexer loss so far) and
    the last piece returns cross-entropy + indexer loss. ``batch`` = (ids,
    labels, positions (B, 3, S))."""
    lo, hi = cfg["layers_held"]

    def embed(w, carry, batch):
        return w["keye_embed_weight"][batch[0]], jnp.zeros((), jnp.float32)

    def layer(i):
        def fn(w, carry, batch):
            x, total = carry
            x, term = _layer(cfg, lin, w, i, x, batch[2],
                             None if selection is None else selection[i])
            return x, total + term
        return fn

    def head(w, carry, batch):
        x, total = carry
        x = _rms(x, w["keye_final_norm_gamma"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(_mm(lin, x, w["keye_head_weight"]), axis=-1)
        return total - jnp.sum(jnp.take_along_axis(logp, batch[1][..., None], axis=-1))

    return ([(("keye_embed_",), embed)]
            + [((f"keye_layer{i}_",), layer(i)) for i in range(lo, hi)]
            + [(("keye_final_norm_", "keye_head_"), head)])


def loss_sum(cfg, w, batch, lin, selection=None):
    x = None
    for prefixes, fn in stages(cfg, lin, selection):
        x = fn({k: v for k, v in w.items() if k.startswith(prefixes)}, x, batch)
    return x
