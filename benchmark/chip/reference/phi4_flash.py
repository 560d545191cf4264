"""Plain reference for phi4_flash: the SambaY decoder-hybrid-decoder of
Phi-4-mini-flash (microsoft, ``model_type`` phi4flash; arXiv:2507.06607) with
differential attention (arXiv:2410.05258) + summed next-token cross-entropy,
straightforward ``jax.numpy`` in float32. No kernels, no chunking, no
grouped-head index tricks, nothing of the program.

Every layer: h = x + Mixer(LN(x)); y = h + MLP(LN(h)), LayerNorm with gain
and bias; MLP = W_d (SiLU(W_g x) * W_u x); a final LayerNorm; logits = LN(x)
E^T with E the embedding (tied); no positional encoding anywhere. The kind of
the published 0-based layer l of N (written out here, not imported): l < N/2:
even -> Mamba, odd -> attention with a window; l = N/2 -> Mamba, the memory
source; l = N/2 + 1 -> full attention, the key/value source; later: even ->
Gated Memory Unit, odd -> cross-attention.

- Mamba: [u, z] = W_in x; u = SiLU(conv4(u) + b) (depthwise, causal); [d, B,
  C] = W_x u; D = softplus(W_dt d + b_dt); A = -exp(A_log); the state walks
  TOKEN BY TOKEN, h_t = exp(D_t A) h_{t-1} + D_t B_t u_t, s_t = sum_n C_t[n]
  h_t[:, n] + D u_t (a ``lax.scan`` over time, checkpointed in segments only
  so that it fits); output W_out (s * SiLU(z)). The source layer hands on s.
- Gated Memory Unit: W_out (m * SiLU(W_in x)), m the source's s.
- Differential attention: [q, k, v] = W_qkv x + b; q's columns are [q1 of
  the 20 pairs, q2 of the 20 pairs], k's [k1 of the 10 groups, k2 of the 10
  groups], v's the 10 groups' paired values (128 wide) one after the other;
  pair p reads group p // 2. A^i = softmax(q^i k^i^T / sqrt(64) + mask) as
  explicit score matrices, in blocks of query rows only so that they fit;
  lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l); o_p = (1 -
  lambda_init(l)) RMSNorm_128((A1_p - lambda A2_p) V_g); W_o, + b. The mask
  is causal, and for a window layer also t - j < window. The key/value source
  hands on its k and v; a cross layer projects q only and reads them.

Rows are independent, so the caller feeds one row a block. ``lin`` wraps
every matmul with a weight and the attention products (``lowp.exact`` for the
reference, ``lowp.fp8`` for the control); the scan's recurrence stays float32
in the control too (no float8 recipe quantises a recurrent state).

The embedding is read twice, by the gather and by the head. So that a
follower that differentiates the ``stages`` one by one (``train_lean``) sums
both gradients, the first stage puts E into the carry and the last reads it
from there: the carry's cotangent brings the head's share back to the one
leaf.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS_INDEPENDENT = True
_SEGMENT = 128      # tokens per checkpointed stretch of the recurrence
_Q_BLOCK = 512      # query rows per block of attention scores


def _kind(l, n, mb):
    if l < n // 2:
        return "mamba" if l % mb == 0 else "window"
    if l == n // 2:
        return "mamba_source"
    if l == n // 2 + 1:
        return "attention_source"
    return "gmu" if l % mb == 0 else "cross"


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _mm(lin, x, w):
    """x (..., i) by w (o, i): the framework's (out, in) weight layout."""
    return lin(lambda a, m: jnp.einsum("...i,oi->...o", a, m))(x, w)


def _scan(u, dt, a, b, c):
    """Token by token. u, dt (B, S, C); a (C, N); b, c (B, S, N). Returns
    sum_n c_t[n] h_t[:, n], (B, S, C)."""
    rows, s, ch = u.shape

    def token(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t[:, :, None] * a) * h
             + dt_t[:, :, None] * b_t[:, None, :] * u_t[:, :, None])
        return h, jnp.einsum("bcn,bn->bc", h, c_t)

    @jax.checkpoint
    def stretch(h, xs):
        return jax.lax.scan(token, h, xs)

    seg = _SEGMENT if s % _SEGMENT == 0 else s
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((s // seg, seg) + x.shape[:1]
                                             + x.shape[2:])
               for x in (u, dt, b, c))
    _, y = jax.lax.scan(stretch, jnp.zeros((rows,) + a.shape, jnp.float32), xs)
    return jnp.moveaxis(y.reshape(s, rows, ch), 0, 1)


def _mamba(cfg, lin, w, p, x):
    ci = cfg["mamba_expand"] * cfg["hidden_size"]
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    uz = _mm(lin, x, w[p + "in_weight"])
    u, z = uz[..., :ci], uz[..., ci:]
    taps = w[p + "conv_weight"]
    k, s = taps.shape[1], x.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(up[:, i:i + s] * taps[:, i] for i in range(k))
                    + w[p + "conv_bias"])
    dbc = _mm(lin, u, w[p + "x_weight"])
    dt = jax.nn.softplus(_mm(lin, dbc[..., :r], w[p + "dt_weight"])
                         + w[p + "dt_bias"])
    y = _scan(u, dt, -jnp.exp(w[p + "a_log"]), dbc[..., r:r + n],
              dbc[..., r + n:]) + w[p + "d"] * u
    return _mm(lin, y * jax.nn.silu(z), w[p + "out_weight"]), y


def _gmu(lin, w, p, x, m):
    return _mm(lin, m * jax.nn.silu(_mm(lin, x, w[p + "in_weight"])),
               w[p + "out_weight"])


def _diff_attention(cfg, lin, w, p, l, x, window, kv=None):
    rows, s, _ = x.shape
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    pairs, groups = h // 2, hk // 2
    if kv is None:
        qkv = _mm(lin, x, w[p + "qkv_weight"]) + w[p + "qkv_bias"]
        q = qkv[..., :h * d]
        k = qkv[..., h * d:(h + hk) * d].reshape(rows, s, 2, groups, d)
        v = qkv[..., (h + hk) * d:].reshape(rows, s, groups, 2 * d)
    else:
        q = _mm(lin, x, w[p + "q_weight"]) + w[p + "q_bias"]
        k, v = kv
    q = q.reshape(rows, s, 2, pairs, d)
    # pair p reads group p // (pairs a group): written out as a repeat
    k_p = jnp.repeat(k, pairs // groups, axis=3)
    v_p = jnp.repeat(v, pairs // groups, axis=2)
    init = 0.8 - 0.6 * math.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(w[p + "lambda_q1"] * w[p + "lambda_k1"]))
           - jnp.exp(jnp.sum(w[p + "lambda_q2"] * w[p + "lambda_k2"])) + init)
    blk = _Q_BLOCK if s % _Q_BLOCK == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(q_blk, first):
        scores = lin(lambda a, m: jnp.einsum("bqiph,bkiph->bipqk", a, m))(
            q_blk, k_p) * d ** -0.5
        t = (first + jnp.arange(blk))[:, None]
        seen = t >= pos[None, :]
        if window is not None:
            seen = seen & (t - pos[None, :] < window)
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        diff = maps[:, 0] - lam * maps[:, 1]                     # (B, P, q, k)
        return lin(lambda a, m: jnp.einsum("bpqk,bkph->bqph", a, m))(diff, v_p)

    # one block of query rows after the other (a lax.map: one block's score
    # matrices are live, in the forward and in the backward)
    q_blocks = jnp.moveaxis(q.reshape(rows, s // blk, blk, 2, pairs, d), 1, 0)
    o = jax.lax.map(lambda a: block(*a), (q_blocks, jnp.arange(0, s, blk)))
    o = jnp.moveaxis(o, 0, 1).reshape(rows, s, pairs, 2 * d)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg["layer_norm_eps"]) * w[p + "subln_gamma"]
    o = (1.0 - init) * o.reshape(rows, s, -1)
    return _mm(lin, o, w[p + "o_weight"]) + w[p + "o_bias"], (k, v)


def _layer(cfg, lin, w, l, carry):
    p = f"phi_layer{l}_"
    eps = cfg["layer_norm_eps"]
    kind = _kind(l, cfg["published_layers"], cfg["mb_per_layer"])
    x = carry["x"]
    h = _ln(x, w[p + "mixer_norm_gamma"], w[p + "mixer_norm_beta"], eps)
    carry = dict(carry)
    if kind in ("mamba", "mamba_source"):
        mixed, scanned = _mamba(cfg, lin, w, p + "mamba_", h)
        if kind == "mamba_source":
            carry["m"] = scanned
    elif kind == "gmu":
        mixed = _gmu(lin, w, p + "gmu_", h, carry["m"])
    elif kind == "cross":
        mixed, _ = _diff_attention(cfg, lin, w, p + "attn_", l, h, None,
                                   kv=carry["kv"])
    else:
        mixed, kv = _diff_attention(
            cfg, lin, w, p + "attn_", l, h,
            cfg["sliding_window"] if kind == "window" else None)
        if kind == "attention_source":
            carry["kv"] = kv
    x = x + mixed
    h = _ln(x, w[p + "mlp_norm_gamma"], w[p + "mlp_norm_beta"], eps)
    hcat = _mm(lin, h, w[p + "mlp_gate_up_weight"])
    f = hcat.shape[-1] // 2
    carry["x"] = x + _mm(lin, jax.nn.silu(hcat[..., :f]) * hcat[..., f:],
                         w[p + "mlp_down_weight"])
    return carry


def stages(cfg, lin):
    """The model as a chain of pieces, ``[(leaf-name prefixes, fn)]`` with
    ``fn(weights of the piece, carry, batch) -> carry`` (the first takes no
    carry, the last returns the summed loss): ``loss_sum`` is their
    composition, and a follower that cannot hold the whole backward at once
    (``train_lean``) differentiates them one by one. The carry is a dict: the
    stream ``x``, the embedding ``embed`` (module docstring), and, from their
    source layers on, the memory ``m`` and the keys and values ``kv``."""
    def embed(w, carry, batch):
        e = w["phi_embed_weight"]
        return {"x": e[batch[0]], "embed": e}

    def layer(l):
        return lambda w, carry, batch: _layer(cfg, lin, w, l, carry)

    def head(w, carry, batch):
        x = _ln(carry["x"], w["phi_final_norm_gamma"], w["phi_final_norm_beta"],
                cfg["layer_norm_eps"])
        logp = jax.nn.log_softmax(_mm(lin, x, carry["embed"]), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, batch[1][..., None], axis=-1))

    lo, hi = cfg["layers_held"]
    return ([(("phi_embed_",), embed)]
            + [((f"phi_layer{l}_",), layer(l)) for l in range(lo, hi)]
            + [(("phi_final_norm_",), head)])


def loss_sum(cfg, w, batch, lin):
    x = None
    for prefixes, fn in stages(cfg, lin):
        x = fn({k: v for k, v in w.items() if k.startswith(prefixes)}, x, batch)
    return x
