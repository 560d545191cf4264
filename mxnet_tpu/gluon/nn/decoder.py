"""Blocks of current open decoders: RMS normalisation, a SiLU-gated MLP, a
depthwise causal short convolution, and three mixers / layers that the
Kimi Linear family (arXiv:2510.26692) is made of — a KDA (gated delta-rule
linear attention) mixer, an MLA (latent attention) mixer, here also with
DeepSeek-V3's low-rank query and rotary positions (arXiv:2412.19437), and an
expert layer that is told which experts it holds — and the three that SambaY
(Phi-4-mini-flash, arXiv:2507.06607) is made of: a Mamba selective-scan
mixer, differential attention (arXiv:2410.05258; self, windowed or full, or
cross to another layer's keys and values) with grouped key/value heads, and
a Gated Memory Unit that reuses another layer's scan output; grouped-query
attention with rotary positions over the keys a learned indexer selects
(DeepSeek-V3.2-Exp's, as Keye-VL-2.0's language model has it); and, at this
file's end, DeepSeek-V3's multi-token prediction module and grouped-query
attention over all its keys under a block-diffusion mask.

No upstream-gluon analog. Layout (batch, seq, units); pre-norm residual
wiring is the model's (gluon/model_zoo/). Every block is a HybridBlock over
registered ``F.`` ops (ndarray/op_impl_nn.py): a hybridized root traces one
program and per-layer remat applies."""
from __future__ import annotations

import math

import numpy as np

from .basic_layers import Dense
from .transformer import _merge_heads, _split_heads
from ..block import HybridBlock, defer_aux_update
from ... import initializer as _init

__all__ = ["RMSNorm", "GatedMLP", "CausalConv1D", "KDAMixer", "MLAMixer",
           "HeldExperts", "MambaMixer", "DiffAttention", "GatedMemoryUnit",
           "SparseGQAttention", "MTPModule", "GQAttention"]


def _linear(units, in_units, dtype, init, prefix):
    return Dense(units, flatten=False, use_bias=False, dtype=dtype,
                 weight_initializer=init, in_units=in_units, prefix=prefix)


def _cast_keeping(block, dtype, kept):
    """``block.cast(dtype)`` that leaves the parameters ``kept`` as they are
    (float32 statistics and device tallies)."""
    for child in block._children.values():
        child.cast(dtype)
    for _, param in block.params.items():
        if not any(param is k for k in kept):
            param.cast(dtype)
    block._cached_graph = {}


class RMSNorm(HybridBlock):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis."""

    def __init__(self, in_channels, epsilon=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._epsilon)


class GatedMLP(HybridBlock):
    """W_d (SiLU(W_g x) * W_u x); gate and up are one matrix (2F, D), rows
    [0, F) the gate."""

    def __init__(self, units, hidden_size, dtype="float32",
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.gate_up = _linear(2 * hidden_size, units, dtype,
                                   weight_initializer, "gate_up_")
            self.down = _linear(units, hidden_size, dtype, weight_initializer,
                                "down_")

    def hybrid_forward(self, F, x):
        return self.down(F.swiglu(self.gate_up(x)))


class CausalConv1D(HybridBlock):
    """Depthwise causal convolution over time, (B, S, C) -> (B, S, C), with
    an optional SiLU: token t sees tokens t-K+1 .. t of its own channel."""

    def __init__(self, channels, kernel_size=4, activation="silu",
                 weight_initializer=None, use_bias=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._activation = activation
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(channels, kernel_size),
                                          init=weight_initializer)
            self.bias = self.params.get("bias", shape=(channels,),
                                        init="zeros") if use_bias else None

    def hybrid_forward(self, F, x, weight, bias=None):
        return F.causal_conv1d(x, weight, bias, activation=self._activation)


class KDAMixer(HybridBlock):
    """Kimi Delta Attention: per head a d_k x d_v state under the gated
    delta rule, position carried by the state (no positional encoding).

    q, k, v = SiLU(conv4(W x)), q and k L2-normalised per head; per-channel
    decay exp(-exp(A_log) softplus(W_f2 W_f1 x + dt_bias)); write strength
    sigmoid(W_b x); output W_o [RMSNorm_head(o) * sigmoid(W_g2 W_g1 x)].
    Computed in chunks (``F.kda_chunked``)."""

    def __init__(self, units, num_heads, head_dim, conv_kernel=4, chunk_size=64,
                 epsilon=1e-5, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._chunk = num_heads, chunk_size
        inner = num_heads * head_dim
        init = weight_initializer
        with self.name_scope():
            self.q_proj = _linear(inner, units, dtype, init, "q_")
            self.k_proj = _linear(inner, units, dtype, init, "k_")
            self.v_proj = _linear(inner, units, dtype, init, "v_")
            self.q_conv = CausalConv1D(inner, conv_kernel, weight_initializer=init,
                                       prefix="qconv_")
            self.k_conv = CausalConv1D(inner, conv_kernel, weight_initializer=init,
                                       prefix="kconv_")
            self.v_conv = CausalConv1D(inner, conv_kernel, weight_initializer=init,
                                       prefix="vconv_")
            self.f_a = _linear(head_dim, units, dtype, init, "f_a_")
            self.f_b = _linear(inner, head_dim, dtype, init, "f_b_")
            self.b_proj = _linear(num_heads, units, dtype, init, "b_")
            self.g_a = _linear(head_dim, units, dtype, init, "g_a_")
            self.g_b = _linear(inner, head_dim, dtype, init, "g_b_")
            self.o_norm = RMSNorm(head_dim, epsilon, prefix="o_norm_")
            self.o_proj = _linear(units, inner, dtype, init, "o_")
            self.a_log = self.params.get("a_log", shape=(num_heads,), init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(inner,), init="zeros")

    def hybrid_forward(self, F, x, a_log, dt_bias):
        h = self._heads
        q = _split_heads(F, self.q_conv(self.q_proj(x)), h)
        k = _split_heads(F, self.k_conv(self.k_proj(x)), h)
        v = _split_heads(F, self.v_conv(self.v_proj(x)), h)
        decay = F.kda_gate(self.f_b(self.f_a(x)), a_log, dt_bias, num_heads=h)
        beta = F.transpose(F.sigmoid(self.b_proj(x)), axes=(0, 2, 1))
        o = F.kda_chunked(q, k, v, _split_heads(F, decay, h), beta,
                          chunk_size=self._chunk)
        gate = F.sigmoid(_split_heads(F, self.g_b(self.g_a(x)), h))
        return self.o_proj(_merge_heads(F, self.o_norm(o) * gate))


class MLAMixer(HybridBlock):
    """Multi-head latent attention: q = W_q x (heads x (nope + rope)), or with
    ``q_lora_rank`` W_qb RMSNorm(W_qa x); [c, k_r] = W_kva x; [k_n, v] = W_kvb
    RMSNorm(c); k = [k_n, k_r shared by the heads]; with ``rope_theta`` the rope
    columns of q and k_r turn by ``positions`` (B, S) in interleaved pairs, else
    never (NoPE); causal softmax(q k^T / sqrt(nope + rope)) v (flash); W_o."""

    def __init__(self, units, num_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, epsilon=1e-5, dtype="float32", weight_initializer=None,
                 prefix=None, params=None, q_lora_rank=None, rope_theta=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._rank, self._theta = num_heads, kv_lora_rank, rope_theta
        self._nope, self._rope, self._v = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
        init = weight_initializer
        with self.name_scope():   # `_mla_query`, `_rotate_pe`: at this file's end
            self.q_proj = _mla_query(num_heads * (self._nope + self._rope), units,
                                     q_lora_rank, epsilon, dtype, init)
            self.kv_a = _linear(kv_lora_rank + self._rope, units, dtype, init, "kva_")
            self.kv_norm = RMSNorm(kv_lora_rank, epsilon, prefix="kv_norm_")
            self.kv_b = _linear(num_heads * (self._nope + self._v), kv_lora_rank,
                                dtype, init, "kvb_")
            self.o_proj = _linear(units, num_heads * self._v, dtype, init, "o_")

    def hybrid_forward(self, F, x, positions=None):
        h = self._heads
        q = _split_heads(F, self.q_proj(x), h)                   # (B,H,S,192)
        kva = self.kv_a(x)
        latent = F.slice_axis(kva, axis=-1, begin=0, end=self._rank)
        k_rope = F.slice_axis(kva, axis=-1, begin=self._rank, end=None)
        q, k_rope = _rotate_pe(F, q, k_rope, positions, self._nope, self._theta)
        kv = _split_heads(F, self.kv_b(self.kv_norm(latent)), h)  # (B,H,S,256)
        k_nope = F.slice_axis(kv, axis=-1, begin=0, end=self._nope)
        v = F.slice_axis(kv, axis=-1, begin=self._nope, end=None)
        k_rope = F.broadcast_axis(F.expand_dims(k_rope, axis=1), axis=1, size=h)
        k = F.concat(k_nope, k_rope, dim=-1)
        out = F.flash_attention(q, k, v, causal=True)
        return self.o_proj(_merge_heads(F, out))


class HeldExperts(HybridBlock):
    """A routed expert layer as ONE chip of an expert-parallel deployment
    computes it: it is told which experts it holds.

    The router is as wide as the model (``num_experts``, scores in float32,
    top ``top_k``, chosen scores normalised and scaled). Two scores:
    ``score="sigmoid"`` (each expert's own sigmoid; the top ``top_k`` of
    score + a selection bias, ``router_running_bias``: the DeepSeek-V3
    family's) and ``score="softmax"`` (a softmax over all the experts, the
    top ``top_k`` of it, no bias and no such parameter: the Qwen3-MoE
    family's). ``experts_held = (lo, hi)`` names the contiguous range of
    experts whose weights live here. The layer computes the chosen terms
    whose expert is held, adds the shared expert, and returns that PARTIAL
    sum on purpose: what the other chips' experts add is their work, and
    nothing here stands in for them or for the exchange. With the default
    ``experts_held=None`` every expert is held and the sum is whole.

    Dropless at static shapes (``F.moe_experts_held``): a sorted row buffer
    of as many rows as tokens, or twice the balanced load where that is more,
    and a dense masked branch inside the same program for a step that needs
    more. ``running_slots``
    (hi - lo + 1,) counts on the device, without a host read, the slots each
    held expert was sent and, last, the slots the branch taken left out (a
    check of the routing tables: 0);
    ``profiler.counters()`` reads it when polled. The score bias is a
    statistic moved by the balancing rule, not by a gradient
    (``router_running_bias``, ``grad_req='null'``); both stay float32."""

    def __init__(self, units, hidden_size, num_experts, top_k, experts_held=None,
                 num_shared_experts=1, routed_scaling_factor=1.0,
                 renormalize=True, score="sigmoid", dtype="float32",
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if score not in ("sigmoid", "softmax"):
            raise ValueError(f"score {score!r} is neither sigmoid nor softmax")
        lo, hi = experts_held if experts_held is not None else (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"experts_held {experts_held!r} is no range of "
                             f"{num_experts} experts")
        self._lo, self._held = lo, hi - lo
        self._kw = dict(top_k=top_k, routed_scaling_factor=routed_scaling_factor,
                        renormalize=renormalize, first_held=lo, score=score)
        init = weight_initializer
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units), dtype=dtype, init=init)
            self.router_running_bias = self.params.get(
                "router_running_bias", shape=(num_experts,), init="zeros",
                grad_req="null") if score == "sigmoid" else None
            self.running_slots = self.params.get(
                "running_slots", shape=(self._held + 1,), init="zeros",
                grad_req="null")
            self.experts_gate_up_weight = self.params.get(
                "experts_gate_up_weight", shape=(self._held, 2 * hidden_size, units),
                dtype=dtype, init=init)
            self.experts_down_weight = self.params.get(
                "experts_down_weight", shape=(self._held, units, hidden_size),
                dtype=dtype, init=init)
            self.shared = GatedMLP(units, hidden_size * num_shared_experts, dtype,
                                   init, prefix="shared_") \
                if num_shared_experts else None
        from ... import profiler
        profiler.register_device_counters(self)

    def cast(self, dtype):
        _cast_keeping(self, dtype, (self.router_running_bias, self.running_slots))

    def device_counters(self):
        """{counter: number} from ``running_slots`` (a host read: for the
        operator's poll, never inside a step)."""
        if self.running_slots._data is None:
            return {}
        tally = sum(a.asnumpy().astype("float64")
                    for a in self.running_slots.list_data())
        return {"moe_slots": float(tally[:-1].sum()),
                "moe_dropped": float(tally[-1]),
                "moe_slots/" + self.name: [float(v) for v in tally[:-1]]}

    def hybrid_forward(self, F, x, router_weight, running_slots,
                       experts_gate_up_weight, experts_down_weight,
                       router_running_bias=None):
        flat = F.reshape(x, shape=(-3, 0))                     # (B*S, D)
        bias = () if router_running_bias is None else (router_running_bias,)
        routed, seen = F.moe_experts_held(
            flat, router_weight, experts_gate_up_weight, experts_down_weight,
            *bias, **self._kw)
        defer_aux_update(self.running_slots, seen, increment=True)
        out = F.reshape_like(routed, x)
        if self.shared is not None:
            out = out + self.shared(x)
        return out


class _MambaALog(_init.Initializer):
    """A_log[c, n] = log(n + 1): Mamba's S4D-real start."""

    def __call__(self, desc, arr):
        self._set(arr, np.broadcast_to(
            np.log(np.arange(1, arr.shape[1] + 1, dtype=np.float64)), arr.shape))


class _MambaDtBias(_init.Initializer):
    """A step bias whose softplus is log-uniform in [1e-3, 1e-1] (Mamba's
    dt_min / dt_max): the inverse softplus of the draw."""

    def __call__(self, desc, arr):
        dt = np.exp(_init._np_rng().uniform(math.log(1e-3), math.log(1e-1),
                                            arr.shape))
        self._set(arr, dt + np.log(-np.expm1(-dt)))


class MambaMixer(HybridBlock):
    """Mamba's mixer (arXiv:2312.00752): [u, z] = W_in x; u = SiLU(conv(u) +
    b) (depthwise, causal); [d, B, C] = W_x u; delta = W_dt d + b_dt; s =
    ``F.selective_scan`` (softplus(delta), A = -exp(A_log), the skip D u
    included); output W_out (s * SiLU(z)). Returns ``(output, s)``: ``s`` (B,
    S, d_inner), the scan's output before the gate, is what a Gated Memory
    Unit further up reads."""

    def __init__(self, units, d_inner, d_state=16, d_conv=4, dt_rank=None,
                 dtype="float32", weight_initializer=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        dt_rank = dt_rank or -(-units // 16)
        self._inner, self._state, self._rank = d_inner, d_state, dt_rank
        init = weight_initializer
        with self.name_scope():
            self.in_proj = _linear(2 * d_inner, units, dtype, init, "in_")
            self.conv = CausalConv1D(d_inner, d_conv, weight_initializer=init,
                                     use_bias=True, prefix="conv_")
            self.x_proj = _linear(dt_rank + 2 * d_state, d_inner, dtype, init,
                                  "x_")
            self.dt_proj = Dense(d_inner, flatten=False, dtype=dtype,
                                 weight_initializer=init,
                                 bias_initializer=_MambaDtBias(),
                                 in_units=dt_rank, prefix="dt_")
            self.out_proj = _linear(units, d_inner, dtype, init, "out_")
            self.a_log = self.params.get("a_log", shape=(d_inner, d_state),
                                         init=_MambaALog())
            self.d = self.params.get("d", shape=(d_inner,), init="ones")

    def hybrid_forward(self, F, x, a_log, d):
        uz = self.in_proj(x)
        u = self.conv(F.slice_axis(uz, axis=-1, begin=0, end=self._inner))
        z = F.slice_axis(uz, axis=-1, begin=self._inner, end=None)
        dbc = self.x_proj(u)
        r, n = self._rank, self._state
        delta = self.dt_proj(F.slice_axis(dbc, axis=-1, begin=0, end=r))
        b = F.slice_axis(dbc, axis=-1, begin=r, end=r + n)
        c = F.slice_axis(dbc, axis=-1, begin=r + n, end=None)
        s = F.selective_scan(u, delta, a_log, b, c, d)
        return self.out_proj(F.silu_mul(z, s)), s


class GatedMemoryUnit(HybridBlock):
    """SambaY's Gated Memory Unit: W_out (m * SiLU(W_in x)), where ``m`` is
    another layer's memory at the same positions (the source Mamba layer's
    scan output). No scan and no convolution of its own."""

    def __init__(self, units, d_inner, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_proj = _linear(d_inner, units, dtype, weight_initializer, "in_")
            self.out_proj = _linear(units, d_inner, dtype, weight_initializer,
                                    "out_")

    def hybrid_forward(self, F, x, m):
        return self.out_proj(F.silu_mul(self.in_proj(x), m))


class DiffAttention(HybridBlock):
    """Differential attention (arXiv:2410.05258) with grouped key/value
    heads and no positional encoding. ``num_heads`` query heads of
    ``head_dim`` form num_heads / 2 pairs (q1_p, q2_p), ``num_kv_heads`` key
    heads num_kv_heads / 2 pairs (k1_g, k2_g), and the values num_kv_heads /
    2 paired values V_g of 2 * head_dim; pair p reads group g = p // (pairs
    a group). o_p = (1 - lambda_init) * RMSNorm((A1_p - lambda A2_p) V_g)
    with A^i = softmax(q^i k^i^T / sqrt(head_dim) + mask), lambda =
    exp(lambda_q1 . lambda_k1) - exp(lambda_q2 . lambda_k2) + lambda_init;
    then W_o. Two ``F.flash_attention`` calls (A1 V and A2 V), each with
    half the query heads reading half as many key/value heads again.

    Columns: the projection's q columns are [q1 of every pair, q2 of every
    pair], k's [k1 of every group, k2 of every group], v's the groups' paired
    values one after the other.

    ``window``: causal sliding window (a token sees itself and the window -
    1 before it); None: full causal. ``cross=True``: only q is projected
    here; ``k`` (B, num_kv_heads, S, head_dim) and ``v`` (B, num_kv_heads /
    2, S, 2 * head_dim) are a source layer's, handed in. A self layer returns
    ``(output, k, v)`` so that it can be that source."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, lambda_init,
                 window=None, cross=False, epsilon=1e-5, dtype="float32",
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % 2 or num_kv_heads % 2 or num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query and {num_kv_heads} key/value "
                             "heads do not pair and group")
        self._h, self._hk, self._d = num_heads, num_kv_heads, head_dim
        self._window, self._cross = window, cross
        self._lambda_init, self._eps = float(lambda_init), epsilon
        self._trace_scope = "mxtpu_swa" if window is not None else "mxtpu_yoco"
        init = weight_initializer
        wide = num_heads * head_dim if cross \
            else (num_heads + 2 * num_kv_heads) * head_dim
        with self.name_scope():
            self.in_proj = Dense(wide, flatten=False, dtype=dtype,
                                 weight_initializer=init, in_units=units,
                                 prefix="q_" if cross else "qkv_")
            self.o_proj = Dense(units, flatten=False, dtype=dtype,
                                weight_initializer=init,
                                in_units=num_heads * head_dim, prefix="o_")
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                setattr(self, name, self.params.get(
                    name, shape=(head_dim,), init=_init.Normal(0.1)))
            self.subln_gamma = self.params.get(
                "subln_gamma", shape=(2 * head_dim,), init="ones")

    def hybrid_forward(self, F, x, k=None, v=None, lambda_q1=None,
                       lambda_k1=None, lambda_q2=None, lambda_k2=None,
                       subln_gamma=None):
        h, hk, d = self._h, self._hk, self._d
        proj = self.in_proj(x)
        if self._cross:
            q = _split_heads(F, proj, h)
        else:
            q = _split_heads(F, F.slice_axis(proj, axis=-1, begin=0, end=h * d), h)
            k = _split_heads(F, F.slice_axis(proj, axis=-1, begin=h * d,
                                             end=(h + hk) * d), hk)
            v = _split_heads(F, F.slice_axis(proj, axis=-1, begin=(h + hk) * d,
                                             end=None), hk // 2)
        halves = []
        for i in range(2):
            halves.append(F.flash_attention(
                F.slice_axis(q, axis=1, begin=i * h // 2, end=(i + 1) * h // 2),
                F.slice_axis(k, axis=1, begin=i * hk // 2, end=(i + 1) * hk // 2),
                v, causal=True, window=self._window, name_scope=self._trace_scope))
        out = F.diff_attention_combine(
            halves[0], halves[1], lambda_q1, lambda_k1, lambda_q2, lambda_k2,
            subln_gamma, lambda_init=self._lambda_init, eps=self._eps)
        out = self.o_proj(_merge_heads(F, out))
        return out if self._cross else (out, k, v)


class SparseGQAttention(HybridBlock):
    """Grouped-query attention with rotary positions over the keys a learned
    indexer selects (DeepSeek-V3.2-Exp's sparse attention, sparse stage).

    Main attention: q = W_q x (``num_heads`` of ``head_dim``), k = W_k x, v =
    W_v x (``num_kv_heads``; query head h reads key/value head h // group); q
    and k RMS-normalised a head (one gain of ``head_dim`` each), then rotated
    (``F.rope``: rotate-half, ``rope_theta``, three position streams over
    ``mrope_section``). Indexer, on ``stop_gradient(x)``: q_i = W_qi x
    (``index_heads`` of ``index_dim``), k_i = LayerNorm(W_ki x) (one key head
    for all), w = W_w x, q_i and k_i rotated over all their columns (the
    sections halved with the width); I[t, s] = (heads * dim)^-1/2 sum_j w[t,
    j] ReLU(q_i[t, j] . k_i[s]) in float32. Query t keeps its min(``top_k``,
    t + 1) best causal keys (``F.dsa_topk_mask``); the main attention runs
    over the kept pairs (``F.dsa_attention``: the flash kernel with the
    mask as an operand), then W_o.

    ``positions`` (B, 3, S). Returns ``(output, index_loss)``: the scalar
    sum over the kept pairs of p_bar (log p_bar - log softmax_kept(I)), p_bar
    the detached head mean of the main attention's probabilities. The two
    losses do not meet: the indexer reads a detached input, so the model's
    loss gives its parameters nothing, and its own loss reaches no other.
    ``running_pairs`` (2,) counts on the device [pairs kept, causal pairs]
    (``profiler.counters()``: ``dsa_pairs_selected``, ``dsa_pairs_causal``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, index_heads,
                 index_dim, top_k, rope_theta=10000.0, mrope_section=None,
                 epsilon=1e-6, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_kv_heads} key/value heads do not divide "
                             f"{num_heads} query heads")
        sections = tuple(mrope_section or (head_dim // 2, 0, 0))
        shrink = head_dim // index_dim
        if sum(sections) != head_dim // 2 or head_dim % index_dim \
                or any(n % shrink for n in sections):
            raise ValueError(f"mrope_section {sections} does not split "
                             f"{head_dim // 2} frequencies, or not {index_dim // 2}")
        self._h, self._hk, self._hi = num_heads, num_kv_heads, index_heads
        self._top_k, self._eps = top_k, epsilon
        self._rope = dict(theta=rope_theta, sections=sections)
        self._index_rope = dict(theta=rope_theta,
                                sections=tuple(n // shrink for n in sections))
        init = weight_initializer
        with self.name_scope():
            self.q_proj = _linear(num_heads * head_dim, units, dtype, init, "q_")
            self.k_proj = _linear(num_kv_heads * head_dim, units, dtype, init, "k_")
            self.v_proj = _linear(num_kv_heads * head_dim, units, dtype, init, "v_")
            self.o_proj = _linear(units, num_heads * head_dim, dtype, init, "o_")
            self.iq_proj = _linear(index_heads * index_dim, units, dtype, init,
                                   "index_q_")
            self.ik_proj = _linear(index_dim, units, dtype, init, "index_k_")
            self.iw_proj = _linear(index_heads, units, dtype, init, "index_w_")
            self.q_norm_gamma = self.params.get("q_norm_gamma", shape=(head_dim,),
                                                init="ones")
            self.k_norm_gamma = self.params.get("k_norm_gamma", shape=(head_dim,),
                                                init="ones")
            self.index_k_norm_gamma = self.params.get(
                "index_k_norm_gamma", shape=(index_dim,), init="ones")
            self.index_k_norm_beta = self.params.get(
                "index_k_norm_beta", shape=(index_dim,), init="zeros")
            self.running_pairs = self.params.get(
                "running_pairs", shape=(2,), init="zeros", grad_req="null")
        from ... import profiler
        profiler.register_device_counters(self)

    def cast(self, dtype):
        _cast_keeping(self, dtype, (self.running_pairs,))

    def device_counters(self):
        """{counter: number} from ``running_pairs`` (a host read: for the
        operator's poll, never inside a step)."""
        if self.running_pairs._data is None:
            return {}
        tally = sum(a.asnumpy().astype("float64")
                    for a in self.running_pairs.list_data())
        return {"dsa_pairs_selected": float(tally[0]),
                "dsa_pairs_causal": float(tally[1])}

    def hybrid_forward(self, F, x, positions, q_norm_gamma, k_norm_gamma,
                       index_k_norm_gamma, index_k_norm_beta, running_pairs):
        from ... import profiler
        profiler.count("dsa_layers")      # trace time: flat across steps
        q = F.RMSNorm(_split_heads(F, self.q_proj(x), self._h), q_norm_gamma,
                      eps=self._eps)
        k = F.RMSNorm(_split_heads(F, self.k_proj(x), self._hk), k_norm_gamma,
                      eps=self._eps)
        v = _split_heads(F, self.v_proj(x), self._hk)
        q = F.rope(q, positions, **self._rope)
        k = F.rope(k, positions, **self._rope)
        # the indexer: nothing of the model's loss reaches it, and nothing of
        # its loss the model
        xi = F.stop_gradient(x)
        qi = F.rope(_split_heads(F, self.iq_proj(xi), self._hi), positions,
                    **self._index_rope)
        ki = F.rope(F.LayerNorm(self.ik_proj(xi), index_k_norm_gamma,
                                index_k_norm_beta, eps=self._eps),
                    positions, **self._index_rope)
        scores = F.dsa_index_scores(qi, ki, self.iw_proj(xi))
        mask, tally = F.dsa_topk_mask(scores, top_k=self._top_k)
        defer_aux_update(self.running_pairs, tally, increment=True)
        out, p_bar = F.dsa_attention(q, k, v, mask)
        loss = F.dsa_index_loss(scores, mask, p_bar)
        return self.o_proj(_merge_heads(F, out)), loss


# ----------------------------------------------------------------------
# What DeepSeek-V3's family adds (JoyAI-LLM-Flash's config): MLA's low-rank
# query and its decoupled rotary positions, and the multi-token prediction
# module. At this file's end, so that no line of a block above moves: a
# Pallas call's compiled module carries the lines of the frames that called
# it (PERF.md section 6, PR 36).
# ----------------------------------------------------------------------
class _LowRankQuery(HybridBlock):
    """MLA's query through a latent: W_qb RMSNorm(W_qa x), ``rank`` wide."""

    def __init__(self, units, in_units, rank, epsilon, dtype, init, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.a = _linear(rank, in_units, dtype, init, "a_")
            self.norm = RMSNorm(rank, epsilon, prefix="norm_")
            self.b = _linear(units, rank, dtype, init, "b_")

    def hybrid_forward(self, F, x):
        return self.b(self.norm(self.a(x)))


def _mla_query(units, in_units, rank, epsilon, dtype, init):
    """``MLAMixer``'s query map, parameters under ``q_``: one matrix, or
    with ``rank`` the low-rank pair (``q_a_weight``, ``q_norm_gamma``,
    ``q_b_weight``)."""
    if rank is None:
        return _linear(units, in_units, dtype, init, "q_")
    return _LowRankQuery(units, in_units, rank, epsilon, dtype, init, prefix="q_")


def _rotate_pe(F, q, k_rope, positions, nope, theta):
    """MLA's decoupled rotary positions: the columns after the first ``nope``
    of every query head (q (B, H, S, nope + rope)) and the key part all heads
    share (k_rope (B, S, rope)) turn by ``positions`` (B, S), interleaved
    pairs (DeepSeek-V3's ``rope_interleave``); the nope columns do not.
    ``theta`` None: both as they are (NoPE). Tallies ``mla_rope_calls`` in
    ``profiler.counters()`` as it is traced, once a mixer call."""
    if theta is None:
        return q, k_rope
    from ... import profiler
    profiler.count("mla_rope_calls")
    turn = dict(theta=theta, interleave=True)
    q_pe = F.rope(F.slice_axis(q, axis=-1, begin=nope, end=None), positions, **turn)
    q = F.concat(F.slice_axis(q, axis=-1, begin=0, end=nope), q_pe, dim=-1)
    return q, F.rope(k_rope, positions, **turn)


class MTPModule(HybridBlock):
    """One multi-token prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2): from the main stack's last hidden state h_i and the next
    token t_{i+1}, h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})); RMSNorm_h(h_i)]
    (2 units -> units), then one decoder layer and an RMSNorm; the model's
    head predicts t_{i+2}.

    It holds ``enorm``, ``hnorm``, ``eh_proj``, ``layer`` (made by
    ``make_layer(prefix)``: a block ``layer(x, positions) -> x``) and
    ``final_norm``. It BORROWS the model's embedding, ``embed(ids)``, and
    head, ``head_loss(F, x, labels, mask)`` -> the summed cross-entropy of
    the rows whose mask is 1: they stay the model's own blocks and
    parameters, neither children of this one nor copies. ``mtp(h, next_ids,
    positions, labels, mask)`` -> that loss (1,). Tallies ``mtp_modules`` in
    ``profiler.counters()`` as it is traced."""

    def __init__(self, units, make_layer, embed, head_loss, epsilon=1e-6,
                 dtype="float32", weight_initializer=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._borrowed = (embed, head_loss)   # a tuple: no child registered
        with self.name_scope():
            self.enorm = RMSNorm(units, epsilon, prefix="enorm_")
            self.hnorm = RMSNorm(units, epsilon, prefix="hnorm_")
            self.eh_proj = _linear(units, 2 * units, dtype, weight_initializer, "eh_")
            self.layer = make_layer("layer_")
            self.final_norm = RMSNorm(units, epsilon, prefix="final_norm_")

    def hybrid_forward(self, F, h, next_ids, positions, labels, mask):
        from ... import profiler
        profiler.count("mtp_modules")
        embed, head_loss = self._borrowed
        x = self.eh_proj(F.concat(self.enorm(embed(next_ids)), self.hnorm(h), dim=-1))
        return head_loss(F, self.final_norm(self.layer(x, positions)), labels, mask)


# ----------------------------------------------------------------------
# Grouped-query attention over all its keys (the Qwen3-MoE family's layer)
# under block diffusion's mask; at this file's end for the reason above.
# ----------------------------------------------------------------------
class GQAttention(HybridBlock):
    """q = W_q x (``num_heads`` of ``head_dim``), k = W_k x, v = W_v x
    (``num_kv_heads``; query head h reads key/value head h // group); q and k
    RMS-normalised a head (one gain of ``head_dim`` each), then rotated
    (``F.rope``: rotate-half over all the columns, ``rope_theta``, one
    position stream, ``positions`` (B, S)); softmax(q k^T / sqrt(head_dim) +
    M) v (flash); W_o. No bias.

    M is the block-diffusion mask of blocks of ``block_diffusion`` = β
    (BD3-LM, Arriola et al. 2025): x (B, 2L, D) holds a clean copy of each row
    and its noised copy, the clean first, both at positions 0 .. L - 1; a
    clean token sees the clean tokens of its own and earlier blocks, a noisy
    token the clean tokens of earlier blocks and the noisy tokens of its own
    block (``F.flash_attention(block_diffusion=β)``, under the name scope
    ``mxtpu_bdiff``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 block_diffusion, rope_theta=10000.0, epsilon=1e-6,
                 dtype="float32", weight_initializer=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_kv_heads} key/value heads do not divide "
                             f"{num_heads} query heads")
        self._h, self._hk, self._eps = num_heads, num_kv_heads, epsilon
        self._theta, self._block = rope_theta, block_diffusion
        init = weight_initializer
        with self.name_scope():
            self.q_proj = _linear(num_heads * head_dim, units, dtype, init, "q_")
            self.k_proj = _linear(num_kv_heads * head_dim, units, dtype, init, "k_")
            self.v_proj = _linear(num_kv_heads * head_dim, units, dtype, init, "v_")
            self.o_proj = _linear(units, num_heads * head_dim, dtype, init, "o_")
            self.q_norm_gamma = self.params.get("q_norm_gamma", shape=(head_dim,),
                                                init="ones")
            self.k_norm_gamma = self.params.get("k_norm_gamma", shape=(head_dim,),
                                                init="ones")

    def hybrid_forward(self, F, x, positions, q_norm_gamma, k_norm_gamma):
        q = F.RMSNorm(_split_heads(F, self.q_proj(x), self._h), q_norm_gamma,
                      eps=self._eps)
        k = F.RMSNorm(_split_heads(F, self.k_proj(x), self._hk), k_norm_gamma,
                      eps=self._eps)
        v = _split_heads(F, self.v_proj(x), self._hk)
        q = F.rope(q, positions, theta=self._theta)
        k = F.rope(k, positions, theta=self._theta)
        out = F.flash_attention(q, k, v, block_diffusion=self._block,
                                name_scope="mxtpu_bdiff")
        return self.o_proj(_merge_heads(F, out))
