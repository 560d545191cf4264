"""Blocks of current open decoders: RMS normalisation, a SiLU-gated MLP, a
depthwise causal short convolution, and three mixers / layers that the
Kimi Linear family (arXiv:2510.26692) is made of — a KDA (gated delta-rule
linear attention) mixer, an MLA (latent attention, no positional encoding)
mixer, and an expert layer that is told which experts it holds.

No upstream-gluon analog. Layout (batch, seq, units); pre-norm residual
wiring is the model's (gluon/model_zoo/kimi_linear.py). Every block is a
HybridBlock over registered ``F.`` ops (ndarray/op_impl_nn.py), so a
hybridized root traces one program and per-layer remat applies.
"""
from __future__ import annotations

from .basic_layers import Dense
from .transformer import _merge_heads, _split_heads
from ..block import HybridBlock, defer_aux_update

__all__ = ["RMSNorm", "GatedMLP", "CausalConv1D", "KDAMixer", "MLAMixer",
           "HeldExperts"]


def _linear(units, in_units, dtype, init, prefix):
    return Dense(units, flatten=False, use_bias=False, dtype=dtype,
                 weight_initializer=init, in_units=in_units, prefix=prefix)


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
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._activation = activation
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(channels, kernel_size),
                                          init=weight_initializer)

    def hybrid_forward(self, F, x, weight):
        return F.causal_conv1d(x, weight, activation=self._activation)


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
    """Multi-head latent attention without positional encoding (NoPE):
    q = W_q x (heads x (nope + rope)); [c, k_r] = W_kva x; [k_n, v] =
    W_kvb RMSNorm(c); k = [k_n, k_r shared by the heads]; causal
    softmax(q k^T / sqrt(nope + rope)) v through ``F.flash_attention``
    (q.k width 192, v width 128 at the published sizes); then W_o. The
    "rope" columns are kept at their width and never rotated."""

    def __init__(self, units, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, epsilon=1e-5, dtype="float32",
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._rank = num_heads, kv_lora_rank
        self._nope, self._rope, self._v = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
        init = weight_initializer
        with self.name_scope():
            self.q_proj = _linear(num_heads * (self._nope + self._rope), units,
                                  dtype, init, "q_")
            self.kv_a = _linear(kv_lora_rank + self._rope, units, dtype, init, "kva_")
            self.kv_norm = RMSNorm(kv_lora_rank, epsilon, prefix="kv_norm_")
            self.kv_b = _linear(num_heads * (self._nope + self._v), kv_lora_rank,
                                dtype, init, "kvb_")
            self.o_proj = _linear(units, num_heads * self._v, dtype, init, "o_")

    def hybrid_forward(self, F, x):
        h = self._heads
        q = _split_heads(F, self.q_proj(x), h)                   # (B,H,S,192)
        kva = self.kv_a(x)
        latent = F.slice_axis(kva, axis=-1, begin=0, end=self._rank)
        k_rope = F.slice_axis(kva, axis=-1, begin=self._rank, end=None)
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

    The router is as wide as the model (``num_experts``, sigmoid scores in
    float32, top ``top_k`` of score + bias, chosen scores normalised and
    scaled). ``experts_held = (lo, hi)`` names the contiguous range of
    experts whose weights live here. The layer computes the chosen terms
    whose expert is held, adds the shared expert, and returns that PARTIAL
    sum on purpose: what the other chips' experts add is their work, and
    nothing here stands in for them or for the exchange. With the default
    ``experts_held=None`` every expert is held and the sum is whole.

    Dropless at static shapes (``F.moe_experts_held``): a sorted row buffer
    of as many rows as tokens, and a dense masked branch inside the same
    program for a step that needs more. ``running_slots``
    (hi - lo + 1,) counts on the device, without a host read, the slots each
    held expert was sent and, last, the slots the branch taken left out (a
    check of the routing tables: 0);
    ``profiler.counters()`` reads it when polled. The score bias is a
    statistic moved by the balancing rule, not by a gradient
    (``router_running_bias``, ``grad_req='null'``); both stay float32."""

    def __init__(self, units, hidden_size, num_experts, top_k, experts_held=None,
                 num_shared_experts=1, routed_scaling_factor=1.0,
                 renormalize=True, dtype="float32",
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        lo, hi = experts_held if experts_held is not None else (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"experts_held {experts_held!r} is no range of "
                             f"{num_experts} experts")
        self._lo, self._held = lo, hi - lo
        self._kw = dict(top_k=top_k, routed_scaling_factor=routed_scaling_factor,
                        renormalize=renormalize, first_held=lo)
        init = weight_initializer
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units), dtype=dtype, init=init)
            self.router_running_bias = self.params.get(
                "router_running_bias", shape=(num_experts,), init="zeros",
                grad_req="null")
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
        kept = (self.router_running_bias, self.running_slots)
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            if param not in kept:
                param.cast(dtype)
        self._cached_graph = {}

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

    def hybrid_forward(self, F, x, router_weight, router_running_bias,
                       running_slots, experts_gate_up_weight, experts_down_weight):
        flat = F.reshape(x, shape=(-3, 0))                     # (B*S, D)
        routed, seen = F.moe_experts_held(
            flat, router_weight, router_running_bias, experts_gate_up_weight,
            experts_down_weight, **self._kw)
        defer_aux_update(self.running_slots, seen, increment=True)
        out = F.reshape_like(routed, x)
        if self.shared is not None:
            out = out + self.shared(x)
        return out
