"""Transformer layers: MultiHeadAttention, PositionwiseFFN, encoder.

No upstream-gluon analog (SURVEY §5.7: MXNet v1.x composes attention
from batch_dot+softmax in user code / GluonNLP, an external repo).
Built TPU-first: the no-mask path is one fused Pallas flash-attention
op per layer (mx.nd.flash_attention); masked attention (padding masks)
composes batch_dot+softmax exactly as the reference era did — the
flash kernel skips attention-prob dropout, standard for flash
implementations.

Layout convention: (batch, seq, units) inputs, post-LN residual blocks
(BERT) or pre-LN (``pre_norm=True``).
"""
from __future__ import annotations

import math

from .basic_layers import Activation, Dense, Dropout, LayerNorm
from ..block import HybridBlock

__all__ = ["MultiHeadAttention", "PositionwiseFFN",
           "TransformerEncoderCell", "TransformerEncoder", "remat_per_layer"]


def _split_heads(F, x, heads):
    """(B, S, heads * D) -> (B, heads, S, D): the one head split of this
    package (attention here, the KDA and MLA mixers in decoder.py)."""
    x = F.reshape(x, shape=(0, 0, heads, -1))
    return F.transpose(x, axes=(0, 2, 1, 3))


def _merge_heads(F, x):
    """(B, heads, S, D) -> (B, S, heads * D)."""
    x = F.transpose(x, axes=(0, 2, 1, 3))
    return F.reshape(x, shape=(0, 0, -1))


def remat_per_layer(cells, rows=None):
    """Mark each of ``cells`` for recomputation in the backward
    (``hybridize(active=False, remat=True)``): the root stays one plain
    CachedOp and a layer's activations are rebuilt, not kept. ``rows``:
    rebuild that many rows of the batch at a time (``remat_rows``). The one
    place the encoder and decoder stacks mark it."""
    for cell in cells:
        cell.hybridize(active=False, remat=True, remat_rows=rows)


class MultiHeadAttention(HybridBlock):
    """Self-attention with fused QKV projection.

    Parameters
    ----------
    units : total model width C (= num_heads * head_dim)
    num_heads : number of attention heads
    attention_dropout : dropout on attention probs (masked path only)
    causal : apply a causal mask
    """

    def __init__(self, units, num_heads, attention_dropout=0.0,
                 use_bias=True, causal=False, weight_initializer=None,
                 bias_initializer="zeros", dtype="float32", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by heads {num_heads}")
        self._units = units
        self._heads = num_heads
        self._causal = causal
        self._attn_drop = attention_dropout
        with self.name_scope():
            self.qkv_proj = Dense(3 * units, flatten=False, use_bias=use_bias,
                                  dtype=dtype,
                                  weight_initializer=weight_initializer,
                                  bias_initializer=bias_initializer,
                                  prefix="qkv_")
            self.out_proj = Dense(units, flatten=False, use_bias=use_bias,
                                  dtype=dtype,
                                  weight_initializer=weight_initializer,
                                  bias_initializer=bias_initializer,
                                  prefix="out_")
            self.dropout = Dropout(attention_dropout) if attention_dropout else None

    def hybrid_forward(self, F, x, mask=None, valid_length=None,
                       segment_ids=None):
        from ... import autograd as _autograd

        c = self._units
        qkv = self.qkv_proj(x)                       # (B, S, 3C)
        q = F.slice_axis(qkv, axis=-1, begin=0, end=c)
        k = F.slice_axis(qkv, axis=-1, begin=c, end=2 * c)
        v = F.slice_axis(qkv, axis=-1, begin=2 * c, end=3 * c)
        q = _split_heads(F, q, self._heads)
        k = _split_heads(F, k, self._heads)
        v = _split_heads(F, v, self._heads)

        # packed rows (io/packing.py): segment_ids (B, S) make attention
        # block-diagonal per sequence. The flash path needs the row's
        # used length too — derive it when the caller didn't pass one
        # (packers lay segments contiguously, so count-of-nonzero IS it)
        if segment_ids is not None and valid_length is None:
            valid_length = F.segment_valid_len(segment_ids)

        # the flash kernel has no attention-prob dropout; honour a
        # configured attention_dropout by taking the composed path while
        # training (trace-time decision — training mode is static).
        # valid_length (B,) padding and segment_ids packing stay ON the
        # flash path — the kernel masks both natively; only arbitrary
        # additive masks force the composed path.
        need_drop = bool(self._attn_drop) and _autograd.is_training()
        if mask is None and not need_drop:
            if segment_ids is not None:
                out = F.flash_attention(q, k, v, valid_length, segment_ids,
                                        causal=self._causal)
            elif valid_length is not None:
                out = F.flash_attention(q, k, v, valid_length,
                                        causal=self._causal)
            else:
                out = F.flash_attention(q, k, v, causal=self._causal)
        else:
            # composed batch_dot+softmax path (reference-era attention);
            # mask is additive, broadcastable to (B, 1|H, S, S)
            scale = 1.0 / math.sqrt(c // self._heads)
            scores = F.batch_dot_attention_scores(q, k) * scale
            if mask is not None:
                scores = F.broadcast_add(scores, mask)
            if valid_length is not None:
                scores = F.attention_length_mask(scores, valid_length)
            if segment_ids is not None:
                scores = F.attention_segment_mask(scores, segment_ids)
            if self._causal:
                scores = F.causal_mask_scores(scores)
            probs = F.softmax(scores, axis=-1)
            if valid_length is not None:
                # an all-masked row softmaxes to uniform — zero it so
                # the composed path matches the flash kernel's l==0
                # zeros for empty (valid_len == 0) examples
                probs = F.attention_zero_empty_rows(probs, valid_length)
            if segment_ids is not None:
                # same guard for packed PADDING rows (segment id 0)
                probs = F.attention_zero_pad_rows(probs, segment_ids)
            if self.dropout is not None:
                probs = self.dropout(probs)
            out = F.batch_dot_attention_apply(probs, v)

        return self.out_proj(_merge_heads(F, out))   # (B, S, C)


class PositionwiseFFN(HybridBlock):
    """Dense(hidden, act) -> Dense(units) with dropout."""

    def __init__(self, units, hidden_size, activation="gelu", dropout=0.0,
                 weight_initializer=None, bias_initializer="zeros",
                 dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.ffn_1 = Dense(hidden_size, flatten=False, dtype=dtype,
                               weight_initializer=weight_initializer,
                               bias_initializer=bias_initializer,
                               prefix="ffn1_")
            self.act = Activation(activation)
            self.ffn_2 = Dense(units, flatten=False, dtype=dtype,
                               weight_initializer=weight_initializer,
                               bias_initializer=bias_initializer,
                               prefix="ffn2_")
            self.dropout = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        out = self.ffn_2(self.act(self.ffn_1(x)))
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class TransformerEncoderCell(HybridBlock):
    """One encoder layer: MHA + residual + LN, FFN + residual + LN."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 attention_dropout=0.0, activation="gelu", pre_norm=False,
                 causal=False, layer_norm_eps=1e-12,
                 weight_initializer=None, bias_initializer="zeros",
                 dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._pre_norm = pre_norm
        with self.name_scope():
            self.attention = MultiHeadAttention(
                units, num_heads, attention_dropout=attention_dropout,
                causal=causal, weight_initializer=weight_initializer,
                bias_initializer=bias_initializer, dtype=dtype,
                prefix="attn_")
            self.attn_ln = LayerNorm(epsilon=layer_norm_eps, prefix="attn_ln_")
            self.ffn = PositionwiseFFN(
                units, hidden_size, activation=activation, dropout=dropout,
                weight_initializer=weight_initializer,
                bias_initializer=bias_initializer, dtype=dtype, prefix="ffn_")
            self.ffn_ln = LayerNorm(epsilon=layer_norm_eps, prefix="ffn_ln_")
            self.dropout = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, mask=None, valid_length=None,
                       segment_ids=None):
        if self._pre_norm:
            h = self.attention(self.attn_ln(x), mask, valid_length,
                               segment_ids)
            if self.dropout is not None:
                h = self.dropout(h)
            x = x + h
            h = self.ffn(self.ffn_ln(x))
            return x + h
        h = self.attention(x, mask, valid_length, segment_ids)
        if self.dropout is not None:
            h = self.dropout(h)
        x = self.attn_ln(x + h)
        h = self.ffn(x)
        return self.ffn_ln(x + h)


class TransformerEncoder(HybridBlock):
    """Stack of encoder cells (+ optional final pre-norm LN)."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, attention_dropout=0.0, activation="gelu",
                 pre_norm=False, causal=False, layer_norm_eps=1e-12,
                 weight_initializer=None, bias_initializer="zeros",
                 dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._pre_norm = pre_norm
        self.cells = []
        with self.name_scope():
            for i in range(num_layers):
                cell = TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout=dropout,
                    attention_dropout=attention_dropout,
                    activation=activation, pre_norm=pre_norm, causal=causal,
                    layer_norm_eps=layer_norm_eps,
                    weight_initializer=weight_initializer,
                    bias_initializer=bias_initializer, dtype=dtype,
                    prefix=f"layer{i}_")
                self.register_child(cell)
                self.cells.append(cell)
            self.final_ln = (LayerNorm(epsilon=layer_norm_eps, prefix="final_ln_")
                             if pre_norm else None)

    def remat_per_layer(self, rows=None):
        remat_per_layer(self.cells, rows)

    def hybrid_forward(self, F, x, mask=None, valid_length=None,
                       segment_ids=None):
        for cell in self.cells:
            x = cell(x, mask, valid_length, segment_ids)
        if self.final_ln is not None:
            x = self.final_ln(x)
        return x
