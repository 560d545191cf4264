"""Phi-4-mini-flash (microsoft, ``model_type`` phi4flash): SambaY, a
decoder-hybrid-decoder (arXiv:2507.06607) with differential attention
(arXiv:2410.05258). The first half of the layers, the self-decoder,
alternates Mamba selective-scan mixers with sliding-window attention and
closes with one Mamba layer and one full-attention layer; the second half,
the cross-decoder, alternates Gated Memory Units that reuse the closing
Mamba layer's scan output with cross-attention to the full-attention layer's
keys and values (YOCO: one layer's key/value cache read by all of them).
Which layer is of which kind follows from the config's own keys
(`layer_kind`), not from a list written here.

``phi4_flash(config)`` takes the published keys ``hidden_size``,
``num_hidden_layers`` (the PUBLISHED depth: the rule needs it),
``mb_per_layer``, ``sliding_window``, ``num_attention_heads``,
``num_key_value_heads``, ``intermediate_size``, ``layer_norm_eps``,
``vocab_size``, ``tie_word_embeddings``; the state-space sizes
``mamba_d_state`` (16), ``mamba_d_conv`` (4), ``mamba_expand`` (2),
``mamba_dt_rank`` (ceil(hidden / 16)); and two of this framework's:
``layers_held`` ``[lo, hi)`` — the published layers that live on this chip
(a pipeline stage; default: all) — and ``loss_chunks`` (default 1). A held
range with a Gated Memory Unit or a cross-attention layer but without the
layer it reads raises.

Every layer: h = x + Mixer(LN(x)); y = h + MLP(LN(h)), LayerNorm with gain
and bias, a SiLU-gated MLP without bias; a final LayerNorm; the head is the
embedding (``tie_word_embeddings``: ONE parameter, its gradient the sum of
both uses); no positional encoding anywhere. ``model(ids)`` -> logits;
``model(ids, labels)`` -> the summed token cross-entropy (1,), head and loss
over ``loss_chunks`` stretches of the sequence, as ``kimi_linear``.
"""
from __future__ import annotations

import math

from ..block import HybridBlock
from ..nn import Embedding, LayerNorm
from ..nn.decoder import DiffAttention, GatedMemoryUnit, GatedMLP, MambaMixer
from ..nn.transformer import remat_per_layer
from .kimi_linear import chunked_token_loss

__all__ = ["Phi4FlashModel", "Phi4FlashLayer", "phi4_flash", "layer_kind"]

SOURCES = {"gmu": "mamba_source", "cross": "attention_source"}


def layer_kind(index, num_layers, mb_per_layer=2):
    """The kind of the published 0-based layer ``index`` of ``num_layers``:
    ``mamba`` / ``window`` in the self-decoder, its closing ``mamba_source``
    and ``attention_source``, ``gmu`` / ``cross`` in the cross-decoder."""
    half = num_layers // 2
    first = index % mb_per_layer == 0
    if index < half:
        return "mamba" if first else "window"
    if index == half:
        return "mamba_source"
    if index == half + 1:
        return "attention_source"
    return "gmu" if first else "cross"


def lambda_init(index):
    """Differential attention's depth-dependent start of lambda."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


class _Head(HybridBlock):
    """Logits = x E^T; with labels, their summed cross-entropy. ``params``:
    the embedding's (tied: this block's ``weight`` IS the embedding's), or
    None for a head of its own."""

    def __init__(self, vocab, units, dtype, weight_initializer, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab, self._units = vocab, units
        self.weight = self.params.get("weight", shape=(vocab, units),
                                      dtype=dtype, init=weight_initializer)

    def hybrid_forward(self, F, x, labels=None, weight=None):
        if labels is not None:
            # a 2-D product, as F.softmax_cross_entropy wants its producer
            x = F.reshape(x, shape=(-1, self._units))
        logits = F.FullyConnected(x, weight, None, no_bias=True,
                                  num_hidden=self._vocab, flatten=False)
        if labels is None:
            return logits
        return F.softmax_cross_entropy(logits, F.reshape(labels, shape=(-1,)))


class Phi4FlashLayer(HybridBlock):
    """One pre-norm residual layer; ``index`` is the published 0-based one.
    What it takes and returns follows its kind: ``mamba_source`` returns
    ``(y, m)``, ``attention_source`` ``(y, k, v)``; ``gmu`` takes ``(x, m)``
    and ``cross`` ``(x, k, v)``."""

    def __init__(self, config, index, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c, init = config, weight_initializer
        units, eps = c["hidden_size"], c["layer_norm_eps"]
        heads = c["num_attention_heads"]
        inner = c.get("mamba_expand", 2) * units
        self.kind = layer_kind(index, c["num_hidden_layers"],
                               c.get("mb_per_layer", 2))
        with self.name_scope():
            self.mixer_norm = LayerNorm(epsilon=eps, in_channels=units,
                                        prefix="mixer_norm_")
            if self.kind in ("mamba", "mamba_source"):
                self.mixer = MambaMixer(
                    units, inner, c.get("mamba_d_state", 16),
                    c.get("mamba_d_conv", 4), c.get("mamba_dt_rank"), dtype,
                    init, prefix="mamba_")
            elif self.kind == "gmu":
                self.mixer = GatedMemoryUnit(units, inner, dtype, init,
                                             prefix="gmu_")
            else:
                self.mixer = DiffAttention(
                    units, heads, c["num_key_value_heads"], units // heads,
                    lambda_init(index),
                    window=c["sliding_window"] if self.kind == "window" else None,
                    cross=self.kind == "cross", epsilon=eps, dtype=dtype,
                    weight_initializer=init, prefix="attn_")
            self.mlp_norm = LayerNorm(epsilon=eps, in_channels=units,
                                      prefix="mlp_norm_")
            self.mlp = GatedMLP(units, c["intermediate_size"], dtype, init,
                                prefix="mlp_")

    def hybrid_forward(self, F, x, *source):
        mixed = self.mixer(self.mixer_norm(x), *source)
        handed = ()
        if self.kind not in SOURCES:    # a mixer of its own hands tensors on
            mixed, *handed = mixed
        x = x + mixed
        y = x + self.mlp(self.mlp_norm(x))
        if self.kind in SOURCES.values():
            return (y, *handed)
        return y


class Phi4FlashModel(HybridBlock):
    def __init__(self, config, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        units, vocab = config["hidden_size"], config["vocab_size"]
        depth = config["num_hidden_layers"]
        lo, hi = config.get("layers_held") or (0, depth)
        if not 0 <= lo < hi <= depth:
            raise ValueError(f"layers_held [{lo}, {hi}) is no range of the "
                             f"{depth} published layers")
        self._loss_chunks = config.get("loss_chunks", 1)
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab, units, dtype=dtype,
                                   weight_initializer=weight_initializer,
                                   prefix="embed_")
            for i in range(lo, hi):
                layer = Phi4FlashLayer(config, i, dtype, weight_initializer,
                                       prefix=f"layer{i}_")
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = LayerNorm(epsilon=config["layer_norm_eps"],
                                        in_channels=units, prefix="final_norm_")
            tied = config.get("tie_word_embeddings", True)
            self.lm_head = _Head(vocab, units, dtype, weight_initializer,
                                 prefix="head_",
                                 params=self.embed.params if tied else None)
        kinds = [layer.kind for layer in self.layers]
        for kind, source in SOURCES.items():
            if kind in kinds and source not in kinds:
                raise ValueError(
                    f"layers_held [{lo}, {hi}) holds a {kind} layer without "
                    f"the {source} layer it reads (published layer "
                    f"{depth // 2 + (source == 'attention_source')})")

    def remat_per_layer(self, rows=None):
        """Recompute each decoder layer, and each stretch of the head, in the
        backward; ``rows``: a layer takes that many rows of the batch at a
        time. What a source layer hands on is one of its outputs and stays."""
        remat_per_layer(self.layers, rows)
        remat_per_layer([self.lm_head])

    def hybrid_forward(self, F, ids, labels=None):
        x = self.embed(ids)
        memory, keys_values = None, ()
        for layer in self.layers:
            if layer.kind == "mamba_source":
                x, memory = layer(x)
            elif layer.kind == "attention_source":
                x, *keys_values = layer(x)
            elif layer.kind == "gmu":
                x = layer(x, memory)
            elif layer.kind == "cross":
                x = layer(x, *keys_values)
            else:
                x = layer(x)
        x = self.final_norm(x)
        if labels is None:
            return self.lm_head(x)
        return chunked_token_loss(F, self.lm_head, x, labels, self._loss_chunks)


def phi4_flash(config, **kwargs):
    """The decoder of ``config`` (a dict of the published ``config.json``
    keys; module docstring). Not initialised: call ``initialize`` next."""
    return Phi4FlashModel(config, **kwargs)
