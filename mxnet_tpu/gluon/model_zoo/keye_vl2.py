"""Keye-VL-2.0's language model (Kwai-Keye, ``model_type`` KeyeVL2): a
decoder of one kind of layer, grouped-query attention with rotary positions
over the keys a learned indexer selects (``sa_config``: DeepSeek-V3.2-Exp's
sparse attention, the top ``topk`` of the causal keys a query) followed by a
softmax-routed expert layer with no shared expert. The language model only:
it takes token ids and three position streams (``mrope_section``: text tokens
carry one position three times; the vision tower that would give image
patches their own is not part of this file).

``keye_vl2(config)`` takes the published keys ``hidden_size``,
``num_hidden_layers`` (the PUBLISHED depth), ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``rms_norm_eps``, ``rope_theta``,
``rope_scaling`` (its ``mrope_section``), ``sa_config``
(``indexer_num_heads``, ``indexer_head_dim``, ``topk``; its one indexer key
head and its two chunk sizes, a kernel's tiles, change nothing here),
``num_experts`` (the router's width), ``num_experts_per_tok``,
``moe_intermediate_size``, ``norm_topk_prob``, ``vocab_size``; and three of
this framework's: ``experts_held`` ``[lo, hi)`` (the experts whose weights
live on this chip; default all: see ``nn.HeldExperts``), ``layers_held``
``[lo, hi)`` (the published layers that live here, a pipeline stage; default
all) and ``loss_chunks`` (default 1).

Every layer: h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h)); a final
RMSNorm; an untied head; no bias anywhere. A layer returns its activation
AND its indexer's loss term (``nn.SparseGQAttention``); the model sums the
terms. ``model(ids, position_ids)`` with ``position_ids`` (3, B, S) ->
(logits (B, S, vocab), index loss (1,)); ``model(ids, position_ids, labels)``
-> (the summed token cross-entropy (1,), index loss (1,)), head and loss over
``loss_chunks`` stretches of the sequence as ``kimi_linear``. The two add up
to the step's loss with weight 1; they share no gradient path.
"""
from __future__ import annotations

from .kimi_linear import _LMHead, chunked_token_loss
from ..block import HybridBlock
from ..nn import Embedding
from ..nn.decoder import HeldExperts, RMSNorm, SparseGQAttention
from ..nn.transformer import remat_per_layer

__all__ = ["KeyeVL2Model", "KeyeVL2Layer", "keye_vl2"]


class KeyeVL2Layer(HybridBlock):
    """One pre-norm residual layer: ``(x, positions (B, 3, S)) -> (y, the
    indexer's loss term, a scalar)``."""

    def __init__(self, config, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c, sa, init = config, config["sa_config"], weight_initializer
        units, eps = c["hidden_size"], c["rms_norm_eps"]
        held = c.get("experts_held")
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, prefix="attn_norm_")
            self.attn = SparseGQAttention(
                units, c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], sa["indexer_num_heads"], sa["indexer_head_dim"],
                sa["topk"], rope_theta=c["rope_theta"],
                mrope_section=c["rope_scaling"]["mrope_section"], epsilon=eps,
                dtype=dtype, weight_initializer=init, prefix="attn_")
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            self.ffn = HeldExperts(
                units, c["moe_intermediate_size"], c["num_experts"],
                c["num_experts_per_tok"],
                experts_held=tuple(held) if held is not None else None,
                num_shared_experts=0, renormalize=c["norm_topk_prob"],
                score="softmax", dtype=dtype, weight_initializer=init,
                prefix="moe_")

    def hybrid_forward(self, F, x, positions):
        mixed, index_loss = self.attn(self.attn_norm(x), positions)
        x = x + mixed
        return x + self.ffn(self.ffn_norm(x)), index_loss


class KeyeVL2Model(HybridBlock):
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
                layer = KeyeVL2Layer(config, dtype, weight_initializer,
                                     prefix=f"layer{i}_")
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(units, config["rms_norm_eps"],
                                      prefix="final_norm_")
            self.lm_head = _LMHead(vocab, units, dtype, weight_initializer,
                                   prefix="")

    def remat_per_layer(self, rows=None):
        """Recompute each decoder layer, and each stretch of the head, in the
        backward; ``rows``: a layer takes that many rows of the batch at a
        time, and its loss term is summed over them."""
        remat_per_layer(self.layers, rows)
        remat_per_layer([self.lm_head])

    def hybrid_forward(self, F, ids, position_ids, labels=None):
        x = self.embed(ids)
        positions = F.transpose(position_ids, axes=(1, 0, 2))   # batch first
        index_loss = None
        for layer in self.layers:
            x, term = layer(x, positions)
            index_loss = term if index_loss is None else index_loss + term
        index_loss = F.reshape(index_loss, shape=(1,))
        x = self.final_norm(x)
        if labels is None:
            return self.lm_head(x), index_loss
        return (chunked_token_loss(F, self.lm_head, x, labels, self._loss_chunks),
                index_loss)


def keye_vl2(config, **kwargs):
    """The decoder of ``config`` (a dict of the published ``config.json``
    keys; module docstring). Not initialised: call ``initialize`` next."""
    return KeyeVL2Model(config, **kwargs)
