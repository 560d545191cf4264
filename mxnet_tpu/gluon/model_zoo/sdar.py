"""SDAR (JetLM, ``model_type`` sdar_moe) trained by block diffusion: a
decoder of one kind of layer, grouped-query attention with per-head q/k
RMSNorm and rotary positions followed by a softmax-routed expert layer with
no shared expert (the Qwen3-MoE layer), run over a clean copy of each row and
a noised copy of it side by side under the block-diffusion mask (BD3-LM,
Arriola et al., ICLR 2025; the SDAR report, JetLM 2025).

``sdar(config)`` takes the published keys ``hidden_size``,
``num_hidden_layers`` (the PUBLISHED depth), ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``rms_norm_eps``, ``rope_theta``,
``num_experts`` (the router's width), ``num_experts_per_tok``,
``moe_intermediate_size``, ``norm_topk_prob``, ``vocab_size``; and four of
this framework's: ``block_length`` (β), ``experts_held`` ``[lo,
hi)`` (the experts whose weights live on this chip; default all: see
``nn.HeldExperts``), ``layers_held`` ``[lo, hi)`` (the published layers that
live here, a pipeline stage; default all) and ``loss_chunks`` (default 1).

Every layer: h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h)); a final
RMSNorm; an untied head; no bias anywhere. ``model(clean_ids, noisy_ids,
positions, targets, weights)``, each (B, L): the model runs once over the 2L
positions [clean; noisy] (one embedding, both copies at ``positions``), and
the head reads the noisy half alone; with ``targets`` it returns sum_i
weights_i CE(logits_i, targets_i) (1,) over ``loss_chunks`` stretches, else
the noisy half's logits (B, L, vocab). For block-diffusion training the
targets are the clean ids (each token predicted at its own position) and a
weight is 1/t_b where the token was masked in a block of noise level t_b,
0 where it was not: the summed NELBO of the linear schedule, which the
trainer divides by the tokens.
"""
from __future__ import annotations

from .lm_head import _LMHead, chunked_token_loss
from ..block import HybridBlock
from ..nn import Embedding
from ..nn.decoder import GQAttention, HeldExperts, RMSNorm
from ..nn.transformer import remat_per_layer

__all__ = ["SDARModel", "SDARLayer", "sdar"]


class SDARLayer(HybridBlock):
    """One pre-norm residual layer, ``(x, positions) -> y``."""

    def __init__(self, config, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c, init = config, weight_initializer
        units, eps = c["hidden_size"], c["rms_norm_eps"]
        held = c.get("experts_held")
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, prefix="attn_norm_")
            self.attn = GQAttention(
                units, c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], c["block_length"], rope_theta=c["rope_theta"],
                epsilon=eps, dtype=dtype, weight_initializer=init, prefix="attn_")
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            self.ffn = HeldExperts(
                units, c["moe_intermediate_size"], c["num_experts"],
                c["num_experts_per_tok"],
                experts_held=tuple(held) if held is not None else None,
                num_shared_experts=0, renormalize=c["norm_topk_prob"],
                score="softmax", dtype=dtype, weight_initializer=init,
                prefix="moe_")

    def hybrid_forward(self, F, x, positions):
        x = x + self.attn(self.attn_norm(x), positions)
        return x + self.ffn(self.ffn_norm(x))


class SDARModel(HybridBlock):
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
                layer = SDARLayer(config, dtype, weight_initializer,
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
        time."""
        remat_per_layer(self.layers, rows)
        remat_per_layer([self.lm_head])

    def hybrid_forward(self, F, clean_ids, noisy_ids, positions, targets=None,
                       weights=None):
        x = self.embed(F.concat(clean_ids, noisy_ids, dim=1))
        both = F.concat(positions, positions, dim=1)
        for layer in self.layers:
            x = layer(x, both)
        x = self.final_norm(F.slice_axis(x, axis=1, begin=clean_ids.shape[1],
                                         end=None))
        if targets is None:
            return self.lm_head(x)
        return chunked_token_loss(F, self.lm_head, x, targets, self._loss_chunks,
                                  weights)


def sdar(config, **kwargs):
    """The decoder of ``config`` (a dict of the published ``config.json``
    keys; module docstring). Not initialised: call ``initialize`` next."""
    return SDARModel(config, **kwargs)
