"""JoyAI-LLM-Flash (jdopensource, ``model_type`` joyai_llm_flash; the
DeepSeek-V3 architecture, arXiv:2412.19437): a decoder whose every layer is
latent attention with a low-rank query and decoupled rotary positions in
interleaved pairs, followed by a dense gated MLP (the leading layers) or a
sigmoid-routed expert layer with a shared expert; and a multi-token
prediction module with its own loss. Built from the model's own
``config.json`` keys.

``joyai_llm_flash(config)`` takes the published keys ``hidden_size``,
``num_hidden_layers``, ``vocab_size``, ``rms_norm_eps``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``,
``intermediate_size``, ``first_k_dense_replace``, ``n_routed_experts`` (the
router's width), ``num_experts_per_tok``, ``moe_intermediate_size``,
``n_shared_experts``, ``routed_scaling_factor``, ``norm_topk_prob`` and
``num_nextn_predict_layers`` (0 or 1); and three of this framework's:
``experts_held`` ``[lo, hi)`` (the experts whose weights live on this chip;
default all: see ``nn.HeldExperts``), ``loss_chunks`` (default 1) and
``mtp_loss_weight`` (lambda, default 0.3: DeepSeek-V3's weight for the
first part of its pre-training). ``rope_interleave`` must be true and
``scoring_func`` sigmoid; ``n_group`` / ``topk_group`` must be 1 (no group
limit); the selection bias (``topk_method`` noaux_tc) is the expert layer's
``router_running_bias``.

Every layer: h = x + MLA(RMSNorm(x), positions); y = h + FFN(RMSNorm(h));
a final RMSNorm; an untied head. Positions are 0 .. S-1 in every row.
``model(ids)`` -> logits (B, S, vocab). ``model(ids, labels)`` (labels: the
ids shifted by one) -> the summed main cross-entropy + lambda * the summed
multi-token one (1,): the module's input at i is the embedding of
labels[i] (= t_{i+1}) beside the main stack's last hidden state h_i (before
the final norm), its target labels[i + 1]; the last position has no such
target and is masked, not dropped, so that every shape is the main loss's.
Both heads' losses are taken over ``loss_chunks`` stretches, one after the
other, through the one head.
"""
from __future__ import annotations

from .lm_head import _LMHead, chunked_token_loss
from ..block import HybridBlock
from ..nn import Embedding
from ..nn.decoder import GatedMLP, HeldExperts, MLAMixer, MTPModule, RMSNorm
from ..nn.transformer import remat_per_layer

__all__ = ["JoyAILLMFlashModel", "JoyAILLMFlashLayer", "joyai_llm_flash"]


class JoyAILLMFlashLayer(HybridBlock):
    """One pre-norm residual layer, ``(x, positions (B, S)) -> y``; ``index``
    is the published 0-based one (the multi-token module's layer is an
    expert layer)."""

    def __init__(self, config, index, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c, init = config, weight_initializer
        units, eps = c["hidden_size"], c["rms_norm_eps"]
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, prefix="attn_norm_")
            self.mixer = MLAMixer(
                units, c["num_attention_heads"], c["kv_lora_rank"],
                c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
                eps, dtype, init, prefix="mla_", q_lora_rank=c["q_lora_rank"],
                rope_theta=c["rope_theta"])
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            if index < c["first_k_dense_replace"]:
                self.ffn = GatedMLP(units, c["intermediate_size"], dtype, init,
                                    prefix="mlp_")
            else:
                held = c.get("experts_held")
                self.ffn = HeldExperts(
                    units, c["moe_intermediate_size"], c["n_routed_experts"],
                    c["num_experts_per_tok"],
                    experts_held=tuple(held) if held is not None else None,
                    num_shared_experts=c["n_shared_experts"],
                    routed_scaling_factor=c["routed_scaling_factor"],
                    renormalize=c["norm_topk_prob"], score="sigmoid",
                    dtype=dtype, weight_initializer=init, prefix="moe_")

    def hybrid_forward(self, F, x, positions):
        x = x + self.mixer(self.attn_norm(x), positions)
        return x + self.ffn(self.ffn_norm(x))


def _check(config):
    """The published settings this file computes, and no other."""
    wanted = {"rope_interleave": True, "scoring_func": "sigmoid", "n_group": 1,
              "topk_group": 1}
    for key, value in wanted.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: only {value!r} is built")
    if config.get("num_nextn_predict_layers", 0) not in (0, 1):
        raise ValueError("at most one multi-token prediction module is built")


class JoyAILLMFlashModel(HybridBlock):
    def __init__(self, config, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        _check(config)
        units, vocab = config["hidden_size"], config["vocab_size"]
        eps, depth = config["rms_norm_eps"], config["num_hidden_layers"]
        self._loss_chunks = config.get("loss_chunks", 1)
        self._mtp_weight = float(config.get("mtp_loss_weight", 0.3))
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab, units, dtype=dtype,
                                   weight_initializer=weight_initializer,
                                   prefix="embed_")
            for i in range(depth):
                layer = JoyAILLMFlashLayer(config, i, dtype, weight_initializer,
                                           prefix=f"layer{i}_")
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(units, eps, prefix="final_norm_")
            self.lm_head = _LMHead(vocab, units, dtype, weight_initializer,
                                   prefix="")
            self.mtp = None
            if config.get("num_nextn_predict_layers", 0):
                self.mtp = MTPModule(
                    units, lambda prefix: JoyAILLMFlashLayer(
                        config, depth, dtype, weight_initializer, prefix=prefix),
                    self.embed, self._head_loss, eps, dtype, weight_initializer,
                    prefix="mtp_")

    def remat_per_layer(self, rows=None):
        """Recompute each decoder layer (the multi-token module's too), and
        each stretch of the head, in the backward; ``rows``: a layer takes
        that many rows of the batch at a time."""
        extra = [] if self.mtp is None else [self.mtp.layer]
        remat_per_layer(self.layers + extra, rows)
        remat_per_layer([self.lm_head])

    def _head_loss(self, F, x, labels, *mask):
        return chunked_token_loss(F, self.lm_head, x, labels, self._loss_chunks, *mask)

    def hybrid_forward(self, F, ids, labels=None):
        x = self.embed(ids)
        positions = F.broadcast_like(
            F.reshape(F.arange_like(ids, axis=1), shape=(1, -1)), ids)
        for layer in self.layers:
            x = layer(x, positions)
        if labels is None:
            return self.lm_head(self.final_norm(x))
        loss = self._head_loss(F, self.final_norm(x), labels)
        if self.mtp is None:
            return loss
        # the module's targets: labels[i + 1], the last one masked (its label,
        # labels[0], is any id of the vocabulary)
        ahead = F.slice_axis(labels, axis=1, begin=1, end=None)
        wrap = F.slice_axis(labels, axis=1, begin=0, end=1)
        mask = F.concat(F.ones_like(ahead), F.zeros_like(wrap), dim=1)
        mtp = self.mtp(x, labels, positions, F.concat(ahead, wrap, dim=1), mask)
        return loss + mtp * self._mtp_weight


def joyai_llm_flash(config, **kwargs):
    """The decoder of ``config`` (a dict of the published ``config.json``
    keys; module docstring). Not initialised: call ``initialize`` next."""
    return JoyAILLMFlashModel(config, **kwargs)
