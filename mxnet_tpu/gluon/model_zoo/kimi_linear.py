"""Kimi Linear (moonshotai, arXiv:2510.26692): a decoder whose layers mix
KDA (gated delta-rule linear attention) and NoPE latent attention 3 : 1,
each followed by a dense gated MLP (the leading layers) or a routed expert
layer with a shared expert. Built from the model's own ``config.json`` keys
— which layer is of which kind is read from the config, not written here.

``kimi_linear(config)`` takes the published keys: ``hidden_size``,
``num_hidden_layers``, ``vocab_size``, ``rms_norm_eps``,
``linear_attn_config`` (``kda_layers`` / ``full_attn_layers`` by 1-based
layer index, ``num_heads``, ``head_dim``, ``short_conv_kernel_size``),
``num_attention_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``intermediate_size``,
``first_k_dense_replace``, ``num_experts``, ``num_experts_per_token``,
``moe_intermediate_size``, ``num_shared_experts``,
``routed_scaling_factor``, ``moe_renormalize``; and three of this
framework's: ``experts_held`` ``[lo, hi)`` — the experts whose weights live
on this chip under expert parallelism (default: all; the expert layers then
return this chip's PARTIAL sum, see ``nn.HeldExperts``) — ``kda_chunk_size``
(default 64) and ``loss_chunks`` (default 1).

Pre-norm residual blocks (h = x + Mixer(RMSNorm(x)); y = h +
FFN(RMSNorm(h))), a final RMSNorm, an untied head, no positional encoding
anywhere. ``model(ids)`` -> logits (B, S, vocab); ``model(ids, labels)`` ->
the summed token cross-entropy (1,), the head and the loss taken over
``loss_chunks`` stretches of the sequence one after the other, so that with
``remat_per_layer()`` the logits of one stretch, not of the batch, are live.
"""
from __future__ import annotations

from .lm_head import _LMHead, chunked_token_loss
from ..block import HybridBlock
from ..nn import Embedding
from ..nn.decoder import GatedMLP, HeldExperts, KDAMixer, MLAMixer, RMSNorm
from ..nn.transformer import remat_per_layer

__all__ = ["KimiLinearModel", "KimiLinearLayer", "kimi_linear"]


class KimiLinearLayer(HybridBlock):
    """One pre-norm residual layer; ``index`` is the published 1-based one."""

    def __init__(self, config, index, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c, lin = config, config["linear_attn_config"]
        units, eps, init = c["hidden_size"], c["rms_norm_eps"], weight_initializer
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, prefix="attn_norm_")
            if index in lin["kda_layers"]:
                self.mixer = KDAMixer(
                    units, lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"], c.get("kda_chunk_size", 64),
                    eps, dtype, init, prefix="kda_")
            elif index in lin["full_attn_layers"]:
                self.mixer = MLAMixer(
                    units, c["num_attention_heads"], c["kv_lora_rank"],
                    c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"], eps, dtype, init, prefix="mla_")
            else:
                raise ValueError(f"layer {index} is in neither kda_layers nor "
                                 "full_attn_layers of linear_attn_config")
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            if index <= c["first_k_dense_replace"]:
                self.ffn = GatedMLP(units, c["intermediate_size"], dtype, init,
                                    prefix="mlp_")
            else:
                held = c.get("experts_held")
                self.ffn = HeldExperts(
                    units, c["moe_intermediate_size"], c["num_experts"],
                    c["num_experts_per_token"],
                    experts_held=tuple(held) if held is not None else None,
                    num_shared_experts=c["num_shared_experts"],
                    routed_scaling_factor=c["routed_scaling_factor"],
                    renormalize=c["moe_renormalize"],
                    dtype=dtype, weight_initializer=init, prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.mixer(self.attn_norm(x))
        return x + self.ffn(self.ffn_norm(x))


class KimiLinearModel(HybridBlock):
    def __init__(self, config, dtype="float32", weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        units, vocab = config["hidden_size"], config["vocab_size"]
        self._loss_chunks = config.get("loss_chunks", 1)
        self.layers = []
        with self.name_scope():
            self.embed = Embedding(vocab, units, dtype=dtype,
                                   weight_initializer=weight_initializer,
                                   prefix="embed_")
            for i in range(config["num_hidden_layers"]):
                layer = KimiLinearLayer(config, i + 1, dtype, weight_initializer,
                                        prefix=f"layer{i}_")
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = RMSNorm(units, config["rms_norm_eps"],
                                      prefix="final_norm_")
            self.lm_head = _LMHead(vocab, units, dtype, weight_initializer,
                                   prefix="")

    def remat_per_layer(self, rows=None):
        """Recompute each decoder layer, and each stretch of the head, in the
        backward (as BERT's cells); ``rows``: a layer takes that many rows of
        the batch at a time."""
        remat_per_layer(self.layers, rows)
        remat_per_layer([self.lm_head])

    def hybrid_forward(self, F, ids, labels=None):
        x = self.embed(ids)
        for layer in self.layers:
            x = layer(x)
        x = self.final_norm(x)
        if labels is None:
            return self.lm_head(x)
        return chunked_token_loss(F, self.lm_head, x, labels, self._loss_chunks)


def kimi_linear(config, **kwargs):
    """The decoder of ``config`` (a dict of the published ``config.json``
    keys; module docstring). Not initialised: call ``initialize`` next."""
    return KimiLinearModel(config, **kwargs)
