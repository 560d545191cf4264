"""bert_base: the configuration built through the framework's normal entry
(``model_zoo.bert_base`` + ``BERTMLMHead`` + the fused softmax cross-entropy
as one HybridBlock, as ``chip_smoke.build_mlm`` builds it), the seeded
weights the benchmark hands to it AND to the plain reference, and the
functions that count its work from shapes.

The weights are the benchmark's, not the program's: one jitted call makes
every leaf on the device from the seed, in the type it is served in; the
program receives them through ``Parameter.set_data``.
"""
from __future__ import annotations

import numpy as np


def sizes(cfg):
    return dict(L=cfg["num_hidden_layers"], H=cfg["hidden_size"],
                A=cfg["num_attention_heads"], F=cfg["intermediate_size"],
                V=cfg["vocab_size"], P=cfg["max_position_embeddings"],
                T=cfg["type_vocab_size"])


def param_specs(cfg):
    """Ordered ``(name, shape, dtype, init)``; names are the program's own
    parameter names below the model's prefix. ``init`` is ``normal`` (std
    ``initializer_range``), ``zeros`` or ``ones``."""
    z = sizes(cfg)
    H, F, V = z["H"], z["F"], z["V"]
    dt = cfg["dtype"]
    out = [("bert_embed_word_weight", (V, H), dt, "normal"),
           ("bert_embed_type_weight", (z["T"], H), dt, "normal"),
           ("bert_embed_pos_weight", (z["P"], H), dt, "normal"),
           ("bert_embed_ln_gamma", (H,), dt, "ones"),
           ("bert_embed_ln_beta", (H,), dt, "zeros")]
    for i in range(z["L"]):
        p = f"bert_enc_layer{i}_"
        out += [(p + "attn_qkv_weight", (3 * H, H), dt, "normal"),
                (p + "attn_qkv_bias", (3 * H,), dt, "zeros"),
                (p + "attn_out_weight", (H, H), dt, "normal"),
                (p + "attn_out_bias", (H,), dt, "zeros"),
                (p + "attn_ln_gamma", (H,), dt, "ones"),
                (p + "attn_ln_beta", (H,), dt, "zeros"),
                (p + "ffn_ffn1_weight", (F, H), dt, "normal"),
                (p + "ffn_ffn1_bias", (F,), dt, "zeros"),
                (p + "ffn_ffn2_weight", (H, F), dt, "normal"),
                (p + "ffn_ffn2_bias", (H,), dt, "zeros"),
                (p + "ffn_ln_gamma", (H,), dt, "ones"),
                (p + "ffn_ln_beta", (H,), dt, "zeros")]
    out += [("bert_pooler_weight", (H, H), dt, "normal"),
            ("bert_pooler_bias", (H,), dt, "zeros"),
            ("head_transform_weight", (H, H), dt, "normal"),
            ("head_transform_bias", (H,), dt, "zeros"),
            ("head_ln_gamma", (H,), dt, "ones"),
            ("head_ln_beta", (H,), dt, "zeros"),
            ("head_decoder_weight", (V, H), dt, "normal"),
            ("head_decoder_bias", (V,), dt, "zeros")]
    return out


def init_std(cfg, name, shape):
    return cfg["initializer_range"]


def build(cfg, ctxs):
    """The Gluon model on ``ctxs``; returns ``(block, forward)`` where
    ``forward(ids, token_types, labels)`` gives the batch's SUMMED token
    loss (shape (1,)) and is what the loop calls under ``record()``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import bert_base
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMHead

    z = sizes(cfg)
    vocab = z["V"]

    class MLM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = bert_base(
                    vocab_size=vocab, max_length=z["P"], dropout=0.0,
                    units=z["H"], hidden_size=z["F"], num_layers=z["L"],
                    num_heads=z["A"], token_types=z["T"],
                    layer_norm_eps=cfg["layer_norm_eps"], prefix="bert_")
                self.head = BERTMLMHead(
                    vocab, z["H"], layer_norm_eps=cfg["layer_norm_eps"],
                    prefix="head_")

        def hybrid_forward(self, F, ids, token_types, labels):
            seq, _ = self.net(ids, token_types)
            logits = self.head(seq)
            return F.softmax_cross_entropy(
                F.reshape(logits, shape=(-1, vocab)),
                F.reshape(labels, shape=(-1,)))

    model = MLM()
    model.initialize(init=mx.initializer.Zero(), ctx=ctxs)
    model.cast(cfg["dtype"])
    hyb = dict(cfg.get("hybridize", {}))
    if hyb.get("remat") == "per_layer":
        # the framework's selective checkpointing: each encoder cell is
        # recomputed in the backward, the root is one plain CachedOp
        for cell in model.net.encoder.cells:
            cell.hybridize(active=False, remat=True)
        hyb.pop("remat")
    model.hybridize(**hyb)
    return model, model


def host_batch(cfg, shape, rng):
    """One host batch for ``batch`` rows (numpy): ids, token types, labels.
    Rows all differ: ids and labels are drawn per token."""
    b, s, v = shape["batch"], shape["seq_len"], cfg["vocab_size"]
    ids = rng.integers(0, v, (b, s), dtype=np.int32)
    labels = rng.integers(0, v, (b, s), dtype=np.int32)
    return ids, np.zeros((b, s), np.int32), labels


def input_dtypes(cfg):
    return ("int32", "int32", "int32")


def samples_and_denominator(cfg, shape):
    """(samples per step, what Trainer.step divides the summed loss by)."""
    return shape["batch"], shape["batch"] * shape["seq_len"]


# ---- work, from shapes ----------------------------------------------------

def flops_per_sample(cfg, shape):
    """Model FLOPs of one 512-token sequence, forward + backward (3x the
    forward's matmul FLOPs; recompute not counted; embeddings' gathers,
    LayerNorm, GELU and softmax not counted)."""
    z = sizes(cfg)
    S, H, F, V, L = shape["seq_len"], z["H"], z["F"], z["V"], z["L"]
    per_layer = (2 * S * H * 3 * H        # fused QKV projection
                 + 2 * 2 * S * S * H      # QK^T and PV over all heads
                 + 2 * S * H * H          # attention output projection
                 + 2 * 2 * S * H * F)     # the two FFN matmuls
    head = 2 * S * H * H + 2 * S * H * V  # MLM transform + decoder
    return 3 * (L * per_layer + head)


def attention_work(cfg, shape):
    """The least work of ONE step's attention (every layer, forward +
    backward, whatever implements it), for the whole batch: FLOPs and HBM
    bytes. Forward: QK^T and PV (4*S*S*D per head). Backward: dV, dP, dQ,
    dK (8*S*S*D per head); the recompute of QK^T inside a flash backward is
    not counted. Bytes: forward reads Q, K, V and writes O; backward reads
    Q, K, V, O, dO and writes dQ, dK, dV (bf16 each)."""
    z = sizes(cfg)
    B, S, H, L = shape["batch"], shape["seq_len"], z["H"], z["L"]
    flops = L * B * (4 + 8) * S * S * H
    itemsize = 2 if cfg["dtype"] in ("bfloat16", "float16") else 4
    bytes_ = L * B * (4 + 8) * S * H * itemsize
    return {"flops": float(flops), "bytes": float(bytes_)}
