"""kimi_linear: the configuration built through the framework's normal entry
(``model_zoo.kimi_linear(config)``, whose ``model(ids, labels)`` is the summed
token loss; each decoder layer marked for remat as BERT's cells are, one row
at a time), the seeded weights the benchmark hands to it AND to the plain
reference, and the functions that count its work from shapes.

The configuration's file keeps the published keys; two are this chip's share
of the deployment: ``num_experts`` is the number of experts HELD (the
range ``experts_held``) and ``router_experts`` the router's published width,
which the model zoo takes as its ``num_experts``.
"""
from __future__ import annotations

import numpy as np


def zoo_config(cfg):
    """The configuration as ``model_zoo.kimi_linear`` reads it."""
    return {**cfg, "num_experts": cfg["router_experts"]}


def sizes(cfg):
    lc = cfg["linear_attn_config"]
    lo, hi = cfg["experts_held"]
    layers = range(1, cfg["num_hidden_layers"] + 1)
    return dict(
        L=cfg["num_hidden_layers"], D=cfg["hidden_size"], V=cfg["vocab_size"],
        H=lc["num_heads"], dk=lc["head_dim"], K=lc["short_conv_kernel_size"],
        A=cfg["num_attention_heads"], R=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], E=cfg["router_experts"], held=hi - lo,
        top_k=cfg["num_experts_per_token"], shared=cfg["num_shared_experts"],
        kda=[i for i in layers if i in lc["kda_layers"]],
        mla=[i for i in layers if i in lc["full_attn_layers"]],
        moe=[i for i in layers if i > cfg["first_k_dense_replace"]])


def param_specs(cfg):
    """Ordered ``(name, shape, dtype, init)``; names are the program's own
    parameter names below the model's prefix. ``init`` is ``normal`` (std
    from ``init_std``), ``zeros``, ``ones`` or a number."""
    z = sizes(cfg)
    D, dt = z["D"], cfg["dtype"]
    inner = z["H"] * z["dk"]
    out = [("kimi_embed_weight", (z["V"], D), dt, "normal")]
    for i in range(z["L"]):
        p = f"kimi_layer{i}_"
        out.append((p + "attn_norm_gamma", (D,), dt, "ones"))
        if i + 1 in z["kda"]:
            m = p + "kda_"
            out += [(m + "a_log", (z["H"],), dt, "normal"),
                    (m + "dt_bias", (inner,), dt, cfg["kda_dt_bias_init"]),
                    (m + "q_weight", (inner, D), dt, "normal"),
                    (m + "k_weight", (inner, D), dt, "normal"),
                    (m + "v_weight", (inner, D), dt, "normal"),
                    (m + "qconv_weight", (inner, z["K"]), dt, "normal"),
                    (m + "kconv_weight", (inner, z["K"]), dt, "normal"),
                    (m + "vconv_weight", (inner, z["K"]), dt, "normal"),
                    (m + "f_a_weight", (z["dk"], D), dt, "normal"),
                    (m + "f_b_weight", (inner, z["dk"]), dt, "normal"),
                    (m + "b_weight", (z["H"], D), dt, "normal"),
                    (m + "g_a_weight", (z["dk"], D), dt, "normal"),
                    (m + "g_b_weight", (inner, z["dk"]), dt, "normal"),
                    (m + "o_norm_gamma", (z["dk"],), dt, "ones"),
                    (m + "o_weight", (D, inner), dt, "normal")]
        else:
            m = p + "mla_"
            out += [(m + "q_weight", (z["A"] * (z["nope"] + z["rope"]), D), dt, "normal"),
                    (m + "kva_weight", (z["R"] + z["rope"], D), dt, "normal"),
                    (m + "kv_norm_gamma", (z["R"],), dt, "ones"),
                    (m + "kvb_weight", (z["A"] * (z["nope"] + z["dv"]), z["R"]), dt, "normal"),
                    (m + "o_weight", (D, z["A"] * z["dv"]), dt, "normal")]
        out.append((p + "ffn_norm_gamma", (D,), dt, "ones"))
        if i + 1 in z["moe"]:
            m = p + "moe_"
            fs = z["Fe"] * z["shared"]
            out += [(m + "router_weight", (z["E"], D), dt, "normal"),
                    (m + "router_running_bias", (z["E"],), "float32", "normal"),
                    (m + "running_slots", (z["held"] + 1,), "float32", "zeros"),
                    (m + "experts_gate_up_weight", (z["held"], 2 * z["Fe"], D), dt, "normal"),
                    (m + "experts_down_weight", (z["held"], D, z["Fe"]), dt, "normal"),
                    (m + "shared_gate_up_weight", (2 * fs, D), dt, "normal"),
                    (m + "shared_down_weight", (D, fs), dt, "normal")]
        else:
            out += [(p + "mlp_gate_up_weight", (2 * z["F"], D), dt, "normal"),
                    (p + "mlp_down_weight", (D, z["F"]), dt, "normal")]
    out += [("kimi_final_norm_gamma", (D,), dt, "ones"),
            ("kimi_head_weight", (z["V"], D), dt, "normal")]
    return out


def init_std(cfg, name, shape):
    if name.endswith("conv_weight"):
        return cfg["kda_conv_init_std"]
    if name.endswith("a_log"):
        return cfg["kda_a_log_init_std"]
    return cfg["initializer_range"]


def build(cfg, ctxs):
    """The Gluon model on ``ctxs``; returns ``(block, forward)`` where
    ``forward(ids, labels)`` gives the batch's SUMMED next-token loss (shape
    (1,)) and is what the loop calls under ``record()``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    class LM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = kimi_linear(zoo_config(cfg), prefix="kimi_")

        def hybrid_forward(self, F, ids, labels):
            return self.net(ids, labels)

    model = LM()
    model.initialize(init=mx.initializer.Zero(), ctx=ctxs)
    model.cast(cfg["dtype"])
    hyb = dict(cfg.get("hybridize", {}))
    rows = hyb.pop("remat_rows", None)
    if hyb.pop("remat", None) == "per_layer":
        model.net.remat_per_layer(rows=rows)
    model.hybridize(**hyb)
    return model, model


def host_batch(cfg, shape, rng):
    """One host batch (numpy): ids and labels, the ids shifted by one. Full
    length, no padding; ids uniform over this chip's slice of the vocabulary."""
    b, s, v = shape["batch"], shape["seq_len"], cfg["vocab_size"]
    row = rng.integers(0, v, (b, s + 1), dtype=np.int32)
    return np.ascontiguousarray(row[:, :-1]), np.ascontiguousarray(row[:, 1:])


def input_dtypes(cfg):
    return ("int32", "int32")


def samples_and_denominator(cfg, shape):
    """(samples per step, what Trainer.step divides the summed loss by). A
    sample is one row of ``seq_len`` tokens."""
    return shape["batch"], shape["batch"] * shape["seq_len"]


# ---- work, from shapes ----------------------------------------------------

def matmul_params_per_token(cfg):
    """Weights one token is multiplied by in a forward pass here: the routed
    experts at the expected top_k * held / experts slots a token."""
    z = sizes(cfg)
    D, inner = z["D"], z["H"] * z["dk"]
    kda = 3 * D * inner + inner * D + 2 * (D * z["dk"] + z["dk"] * inner) + D * z["H"]
    mla = (D * z["A"] * (z["nope"] + z["rope"]) + D * (z["R"] + z["rope"])
           + z["R"] * z["A"] * (z["nope"] + z["dv"]) + z["A"] * z["dv"] * D)
    expert = 3 * D * z["Fe"]
    moe = D * z["E"] + z["shared"] * expert + expert * z["top_k"] * z["held"] / z["E"]
    dense = 3 * D * z["F"]
    return (len(z["kda"]) * kda + len(z["mla"]) * mla + len(z["moe"]) * moe
            + (z["L"] - len(z["moe"])) * dense + D * z["V"])


def flops_per_sample(cfg, shape):
    """Model FLOPs of one row, forward + backward (3x the forward's): 2 a
    weight a token, the causal half of MLA's S^2 products, KDA's recurrence
    (6 d_k d_v a token a head). Recompute, norms, gates and the embedding's
    gather are not counted."""
    z = sizes(cfg)
    S = shape["seq_len"]
    per_token = (2 * matmul_params_per_token(cfg)
                 + len(z["mla"]) * z["A"] * S * (z["nope"] + z["rope"] + z["dv"])
                 + len(z["kda"]) * z["H"] * 6 * z["dk"] * z["dk"])
    return 3 * S * per_token


def _itemsize(cfg):
    return 2 if cfg["dtype"] in ("bfloat16", "float16") else 4


def kda_work(cfg, shape):
    """The least work of ONE step's gated delta rule (every KDA layer, forward
    + backward, whatever chunking implements it): the recurrence's FLOPs (k^T S,
    the rank-1 update, S^T q: 6 d_k d_v a token a head, twice that backward)
    and the bytes of q, k, v, beta, o in the served type and the float32
    decay, and their cotangents, once each."""
    z = sizes(cfg)
    tokens = shape["batch"] * shape["seq_len"] * z["H"] * len(z["kda"])
    d = z["dk"]
    flops = tokens * 3 * 6 * d * d
    bytes_ = tokens * 2 * (_itemsize(cfg) * (4 * d + 1) + 4 * d)
    return {"flops": float(flops), "bytes": float(bytes_)}


def moe_expert_work(cfg, shape):
    """The least work of ONE step's held experts (every expert layer, forward +
    backward): the three matmuls of the slots routed here at their expected
    count (tokens * top_k * held / experts), and the held experts' weights
    read once."""
    z = sizes(cfg)
    slots = shape["batch"] * shape["seq_len"] * z["top_k"] * z["held"] / z["E"]
    expert = 3 * z["D"] * z["Fe"]
    flops = len(z["moe"]) * 3 * 2 * expert * slots
    bytes_ = len(z["moe"]) * z["held"] * expert * _itemsize(cfg)
    return {"flops": float(flops), "bytes": float(bytes_)}


def mla_attention_work(cfg, shape):
    """The least work of ONE step's latent attention (every MLA layer, forward
    + backward): the causal half of the S^2 products, q.k at nope + rope wide
    and p.v at v wide (backward twice the forward); q, k, v, o once forward,
    q, k, v, o, do read and dq, dk, dv written backward."""
    z = sizes(cfg)
    B, S = shape["batch"], shape["seq_len"]
    qk, v = z["nope"] + z["rope"], z["dv"]
    heads = len(z["mla"]) * B * z["A"]
    flops = heads * 3 * S * S * (qk + v)
    bytes_ = heads * S * _itemsize(cfg) * ((2 * qk + 2 * v) + (4 * qk + 4 * v))
    return {"flops": float(flops), "bytes": float(bytes_)}
