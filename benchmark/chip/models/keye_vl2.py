"""keye_vl2: the configuration built through the framework's normal entry
(``model_zoo.keye_vl2(config)``, whose ``model(ids, position_ids, labels)``
gives the summed token loss and the summed indexer loss; each decoder layer
marked for remat as Kimi's are, one row at a time), the seeded weights the
benchmark hands to it AND to the plain reference, and the functions that
count its work from shapes.

The configuration's file keeps the published keys; three are this chip's
share of the deployment: ``num_hidden_layers`` is the number of layers HELD
(the range ``layers_held`` of the ``published_layers``), ``num_experts`` /
``num_local_experts`` the number of experts HELD (the range ``experts_held``
of the router's ``router_experts``); the model zoo takes the published depth
and the router's width under those keys.
"""
from __future__ import annotations

import numpy as np

from models.kimi_linear import _itemsize, samples_and_denominator  # noqa: F401


def zoo_config(cfg):
    """The configuration as ``model_zoo.keye_vl2`` reads it."""
    return {**cfg, "num_experts": cfg["router_experts"],
            "num_hidden_layers": cfg["published_layers"]}


def sizes(cfg):
    sa = cfg["sa_config"]
    lo, hi = cfg["experts_held"]
    first, last = cfg["layers_held"]
    return dict(
        L=last - first, layers=range(first, last), D=cfg["hidden_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        Hk=cfg["num_key_value_heads"], d=cfg["head_dim"],
        Hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"], K=sa["topk"],
        Fe=cfg["moe_intermediate_size"], E=cfg["router_experts"], held=hi - lo,
        top_k=cfg["num_experts_per_tok"])


def param_specs(cfg):
    """Ordered ``(name, shape, dtype, init)``; names are the program's own
    parameter names below the model's prefix. ``init`` is ``normal`` (std
    from ``init_std``), ``zeros``, ``ones`` or a number."""
    z = sizes(cfg)
    D, dt, d = z["D"], cfg["dtype"], z["d"]
    out = [("keye_embed_weight", (z["V"], D), dt, "normal")]
    for i in z["layers"]:
        p = f"keye_layer{i}_"
        a, m = p + "attn_", p + "moe_"
        out += [(p + "attn_norm_gamma", (D,), dt, "ones"),
                (a + "q_weight", (z["H"] * d, D), dt, "normal"),
                (a + "k_weight", (z["Hk"] * d, D), dt, "normal"),
                (a + "v_weight", (z["Hk"] * d, D), dt, "normal"),
                (a + "o_weight", (D, z["H"] * d), dt, "normal"),
                (a + "q_norm_gamma", (d,), dt, "ones"),
                (a + "k_norm_gamma", (d,), dt, "ones"),
                (a + "index_q_weight", (z["Hi"] * z["di"], D), dt, "normal"),
                (a + "index_k_weight", (z["di"], D), dt, "normal"),
                (a + "index_w_weight", (z["Hi"], D), dt, "normal"),
                (a + "index_k_norm_gamma", (z["di"],), dt, "ones"),
                (a + "index_k_norm_beta", (z["di"],), dt, "zeros"),
                (a + "running_pairs", (2,), "float32", "zeros"),
                (p + "ffn_norm_gamma", (D,), dt, "ones"),
                (m + "router_weight", (z["E"], D), dt, "normal"),
                (m + "running_slots", (z["held"] + 1,), "float32", "zeros"),
                (m + "experts_gate_up_weight", (z["held"], 2 * z["Fe"], D), dt, "normal"),
                (m + "experts_down_weight", (z["held"], D, z["Fe"]), dt, "normal")]
    out += [("keye_final_norm_gamma", (D,), dt, "ones"),
            ("keye_head_weight", (z["V"], D), dt, "normal")]
    return out


def init_std(cfg, name, shape):
    return cfg["initializer_range"]


def build(cfg, ctxs):
    """The Gluon model on ``ctxs``; returns ``(block, forward)`` where
    ``forward(ids, labels, positions)`` gives the batch's SUMMED loss (shape
    (1,): next-token cross-entropy + the indexer's loss) and is what the loop
    calls under ``record()``. ``positions`` come batch first, (B, 3, S), as a
    batch is split; the model takes them stream first."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import keye_vl2

    class LM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = keye_vl2(zoo_config(cfg), prefix="keye_")

        def hybrid_forward(self, F, ids, labels, positions):
            token_loss, index_loss = self.net(
                ids, F.transpose(positions, axes=(1, 0, 2)), labels)
            return token_loss + index_loss.astype(token_loss.dtype)

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
    """One host batch (numpy): ids, labels (the ids shifted by one) and the
    three position streams (B, 3, S), each 0 .. S-1 (text). Full length, no
    padding; ids uniform over this chip's slice of the vocabulary."""
    b, s, v = shape["batch"], shape["seq_len"], cfg["vocab_size"]
    row = rng.integers(0, v, (b, s + 1), dtype=np.int32)
    positions = np.broadcast_to(np.arange(s, dtype=np.int32), (b, 3, s))
    return (np.ascontiguousarray(row[:, :-1]), np.ascontiguousarray(row[:, 1:]),
            np.ascontiguousarray(positions))


def input_dtypes(cfg):
    return ("int32", "int32", "int32")


# ---- work, from shapes ----------------------------------------------------

def _pairs(cfg, seq_len):
    """(causal pairs, selected pairs) of one row of one layer: s <= t, and
    min(topk, t + 1) a query."""
    k = min(sizes(cfg)["K"], seq_len)
    return seq_len * (seq_len + 1) // 2, k * (k + 1) // 2 + (seq_len - k) * k


def matmul_params_per_token(cfg):
    """Weights one token is multiplied by in a forward pass here: the routed
    experts at the expected top_k * held / experts slots a token."""
    z = sizes(cfg)
    D = z["D"]
    attn = 2 * D * z["H"] * z["d"] + 2 * D * z["Hk"] * z["d"]
    index = D * (z["Hi"] * z["di"] + z["di"] + z["Hi"])
    moe = D * z["E"] + 3 * D * z["Fe"] * z["top_k"] * z["held"] / z["E"]
    return z["L"] * (attn + index + moe) + D * z["V"]


def flops_per_sample(cfg, shape):
    """Model FLOPs of one row, forward + backward (3x the forward's): 2 a
    weight a token; the main attention's q.k and p.v on the SELECTED pairs
    (what the mask throws away is not model work); the indexer's products on
    the causal pairs; and once, forward only, the q.k of the head-mean
    probabilities. Recompute, norms, rotations and the selection itself are
    not counted."""
    z = sizes(cfg)
    S = shape["seq_len"]
    causal, selected = _pairs(cfg, S)
    forward = (S * 2 * matmul_params_per_token(cfg)
               + z["L"] * z["H"] * selected * 4 * z["d"]
               + z["L"] * causal * 2 * z["Hi"] * z["di"])
    return 3 * forward + z["L"] * z["H"] * selected * 2 * z["d"]


def dsa_index_work(cfg, shape):
    """The least work of ONE step's indexer (every layer: the scores, the
    loss on them and its backward, whatever implements them): 2 * heads * dim
    FLOPs a causal pair forward and twice that backward; q_I, k_I, w in the
    served type and the float32 scores, and their cotangents, once each."""
    z = sizes(cfg)
    B, S = shape["batch"], shape["seq_len"]
    causal, _ = _pairs(cfg, S)
    flops = z["L"] * B * causal * 3 * 2 * z["Hi"] * z["di"]
    per_token = (z["Hi"] * z["di"] + z["di"] + z["Hi"]) * _itemsize(cfg)
    bytes_ = z["L"] * B * 2 * (S * per_token + causal * 4)
    return {"flops": float(flops), "bytes": float(bytes_)}


def dsa_topk_work(cfg, shape):
    """The least work of ONE step's selections: the causal float32 scores
    read once and the int8 mask written once (memory-bound; a comparison a
    score is the only arithmetic counted)."""
    z = sizes(cfg)
    causal, _ = _pairs(cfg, shape["seq_len"])
    pairs = z["L"] * shape["batch"] * causal
    return {"flops": float(pairs), "bytes": float(pairs * (4 + 1))}


def dsa_attention_work(cfg, shape):
    """The least work of ONE step's main attention over the selection: q.k
    and p.v at the head width on the SELECTED pairs, forward and 2.5x that
    backward, and one more q.k for the head-mean probabilities; q, k, v, o
    once forward, q, k, v, o, do read and dq, dk, dv written backward. A
    kernel that walks every causal tile and masks inside does 1 / 0.4375 of
    this at 8,192 tokens."""
    z = sizes(cfg)
    B, S = shape["batch"], shape["seq_len"]
    _, selected = _pairs(cfg, S)
    flops = z["L"] * B * z["H"] * selected * z["d"] * (4 * 3.5 + 2)
    q, kv = z["H"] * z["d"], 2 * z["Hk"] * z["d"]
    bytes_ = z["L"] * B * S * _itemsize(cfg) * ((2 * q + kv) + (4 * q + 2 * kv))
    return {"flops": float(flops), "bytes": float(bytes_)}


def moe_expert_work(cfg, shape):
    """The least work of ONE step's held experts (every layer, forward +
    backward): the three matmuls of the slots routed here at their expected
    count (tokens * top_k * held / experts), and the held experts' weights
    read once."""
    z = sizes(cfg)
    slots = shape["batch"] * shape["seq_len"] * z["top_k"] * z["held"] / z["E"]
    expert = 3 * z["D"] * z["Fe"]
    flops = z["L"] * 3 * 2 * expert * slots
    bytes_ = z["L"] * z["held"] * expert * _itemsize(cfg)
    return {"flops": float(flops), "bytes": float(bytes_)}
