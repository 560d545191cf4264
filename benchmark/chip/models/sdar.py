"""sdar: the configuration built through the framework's normal entry
(``model_zoo.sdar(config)``, whose ``model(clean_ids, noisy_ids, positions,
targets, weights)`` gives the summed weighted token loss of block-diffusion
training; each decoder layer marked for remat as Keye's are, one row at a
time), the seeded weights the benchmark hands to it AND to the plain
reference, the noised batches both read, and the functions that count its
work from shapes.

The configuration's file keeps the published keys; three are this chip's
share of the deployment: ``num_hidden_layers`` is the number of layers HELD
(the range ``layers_held`` of the ``published_layers``), ``num_experts`` the
number of experts HELD (the range ``experts_held`` of the router's
``router_experts``) and ``vocab_size`` the slice of the vocabulary held; the
model zoo takes the published depth and the router's width under those keys.
A sample is one row of ``seq_len`` clean tokens, which the model runs with
its noised copy: 2 * ``seq_len`` positions.
"""
from __future__ import annotations

import numpy as np

from models.kimi_linear import _itemsize, samples_and_denominator  # noqa: F401


def zoo_config(cfg):
    """The configuration as ``model_zoo.sdar`` reads it."""
    return {**cfg, "num_experts": cfg["router_experts"],
            "num_hidden_layers": cfg["published_layers"]}


def sizes(cfg):
    lo, hi = cfg["experts_held"]
    first, last = cfg["layers_held"]
    return dict(
        L=last - first, layers=range(first, last), D=cfg["hidden_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        Hk=cfg["num_key_value_heads"], d=cfg["head_dim"],
        Fe=cfg["moe_intermediate_size"], E=cfg["router_experts"], held=hi - lo,
        top_k=cfg["num_experts_per_tok"], block=cfg["block_length"])


def param_specs(cfg):
    """Ordered ``(name, shape, dtype, init)``; names are the program's own
    parameter names below the model's prefix. ``init`` is ``normal`` (std
    from ``init_std``), ``zeros``, ``ones`` or a number."""
    z = sizes(cfg)
    D, dt, d = z["D"], cfg["dtype"], z["d"]
    out = [("sdar_embed_weight", (z["V"], D), dt, "normal")]
    for i in z["layers"]:
        p = f"sdar_layer{i}_"
        a, m = p + "attn_", p + "moe_"
        out += [(p + "attn_norm_gamma", (D,), dt, "ones"),
                (a + "q_weight", (z["H"] * d, D), dt, "normal"),
                (a + "k_weight", (z["Hk"] * d, D), dt, "normal"),
                (a + "v_weight", (z["Hk"] * d, D), dt, "normal"),
                (a + "o_weight", (D, z["H"] * d), dt, "normal"),
                (a + "q_norm_gamma", (d,), dt, "ones"),
                (a + "k_norm_gamma", (d,), dt, "ones"),
                (p + "ffn_norm_gamma", (D,), dt, "ones"),
                (m + "router_weight", (z["E"], D), dt, "normal"),
                (m + "running_slots", (z["held"] + 1,), "float32", "zeros"),
                (m + "experts_gate_up_weight", (z["held"], 2 * z["Fe"], D), dt, "normal"),
                (m + "experts_down_weight", (z["held"], D, z["Fe"]), dt, "normal")]
    out += [("sdar_final_norm_gamma", (D,), dt, "ones"),
            ("sdar_head_weight", (z["V"], D), dt, "normal")]
    return out


def init_std(cfg, name, shape):
    return cfg["initializer_range"]


def build(cfg, ctxs):
    """The Gluon model on ``ctxs``; returns ``(block, forward)`` where
    ``forward(clean_ids, noisy_ids, positions, targets, weights)`` gives the
    batch's SUMMED weighted loss (shape (1,)) and is what the loop calls under
    ``record()``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import sdar

    class LM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = sdar(zoo_config(cfg), prefix="sdar_")

        def hybrid_forward(self, F, clean_ids, noisy_ids, positions, targets,
                           weights):
            return self.net(clean_ids, noisy_ids, positions, targets, weights)

    model = LM()
    model.initialize(init=mx.initializer.Zero(), ctx=ctxs)
    model.cast(cfg["dtype"])
    hyb = dict(cfg.get("hybridize", {}))
    rows = hyb.pop("remat_rows", None)
    if hyb.pop("remat", None) == "per_layer":
        model.net.remat_per_layer(rows=rows)
    model.hybridize(**hyb)
    return model, model


def noise(cfg, clean, rng):
    """Block diffusion's forward process on ``clean`` (B, L) ids: each block
    of ``block_length`` tokens draws its level t ~ U[t_min, 1] and each of its
    tokens becomes ``mask_token_id`` with probability t. Returns (the noised
    ids, the weights: 1/t where a token was masked, else 0)."""
    b, n = clean.shape
    block, lo = cfg["block_length"], cfg["noise_level_min"]
    t = rng.uniform(lo, 1.0, (b, -(-n // block)))
    t = np.repeat(t, block, axis=1)[:, :n]
    masked = rng.random((b, n)) < t
    noisy = np.where(masked, np.int32(cfg["mask_token_id"]), clean).astype(np.int32)
    return noisy, np.where(masked, 1.0 / t, 0.0).astype(np.float32)


def host_batch(cfg, shape, rng):
    """One host batch (numpy): clean ids uniform over this chip's slice of the
    vocabulary but its last id (the mask token), their noised copy and the
    weights (``noise``), the positions 0 .. L - 1 and the targets (the clean
    ids). Full length, no padding."""
    b, s = shape["batch"], shape["seq_len"]
    clean = rng.integers(0, cfg["mask_token_id"], (b, s), dtype=np.int32)
    noisy, weights = noise(cfg, clean, rng)
    positions = np.broadcast_to(np.arange(s, dtype=np.int32), (b, s))
    return (clean, noisy, np.ascontiguousarray(positions), clean.copy(), weights)


def input_dtypes(cfg):
    return ("int32", "int32", "int32", "int32", "float32")


# ---- work, from shapes ----------------------------------------------------

def live_pairs(seq_len, block):
    """(query, key) pairs the block-diffusion mask leaves live in one row of
    ``seq_len`` clean tokens with its noised copy, one head: the clean copy's
    block-causal pairs, the noisy rows' clean keys of earlier blocks and the
    noisy rows' own blocks; L^2 + L * block where the block divides L."""
    sizes_ = [min(block, seq_len - lo) for lo in range(0, seq_len, block)]
    before = np.concatenate([[0], np.cumsum(sizes_)[:-1]])
    return int(sum(n * (b + n) + n * b + n * n for n, b in zip(sizes_, before)))


def matmul_params_per_token(cfg):
    """Weights one position is multiplied by in a forward pass of the layers
    here: the routed experts at the expected top_k * held / experts slots a
    position. The head is counted apart (it reads the noisy half only)."""
    z = sizes(cfg)
    D = z["D"]
    attn = 2 * D * z["H"] * z["d"] + 2 * D * z["Hk"] * z["d"]
    moe = D * z["E"] + 3 * D * z["Fe"] * z["top_k"] * z["held"] / z["E"]
    return z["L"] * (attn + moe)


def flops_per_sample(cfg, shape):
    """Model FLOPs of one row, forward + backward (3x the forward's): 2 a
    weight a position over both copies' 2 * seq_len positions, the head over
    the seq_len noisy positions only, and q.k and p.v at the head width on the
    live pairs of the mask. Recompute, norms, rotations and the router's
    choice are not counted."""
    z = sizes(cfg)
    S = shape["seq_len"]
    forward = (2 * S * 2 * matmul_params_per_token(cfg) + S * 2 * z["D"] * z["V"]
               + z["L"] * z["H"] * live_pairs(S, z["block"]) * 4 * z["d"])
    return 3 * forward


def block_diffusion_attention_work(cfg, shape):
    """The least work of ONE step's attention under the block-diffusion mask
    (every layer, forward + backward): q.k and p.v at the head width on the
    mask's live pairs, the backward 2.5x the forward; q, k, v, o and their
    cotangents in the served type, once each."""
    z = sizes(cfg)
    B, S = shape["batch"], shape["seq_len"]
    flops = z["L"] * B * z["H"] * live_pairs(S, z["block"]) * z["d"] * 4 * 3.5
    per_position = 2 * (2 * z["H"] * z["d"] + 2 * z["Hk"] * z["d"])
    bytes_ = z["L"] * B * 2 * S * _itemsize(cfg) * per_position
    return {"flops": float(flops), "bytes": float(bytes_)}


def moe_expert_work(cfg, shape):
    """The least work of ONE step's held experts (every layer, forward +
    backward; Keye's count over both copies' 2 * seq_len positions): the three
    matmuls of the slots routed here at their expected count (positions *
    top_k * held / experts), and the held experts' weights read once."""
    z = sizes(cfg)
    slots = shape["batch"] * 2 * shape["seq_len"] * z["top_k"] * z["held"] / z["E"]
    expert = 3 * z["D"] * z["Fe"]
    flops = z["L"] * 3 * 2 * expert * slots
    bytes_ = z["L"] * z["held"] * expert * _itemsize(cfg)
    return {"flops": float(flops), "bytes": float(bytes_)}
