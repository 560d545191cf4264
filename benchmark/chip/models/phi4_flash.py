"""phi4_flash: the configuration built through the framework's normal entry
(``model_zoo.phi4_flash(config)``, whose ``model(ids, labels)`` is the summed
token loss; each decoder layer marked for remat as Kimi's are, one row at a
time), the seeded weights the benchmark hands to it AND to the plain
reference, and the functions that count its work from shapes.

The configuration's file keeps the published keys; ``num_hidden_layers`` is
the number of layers HELD (the range ``layers_held`` of the
``published_layers``), and the model zoo, whose rule for a layer's kind needs
the published depth, takes that as its ``num_hidden_layers``.
"""
from __future__ import annotations

from mxnet_tpu.gluon.model_zoo.phi4_flash import layer_kind

# the same traffic as Kimi's cell: full-length rows of ids from the slice,
# labels the ids shifted by one; a sample is one row
from models.kimi_linear import (_itemsize, host_batch, input_dtypes,  # noqa: F401
                                samples_and_denominator)


def zoo_config(cfg):
    """The configuration as ``model_zoo.phi4_flash`` reads it."""
    return {**cfg, "num_hidden_layers": cfg["published_layers"]}


def sizes(cfg):
    d = cfg["hidden_size"]
    lo, hi = cfg["layers_held"]
    kinds = {i: layer_kind(i, cfg["published_layers"], cfg["mb_per_layer"])
             for i in range(lo, hi)}
    return dict(
        D=d, V=cfg["vocab_size"], F=cfg["intermediate_size"],
        H=cfg["num_attention_heads"], Hk=cfg["num_key_value_heads"],
        d=d // cfg["num_attention_heads"], W=cfg["sliding_window"],
        Ci=cfg["mamba_expand"] * d, N=cfg["mamba_d_state"],
        K=cfg["mamba_d_conv"], R=cfg["mamba_dt_rank"], kinds=kinds,
        n={k: sum(v == k for v in kinds.values())
           for k in ("mamba", "mamba_source", "window", "attention_source",
                     "gmu", "cross")})


def param_specs(cfg):
    """Ordered ``(name, shape, dtype, init)``; names are the program's own
    parameter names below the model's prefix. ``init`` is ``normal`` (std
    from ``init_std``), ``zeros``, ``ones`` or a number. The head is the
    embedding: one leaf."""
    z = sizes(cfg)
    D, dt = z["D"], cfg["dtype"]
    Ci, N, R, d = z["Ci"], z["N"], z["R"], z["d"]
    out = [("phi_embed_weight", (z["V"], D), dt, "normal")]
    for i, kind in z["kinds"].items():
        p = f"phi_layer{i}_"
        out += [(p + "mixer_norm_gamma", (D,), dt, "ones"),
                (p + "mixer_norm_beta", (D,), dt, "zeros")]
        if kind in ("mamba", "mamba_source"):
            m = p + "mamba_"
            out += [(m + "a_log", (Ci, N), dt, "normal"),
                    (m + "d", (Ci,), dt, "ones"),
                    (m + "in_weight", (2 * Ci, D), dt, "normal"),
                    (m + "conv_weight", (Ci, z["K"]), dt, "normal"),
                    (m + "conv_bias", (Ci,), dt, "normal"),
                    (m + "x_weight", (R + 2 * N, Ci), dt, "normal"),
                    (m + "dt_weight", (Ci, R), dt, "normal"),
                    (m + "dt_bias", (Ci,), dt, cfg["mamba_dt_bias_init"]),
                    (m + "out_weight", (D, Ci), dt, "normal")]
        elif kind == "gmu":
            out += [(p + "gmu_in_weight", (Ci, D), dt, "normal"),
                    (p + "gmu_out_weight", (D, Ci), dt, "normal")]
        else:
            m = p + "attn_"
            out += [(m + f"lambda_{n}", (d,), dt, "normal")
                    for n in ("q1", "k1", "q2", "k2")]
            out.append((m + "subln_gamma", (2 * d,), dt, "ones"))
            wide = z["H"] * d if kind == "cross" else (z["H"] + 2 * z["Hk"]) * d
            proj = "q_" if kind == "cross" else "qkv_"
            out += [(m + proj + "weight", (wide, D), dt, "normal"),
                    (m + proj + "bias", (wide,), dt, "normal"),
                    (m + "o_weight", (D, z["H"] * d), dt, "normal"),
                    (m + "o_bias", (D,), dt, "normal")]
        out += [(p + "mlp_norm_gamma", (D,), dt, "ones"),
                (p + "mlp_norm_beta", (D,), dt, "zeros"),
                (p + "mlp_gate_up_weight", (2 * z["F"], D), dt, "normal"),
                (p + "mlp_down_weight", (D, z["F"]), dt, "normal")]
    out += [("phi_final_norm_gamma", (D,), dt, "ones"),
            ("phi_final_norm_beta", (D,), dt, "zeros")]
    return out


def init_std(cfg, name, shape):
    if name.endswith("conv_weight"):
        return cfg["mamba_conv_init_std"]
    if name.endswith("a_log"):
        return cfg["mamba_a_log_init_std"]
    if "_lambda_" in name:
        return cfg["lambda_init_std"]
    return cfg["initializer_range"]


def build(cfg, ctxs):
    """The Gluon model on ``ctxs``; returns ``(block, forward)`` where
    ``forward(ids, labels)`` gives the batch's SUMMED next-token loss (shape
    (1,)) and is what the loop calls under ``record()``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import phi4_flash

    class LM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = phi4_flash(zoo_config(cfg), prefix="phi_")

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


# ---- work, from shapes ----------------------------------------------------

def matmul_params_per_token(cfg):
    """Weights one token is multiplied by in a forward pass here (the head is
    the embedding read as a matrix; the embedding's gather is not one)."""
    z = sizes(cfg)
    D, Ci, hd = z["D"], z["Ci"], z["H"] * z["d"]
    mamba = D * 2 * Ci + Ci * (z["R"] + 2 * z["N"]) + z["R"] * Ci + Ci * D
    attn = D * (z["H"] + 2 * z["Hk"]) * z["d"] + hd * D
    per_kind = {"mamba": mamba, "mamba_source": mamba, "window": attn,
                "attention_source": attn, "gmu": 2 * D * Ci, "cross": 2 * D * hd}
    return (sum(per_kind[k] + 3 * D * z["F"] for k in z["kinds"].values())
            + D * z["V"])


def _pair_key_flops(z):
    """Forward FLOPs of one (query token, key) of one pair of heads: two
    score maps at the head width and one product of their difference with the
    paired value, twice the head width."""
    return 2 * 2 * z["d"] + 2 * 2 * z["d"]


def _band_keys(S, W):
    """(query, key) pairs of a causal window W over a row of S tokens."""
    W = min(W, S)
    return S * W - W * (W - 1) // 2


_SCAN_FLOPS = 7     # a state element a token: three multiply-adds and one exp


def flops_per_sample(cfg, shape):
    """Model FLOPs of one row, forward + backward (3x the forward's): 2 a
    weight a token; differential attention's products over the keys each
    layer's mask leaves (the 512-wide band, or the causal half of S^2); the
    scan's three multiply-adds and one exponential a state element a token.
    Recompute, norms, gates, convolutions and the embedding's gather are not
    counted."""
    z = sizes(cfg)
    S = shape["seq_len"]
    pairs = z["H"] // 2
    keys = (z["n"]["window"] * _band_keys(S, z["W"])
            + (z["n"]["attention_source"] + z["n"]["cross"]) * _band_keys(S, S))
    scans = z["n"]["mamba"] + z["n"]["mamba_source"]
    return 3 * (S * 2 * matmul_params_per_token(cfg)
                + pairs * keys * _pair_key_flops(z)
                + scans * S * z["Ci"] * z["N"] * _SCAN_FLOPS)


def ssm_work(cfg, shape):
    """The least work of ONE step's selective scans (every Mamba layer,
    forward + backward, whatever blocks implement them): the recurrence's
    three multiply-adds and one exponential a state element a token, twice
    that backward; u, delta, the output and their cotangents (C wide) and B,
    C and theirs (N wide) once each, in the served type."""
    z = sizes(cfg)
    tokens = shape["batch"] * shape["seq_len"] * (z["n"]["mamba"]
                                                  + z["n"]["mamba_source"])
    flops = tokens * 3 * z["Ci"] * z["N"] * _SCAN_FLOPS
    bytes_ = tokens * 2 * _itemsize(cfg) * (3 * z["Ci"] + 2 * z["N"])
    return {"flops": float(flops), "bytes": float(bytes_)}


def _attention_work(cfg, shape, layers, keys, own_kv):
    """Differential attention's least work over ``layers`` layers whose mask
    leaves ``keys`` (query, key) pairs a row: the products forward and twice
    backward; q, o (and k, v) once forward, q, k, v, o, do read and dq (dk,
    dv) written backward. ``own_kv``: the layers among them that project
    their own k and v (a cross layer reads another's: read, not written)."""
    z = sizes(cfg)
    B, S = shape["batch"], shape["seq_len"]
    flops = layers * B * (z["H"] // 2) * keys * 3 * _pair_key_flops(z)
    q = z["H"] * z["d"]             # q's columns; o's are as many
    kv = 2 * z["Hk"] * z["d"]       # k's and v's columns together
    per_token = layers * (2 * q + kv + 4 * q + q + kv) + own_kv * kv
    return {"flops": float(flops),
            "bytes": float(B * S * _itemsize(cfg) * per_token)}


def swa_attention_work(cfg, shape):
    """ONE step's sliding-window layers: the ``sliding_window``-wide causal
    band (a token, itself and the 511 before it), q.k at 64 twice and p.v at
    128 a pair."""
    z = sizes(cfg)
    n = z["n"]["window"]
    return _attention_work(cfg, shape, n, _band_keys(shape["seq_len"], z["W"]), n)


def yoco_attention_work(cfg, shape):
    """ONE step's full-attention layer and the cross-attention layers that
    read its keys and values: the causal half of S^2 each."""
    z = sizes(cfg)
    S = shape["seq_len"]
    own = z["n"]["attention_source"]
    return _attention_work(cfg, shape, own + z["n"]["cross"], _band_keys(S, S), own)
