"""What JoyAI-LLM-Flash forced into the framework, on the CPU at small sizes
against the plain float32 reference (``benchmark/chip/reference/
joyai_llm_flash.py``) on seeded weights: rotary positions in interleaved
pairs; the latent attention with a low-rank query and rotated rope columns;
the multi-token prediction module and its masked last target; the whole
model's loss and every gradient; the expert shares adding up to the uncut
layer, a main one and the module's; a record -> backward -> Trainer.step run
with remat rows; the two tallies on the build span; and the programs of the
other cells' shared blocks (Kimi's ``MLAMixer``, Keye's ``F.rope``), whose
jaxprs are the parent's.

Tolerances: both sides compute in float32 (the program's weights cast to
float32, the reference under ``highest`` precision), so what is left is
summation order: 1e-5 of the largest value for a block, 2e-4 for a gradient
through seven layers and two heads (Keye's, ``tests/chip_benchmark``)."""
import hashlib
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import functionalize
from mxnet_tpu.gluon.model_zoo import joyai_llm_flash
from mxnet_tpu.ndarray import op_impl_nn

F32, BF16 = jnp.float32, jnp.bfloat16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# published keys at toy widths (the configuration's own rehearsal widths): 2
# layers (one dense, one expert), 4 heads of 16 + 8 (q.k) and 16 (v), q rank
# 48, kv rank 32, 8 experts 32 wide of which 2 a token, a shared one
_TOY = dict(hidden_size=64, num_hidden_layers=2, vocab_size=256, rms_norm_eps=1e-6,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4,
            rope_interleave=True, intermediate_size=128, first_k_dense_replace=1,
            n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
            scoring_func="sigmoid", n_group=1, topk_group=1,
            num_nextn_predict_layers=1, mtp_loss_weight=0.3, loss_chunks=2)


@pytest.fixture(scope="module")
def reference():
    chip = os.path.join(ROOT, "benchmark", "chip")
    sys.path.insert(0, chip)
    yield importlib.import_module("reference.joyai_llm_flash")
    sys.path.remove(chip)


def _normal(seed, *shapes):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), s, F32)
            for i, s in enumerate(shapes)]


def _close(got, want, tol):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) / scale < tol


def _positions(batch, length):
    return jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32), (batch, length))


def _ref_cfg(**over):
    return {**_TOY, "router_experts": 8, "experts_held": [0, 8], **over}


def _weights(block, prefix):
    """{reference name: float32 array} of a block's parameters."""
    return {k[len(prefix):] if prefix else k: v.data()._data.astype(F32)
            for k, v in block.collect_params().items()}


# ---- rotary positions in interleaved pairs --------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 3, 16, 8), (2, 16, 8)], ids=["heads", "shared_key"])
def test_interleaved_rope_turns_each_adjacent_pair(shape):
    (x,) = _normal(0, shape)
    t = _positions(2, 16)
    got = mx.nd.rope(mx.nd.array(x), mx.nd.array(t, dtype="int32"), theta=1e4,
                     interleave=True)._data
    # the pairwise formula, written out in numpy float64
    xs = np.asarray(x, np.float64)
    angle = np.arange(16)[:, None] * 1e4 ** (-np.arange(0, 8, 2) / 8)     # (S, 4)
    if xs.ndim == 4:
        angle = angle[None]
    even, odd = xs[..., 0::2], xs[..., 1::2]
    want = np.empty_like(xs)
    want[..., 0::2] = even * np.cos(angle) - odd * np.sin(angle)
    want[..., 1::2] = odd * np.cos(angle) + even * np.sin(angle)
    _close(got, jnp.asarray(want, F32), 1e-5)
    # position 0 is the identity; each pair keeps its norm
    _close(got[..., 0, :] if xs.ndim == 4 else got[:, 0], x[..., 0, :] if xs.ndim == 4
           else x[:, 0], 1e-6)
    _close(got[..., 0::2] ** 2 + got[..., 1::2] ** 2,
           x[..., 0::2] ** 2 + x[..., 1::2] ** 2, 1e-5)


def test_interleaved_rope_is_rotate_half_on_the_pairs_regrouped():
    """The same rotation as rotate-half once the columns are regrouped, [even
    columns, odd columns] (how DeepSeek-V3's released code applies it)."""
    (x,) = _normal(1, (2, 3, 16, 8))
    t = mx.nd.array(_positions(2, 16), dtype="int32")
    inter = mx.nd.rope(mx.nd.array(x), t, theta=3.2e7, interleave=True)._data
    regrouped = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = mx.nd.rope(mx.nd.array(regrouped), t, theta=3.2e7)._data
    _close(jnp.concatenate([inter[..., 0::2], inter[..., 1::2]], -1), half, 1e-6)


# ---- latent attention with a low-rank query and rotated rope columns ------------------------

def _mla_block(seed=0, **kw):
    mx.random.seed(seed)
    block = nn.MLAMixer(64, 4, 32, 16, 8, 16, epsilon=1e-6, prefix="mla_", **kw)
    block.initialize(init=mx.initializer.Normal(0.1), ctx=mx.cpu(0))
    return block


def test_mla_with_a_low_rank_query_and_rope_is_the_references(reference):
    block = _mla_block(q_lora_rank=48, rope_theta=1e4)
    assert sorted(block.collect_params()) == sorted(
        "mla_" + n for n in ("q_a_weight", "q_norm_gamma", "q_b_weight", "kva_weight",
                             "kv_norm_gamma", "kvb_weight", "o_weight"))
    (x,) = _normal(2, (2, 32, 64))
    got = block(mx.nd.array(x), mx.nd.array(_positions(2, 32), dtype="int32"))._data
    with jax.default_matmul_precision("highest"):
        want = reference._mla(_ref_cfg(), lambda f: f, _weights(block, ""), "mla_", x)
    _close(got, want, 1e-5)
    # the rotation is there (its scores depend on distance only: positions
    # shifted by one give the same output, a block that never turns another)
    moved = block(mx.nd.array(x), mx.nd.array(_positions(2, 32) + 1, dtype="int32"))._data
    _close(moved, got, 1e-5)
    nope = _mla_block(q_lora_rank=48)
    for name, param in nope.collect_params().items():
        param.set_data(block.collect_params()[name].data())
    unturned = nope(mx.nd.array(x))._data
    assert float(jnp.abs(unturned - got).max()) > 1e-2 * float(jnp.abs(got).max())


def test_mla_tallies_its_rotations_as_traced():
    before = mx.profiler.counters(device=False)["mla_rope_calls"]
    x = mx.nd.array(np.ones((1, 8, 64), "float32"))
    _mla_block()(x)                                               # NoPE: none
    assert mx.profiler.counters(device=False)["mla_rope_calls"] == before
    _mla_block(rope_theta=1e4)(x, mx.nd.array(_positions(1, 8), dtype="int32"))
    assert mx.profiler.counters(device=False)["mla_rope_calls"] == before + 1


# ---- the whole model against the reference ---------------------------------------------------

def _toy_model(cfg=None, dtype="float32", seed=0):
    mx.random.seed(seed)
    model = joyai_llm_flash(cfg or _TOY, prefix="joyai_")
    model.initialize(init=mx.initializer.Normal(0.05), ctx=mx.cpu(0))
    if dtype != "float32":
        model.cast(dtype)
    return model


def _batch(seed, batch=2, length=32):
    rng = np.random.default_rng(seed)
    row = rng.integers(0, 256, (batch, length + 1))
    return (jnp.asarray(row[:, :-1], jnp.int32), jnp.asarray(row[:, 1:], jnp.int32))


def _seed_the_bias(model, seed=7):
    """A router bias that moves the choice (the initialiser left it 0)."""
    for name, p in model.collect_params().items():
        if name.endswith("router_running_bias"):
            p.set_data(mx.nd.array(np.random.default_rng(seed).normal(0, 0.3, p.shape)
                                   .astype("float32")))


def test_the_whole_models_loss_and_every_gradient_are_the_references(reference):
    model = _toy_model()
    _seed_the_bias(model)
    fn, params = functionalize(model, training=True, ctx=mx.cpu(0))
    trained = {k: v for k, v in params.items() if "running_" not in k}
    rest = {k: v for k, v in params.items() if "running_" in k}
    ids, labels = _batch(3)
    loss, grads = jax.value_and_grad(
        lambda p: fn({**p, **rest}, jax.random.PRNGKey(0), ids, labels).sum())(trained)
    w = {k: v.astype(F32) for k, v in params.items()}
    fixed = {k: v for k, v in w.items() if "running_" in k}
    with jax.default_matmul_precision("highest"):
        want, ref_grads = jax.value_and_grad(lambda m: reference.loss_sum(
            _ref_cfg(), {**m, **fixed}, (ids, labels), lambda f: f))(
                {k: v for k, v in w.items() if "running_" not in k})
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert set(grads) == set(ref_grads)
    for name, g in ref_grads.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(grads[name] - g).max()) / scale < 2e-4, name
    # every parameter of the module has a gradient, the borrowed embedding and
    # head take the module's loss too
    lam0 = _ref_cfg(mtp_loss_weight=0.0)
    with jax.default_matmul_precision("highest"):
        plain = jax.grad(lambda m: reference.loss_sum(
            lam0, {**m, **fixed}, (ids, labels), lambda f: f))(
                {k: v for k, v in w.items() if "running_" not in k})
    for name in ("joyai_embed_weight", "joyai_head_weight"):
        assert float(jnp.abs(plain[name] - ref_grads[name]).max()) > 0, name
    assert all(float(jnp.abs(g).max()) > 0 for g in grads.values())


def test_the_stages_are_the_loss(reference):
    """``stages`` (what the lean follower differentiates piece by piece) owns
    each weight once and composes to ``loss_sum``."""
    model = _toy_model()
    w = {k: v.data()._data for k, v in model.collect_params().items()}
    pieces = reference.stages(_ref_cfg(), lambda f: f)
    owners = [[k for k in w if k.startswith(prefixes)] for prefixes, _ in pieces]
    assert sorted(sum(owners, [])) == sorted(w)
    assert len(pieces) == 2 + _TOY["num_hidden_layers"]


def _mtp_parts(seed=4):
    """A model's module, and what it is called with: h, the next ids, the
    positions, the targets and the mask, as the model builds them."""
    model = _toy_model(seed=seed)
    ids, labels = _batch(seed)
    h, = _normal(seed, (2, 32, 64))
    ahead = jnp.concatenate([labels[:, 1:], labels[:, :1]], 1)
    mask = jnp.concatenate([jnp.ones((2, 31), jnp.int32), jnp.zeros((2, 1), jnp.int32)], 1)
    return model, h, labels, ahead, mask


def test_the_mtp_module_is_the_references_and_its_last_target_is_masked(reference):
    model, h, labels, ahead, mask = _mtp_parts()
    mtp = model.mtp
    assert mtp.layer.prefix == "joyai_mtp_layer_"
    assert not any(p is model.embed.weight for p in mtp.collect_params().values())
    args = [mx.nd.array(h), mx.nd.array(labels, dtype="int32"),
            mx.nd.array(_positions(2, 32), dtype="int32"),
            mx.nd.array(ahead, dtype="int32"), mx.nd.array(mask, dtype="int32")]
    before = mx.profiler.counters(device=False)["mtp_modules"]
    got = float(mtp(*args)._data[0])
    assert mx.profiler.counters(device=False)["mtp_modules"] == before + 1
    w = {k: v.data()._data for k, v in model.collect_params().items()}
    cfg = _ref_cfg(mtp_loss_weight=1.0)
    pieces = reference.stages(cfg, lambda f: f)
    heads = pieces[-1][1]
    table = w["joyai_embed_weight"]
    with jax.default_matmul_precision("highest"):      # lambda 1 less lambda 0
        both = heads(w, (h, table[labels]), (None, labels))
        only_main = reference.stages(_ref_cfg(mtp_loss_weight=0.0), lambda f: f)[-1][1](
            w, (h, table[labels]), (None, labels))
    assert got == pytest.approx(float(both - only_main), rel=1e-5)
    # the masked target: whatever the last label, the loss is the same, and
    # neither the last hidden state nor the last next-token reaches it
    other = ahead.at[:, -1].set((ahead[:, -1] + 17) % 256)
    args2 = args[:3] + [mx.nd.array(other, dtype="int32"), args[4]]
    assert float(mtp(*args2)._data[0]) == pytest.approx(got, rel=1e-6)
    fn, params = functionalize(mtp, training=True, ctx=mx.cpu(0))
    # the borrowed embedding and head are read where they live: the model
    g = jax.grad(lambda hh: fn(params, jax.random.PRNGKey(0), hh,
                               *[a._data for a in args[1:]]).sum())(h)
    assert float(jnp.abs(g[:, -1]).max()) == 0.0
    assert float(jnp.abs(g[:, :-1]).max()) > 0.0


# ---- the shares add up -----------------------------------------------------------------------

@pytest.mark.parametrize("which", ["joyai_layer1_", "joyai_mtp_layer_"],
                         ids=["main_layer", "mtp_layer"])
def test_the_shares_partial_sums_add_up_to_the_uncut_layer(reference, which):
    """The guide's test of a cut by experts: each of 4 chips holds 2 experts of
    8; what each share adds over the layer without its experts, summed, plus
    that common part (attention, the shared expert) once, is the uncut
    reference's layer."""
    whole = _toy_model(seed=5)
    _seed_the_bias(whole)
    w = {k: v.data()._data for k, v in whole.collect_params().items()}
    x, = _normal(6, (2, 32, 64))
    pos = mx.nd.array(_positions(2, 32), dtype="int32")

    def layer_of(model):
        return model.mtp.layer if "mtp" in which else model.layers[1]

    def share(lo, hi, zero=False):
        model = _toy_model({**_TOY, "experts_held": [lo, hi]}, seed=5)
        for name, p in model.collect_params().items():
            value = w[name]
            if "experts_" in name and "running" not in name:
                value = jnp.zeros_like(value[lo:hi]) if zero else value[lo:hi]
            elif name.endswith("running_slots"):
                value = jnp.zeros(p.shape, F32)
            p.set_data(mx.nd.array(np.asarray(value)))
        return layer_of(model)(mx.nd.array(x), pos)._data

    common = share(0, 2, zero=True)
    total = common + sum(share(lo, lo + 2) - common for lo in (0, 2, 4, 6))
    with jax.default_matmul_precision("highest"):
        want = reference._layer(_ref_cfg(), lambda f: f, w, which, x, False)
    _close(total, want, 1e-5)
    _close(share(0, 8), want, 1e-5)


# ---- record -> backward -> Trainer.step, remat rows, the build span ---------------------------

def _train_once(rows):
    model = _toy_model(seed=8)
    if rows is not None:
        model.remat_per_layer(rows=rows)
    model.hybridize()
    trainer = gluon.Trainer(model.collect_params(), "adamw",
                            {"learning_rate": 1e-3, "wd": 0.1})
    ids, labels = (mx.nd.array(a, dtype="int32") for a in _batch(9))
    with autograd.record():
        loss = model(ids, labels)
    loss.backward()
    grads = {k: p.grad()._data for k, p in model.collect_params().items()
             if p.grad_req != "null"}
    trainer.step(64)
    return float(loss._data[0]), grads, model


def test_a_trainer_step_with_remat_rows_is_the_plain_steps():
    plain_loss, plain, _ = _train_once(None)
    mx.profiler.set_state("run")
    try:
        loss, grads, model = _train_once(1)
    finally:
        mx.profiler.set_state("stop")
    assert np.isfinite(loss) and loss == pytest.approx(plain_loss, rel=1e-5)
    for name, g in plain.items():
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(grads[name] - g).max()) / scale < 1e-4, name
    moved = model.collect_params()["joyai_mtp_eh_weight"]
    assert float(jnp.abs(moved.data()._data).max()) > 0
    from mxnet_tpu.profiler import _EVENTS
    span = [e["args"] for e in _EVENTS if e["name"] == "mxtpu/cachedop/build"][-1]
    # two main layers and the module's: three rotating mixers, one module
    assert (span["mla_rope_calls"], span["mtp_modules"]) == (3, 1)


def test_only_the_built_settings_are_accepted():
    for key, value in (("rope_interleave", False), ("scoring_func", "softmax"),
                       ("n_group", 8), ("num_nextn_predict_layers", 2)):
        with pytest.raises(ValueError, match=key if key != "num_nextn_predict_layers"
                           else "multi-token"):
            joyai_llm_flash({**_TOY, key: value})
    assert joyai_llm_flash({**_TOY, "num_nextn_predict_layers": 0}).mtp is None


# ---- the other cells' programs of the shared blocks are the parent's -------------------------

# sha256 of the parent's (PR 36) jaxprs, at toy widths in bfloat16: Kimi's
# MLAMixer (NoPE, one query matrix) through `functionalize`, on the CPU's
# flash twin and in interpret mode (the Pallas call with its pad to 256);
# Keye's F.rope with three position streams. The kernel form keeps its hash
# since the kernel took v at its own width: at these toy widths (q.k 16 + 8,
# v 16) both round to one 128-lane tile, so the op pads all three as before;
# the two-width calls are `tests/test_flash_two_widths.py`'s
_PARENT = {
    ("kimi_mla", "forward", "twin"): "de2c65afde7bfec9acfa523f0a56c9e5f06003eb736c49fe3fc58c6aa7756739",
    ("kimi_mla", "gradient", "twin"): "402e6dc727882882fd1b9d3fcf43b9e876c7b51301c93e7a7975863d5c1785e7",
    ("kimi_mla", "forward", "kernel"): "aaf49e1cceba7868c3781bbbfb7523bbf6151294d0234012a47c8ddc44a36aea",
    ("kimi_mla", "gradient", "kernel"): "62b7260e7e8be365863907d751fdc7d14418ec25c797cccc5c9d002459f6f623",
    ("keye_rope", "forward", "twin"): "90ac772dc48a4c82e53848028556065cbbf8c562045d50c92ac24cf53d92b752",
    ("keye_rope", "gradient", "twin"): "70bc7a17d1192e551d5a6b649bbd1b1fcc43cd12bb004147de7ba36664ae53eb",
}


def _jaxpr(cell, what):
    key = jax.random.PRNGKey(0)
    if cell == "kimi_mla":
        mx.random.seed(0)
        block = nn.MLAMixer(64, 4, 32, 16, 8, 16, prefix="mla_")
        block.initialize(init=mx.initializer.Normal(0.05), ctx=mx.cpu(0))
        block.cast("bfloat16")
        fn, params = functionalize(block, training=True, ctx=mx.cpu(0))
        f = lambda p, x: fn(p, key, x)                           # noqa: E731
        if what == "gradient":
            f = jax.grad(lambda p, x: fn(p, key, x).astype(F32).sum(), (0, 1))
        return str(jax.make_jaxpr(f)(params, jax.ShapeDtypeStruct((2, 64, 64), BF16)))

    def f(x, p):
        return op_impl_nn.rope(x, p, theta=1e7, sections=(2, 2, 4))
    if what == "gradient":
        f = jax.grad(lambda x, p: op_impl_nn.rope(x, p, theta=1e7, sections=(2, 2, 4))
                     .astype(F32).sum())
    return str(jax.make_jaxpr(f)(jax.ShapeDtypeStruct((2, 4, 64, 16), BF16),
                                 jax.ShapeDtypeStruct((2, 3, 64), jnp.int32)))


@pytest.mark.parametrize("cell,what,form", sorted(_PARENT))
def test_the_other_cells_jaxprs_are_the_parents(monkeypatch, cell, what, form):
    if form == "kernel":
        monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    text = _jaxpr(cell, what)
    assert ("pallas_call" in text) == (form == "kernel")
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT[cell, what, form]
