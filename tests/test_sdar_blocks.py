"""Block-diffusion training's pieces on the CPU: the flash kernel's static
block-diffusion mask (interpret mode) and ``nn.GQAttention``.

The kernel: q, k and v span a clean copy and a noised copy of a row, the clean
first; every arm (the forward, the fused backward, the split dq / dkv pair)
with grouped key/value heads, at blocks of 1 and 4 and at blocks that straddle
a tile (48 positions against tiles of 128 rows, the copies meeting inside a
tile), against the same kernel given the mask as a ``pair_mask`` operand,
against a dense softmax over an explicit mask built here by loops, and against
the op's ``jax.numpy`` form; the tiles the kernel counts live against the
tiles the mask leaves anything in; the other cells' flash calls trace the
jaxprs they traced before (sha256 pins, ``tests/test_flash_two_widths.py``'s).

Tolerances: float32 inputs, so the kernels' dots run at ``HIGHEST``; what is
left is summation order: 1e-6 of the largest value against the pair-mask call
(the same tiles, the same products), 1e-5 against the dense softmax and the
``jax.numpy`` form."""
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import functionalize
from mxnet_tpu.gluon.model_zoo import sdar
from mxnet_tpu.ndarray import op_impl_nn

import test_flash_two_widths as two_widths

flash_mod = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
F32, BF16 = jnp.float32, jnp.bfloat16


def _explicit_mask(half, block):
    """The mask by its definition, position pair by position pair."""
    n = 2 * half
    m = np.zeros((n, n), bool)
    for t in range(n):
        for s in range(n):
            tn, sn = t >= half, s >= half
            bt, bs = (t - half * tn) // block, (s - half * sn) // block
            if not tn:
                m[t, s] = not sn and bs <= bt
            elif not sn:
                m[t, s] = bs < bt
            else:
                m[t, s] = bs == bt
    return m


def _dense(q, k, v, mask, scale):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _close(got, want, tol):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) / scale < tol


# arm: (2L positions, tile caps or None); the split arm's copies meet inside
# a 128-row tile (L = 500) and its rows end in tile padding
_ARMS = {"fused_s512": (512, None), "split_s1000": (1000, (128, 256))}
_BLOCKS = (1, 4, 48)


@pytest.mark.parametrize("block", _BLOCKS)
@pytest.mark.parametrize("arm", sorted(_ARMS))
def test_the_static_mask_is_the_pair_mask_the_dense_softmax_and_the_jnp_form(
        monkeypatch, arm, block):
    length, caps = _ARMS[arm]
    if caps:
        monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", caps[0])
        monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", caps[1])
    nk = -(-length // (caps[1] if caps else 2048))
    assert (nk <= 2) == arm.startswith("fused")
    key = jax.random.PRNGKey(block)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), s, F32) for i, s in
                  enumerate([(1, 4, length, 128), (1, 2, length, 128),
                             (1, 2, length, 128), (1, 4, length, 128)]))
    q, k = q * 0.3, k * 0.3
    scale = 128 ** -0.5
    mask = _explicit_mask(length // 2, block)
    pair = jnp.asarray(mask, jnp.int8)[None]

    def static(q, k, v):
        return flash_mod.flash_attention_block_diffusion(q, k, v, block, scale, True)

    def paired(q, k, v):
        return flash_mod.flash_attention(q, k, v, scale, False, 0, True, None, None,
                                         None, pair)

    def dense(q, k, v):
        return _dense(q, k, v, mask, scale)

    def jnp_form(q, k, v):
        return op_impl_nn.flash_attention_op(q, k, v, sm_scale=scale,
                                             block_diffusion=block)

    def grads(fn):
        return jax.grad(lambda *a: (fn(*a) * w).sum(), (0, 1, 2))(q, k, v)

    got, got_g = static(q, k, v), grads(static)
    _close(got, paired(q, k, v), 1e-6)
    _close(got_g, grads(paired), 1e-6)
    _close(got, dense(q, k, v), 1e-5)
    _close(got_g, grads(dense), 1e-5)
    assert "pallas_call" not in str(jax.make_jaxpr(jnp_form)(q, k, v))
    _close(got, jnp_form(q, k, v), 1e-5)
    _close(got_g, grads(jnp_form), 1e-5)


@pytest.mark.parametrize("block", _BLOCKS)
def test_the_jnp_forms_mask_is_the_definition(block):
    got = np.asarray(flash_mod.block_diffusion_mask(200, block))
    assert (got == _explicit_mask(100, block)).all()


@pytest.mark.parametrize("block", _BLOCKS)
@pytest.mark.parametrize("arm", sorted(_ARMS))
def test_the_live_tiles_counted_are_the_tiles_the_mask_leaves_anything_in(
        monkeypatch, arm, block):
    length, caps = _ARMS[arm]
    if caps:
        monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", caps[0])
        monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", caps[1])
    bq, bk = flash_mod._pick_blocks(length, length)
    nq, nk = -(-length // bq), -(-length // bk)
    padded = np.zeros((nq * bq, nk * bk), bool)
    padded[:length, :length] = _explicit_mask(length // 2, block)
    live = int(padded.reshape(nq, bq, nk, bk).any(axis=(1, 3)).sum())
    q = jax.ShapeDtypeStruct((2, 4, length, 128), BF16)
    kv = jax.ShapeDtypeStruct((2, 2, length, 128), BF16)
    names = ("bdiff_calls", "bdiff_tiles", "bdiff_tiles_live",
             "flash_tiles", "flash_tiles_live")
    before = mx.profiler.counters(device=False)
    jax.make_jaxpr(jax.grad(lambda q, k, v: flash_mod.flash_attention_block_diffusion(
        q, k, v, block, None, True).astype(F32).sum(), (0, 1, 2)))(q, kv, kv)
    after = mx.profiler.counters(device=False)
    grew = {n: after[n] - before[n] for n in names}
    # a forward and a backward call, each over the 2 x 4 query rows
    assert grew == {"bdiff_calls": 2, "bdiff_tiles": 2 * 8 * nq * nk,
                    "bdiff_tiles_live": 2 * 8 * live,
                    "flash_tiles": 2 * 8 * nq * nk, "flash_tiles_live": 2 * 8 * live}
    assert live < nq * nk or nq * nk == 1      # the split arm skips tiles


def test_the_live_tiles_at_the_cells_size_are_the_issues_count():
    """At 2 x 8,192 positions in 512 x 2,048 tiles and blocks of 4: the clean
    copy's causal tiles (40), the noisy rows' clean tiles (40) and the noisy
    diagonal (16) of 32 x 8."""
    i, j = np.arange(32)[:, None], np.arange(8)[None, :]
    live = flash_mod._bdiff_live(i * 512, i * 512 + 511, j * 2048, j * 2048 + 2047,
                                 8192, 4, xp=np)
    assert int(live.sum()) == 96


# the other cells' op calls: tests/test_flash_two_widths.py's pins, and the
# latent attention call of Kimi's and JoyAI's cells (q.k 192, v 128, 32 heads,
# one row: the two cells make the same call), taken on the parent and
# re-pinned when the kernel began to run wholly live tiles without building
# their static mask and partly live ones a chunk at a time, and again when
# its kernels began to walk a table of the live tiles (outputs and gradients
# the parent's bit for bit)
_MLA = ((1, 32, 8192, 192), (1, 32, 8192, 192), (1, 32, 8192, 128), True, None, False, False)
_PARENT = {**two_widths._PARENT,
           ("kimi", "forward"): "8782dcfdc703758d7d3258344775b4e278a4edcd6dff3a7fe10e2fdc64868d71",
           ("kimi", "gradient"): "6dbeea3cf7e85be9857c7cf957ae6fa5679e48d9c65110661bfdab67630df008",
           ("joyai", "forward"): "8782dcfdc703758d7d3258344775b4e278a4edcd6dff3a7fe10e2fdc64868d71",
           ("joyai", "gradient"): "6dbeea3cf7e85be9857c7cf957ae6fa5679e48d9c65110661bfdab67630df008"}


@pytest.mark.parametrize("call,what", sorted(_PARENT))
def test_every_other_cells_flash_call_is_the_parents(monkeypatch, call, what):
    monkeypatch.setitem(two_widths._OP_CALLS, "kimi", _MLA)
    monkeypatch.setitem(two_widths._OP_CALLS, "joyai", _MLA)
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    text = two_widths._op_jaxpr(call, what)
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT[call, what]


# ---- nn.GQAttention and the model at toy widths ---------------------------------------------

_TOY = dict(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6, rope_theta=1e6,
            num_experts=16, num_experts_per_tok=2, moe_intermediate_size=16,
            norm_topk_prob=True, vocab_size=64, block_length=4)


def _ref_mod():
    import os
    import sys
    chip = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "chip")
    if chip not in sys.path:
        sys.path.insert(0, chip)
    return importlib.import_module("reference.sdar")


@pytest.mark.parametrize("block", [1, 4], ids=["block_of_1", "block_diffusion"])
def test_gq_attention_is_the_references_attention(block):
    """The block's q / k norms a head, rotation and grouped heads against
    ``reference/sdar.py``'s attention."""
    ref = _ref_mod()
    att = nn.GQAttention(32, 4, 2, 16, block, rope_theta=1e6, prefix="a_")
    att.initialize(init=mx.initializer.Normal(0.2), ctx=mx.cpu(0))
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 48, 32)).astype("float32")
    pos = np.broadcast_to(np.arange(24, dtype=np.int32), (2, 24))
    pos = np.concatenate([pos, pos], 1)
    got = att(mx.nd.array(x), mx.nd.array(pos, dtype="int32"))._data
    w = {"p_" + k[len("a_"):]: v.data()._data for k, v in att.collect_params().items()}
    cfg = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               rms_norm_eps=1e-6, rope_theta=1e6, block_length=block)
    with jax.default_matmul_precision("highest"):
        want = ref._attention(cfg, lambda f: f, w, "p_", jnp.asarray(x), jnp.asarray(pos))
    _close(got, want, 1e-4)


def test_gq_attention_runs_the_mask_under_its_scope():
    att = nn.GQAttention(32, 4, 2, 16, 4, prefix="a_")
    att.initialize(ctx=mx.cpu(0))
    fn, params = functionalize(att, training=True, ctx=mx.cpu(0))
    x = jax.ShapeDtypeStruct((1, 32, 32), F32)
    pos = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    text = jax.jit(lambda p, x, pos: fn(p, jax.random.PRNGKey(0), x, pos)).lower(
        params, x, pos).as_text(debug_info=True)
    assert "mxtpu_bdiff" in text
    with pytest.raises(ValueError, match="divide"):
        nn.GQAttention(32, 4, 3, 16, 4)


def _toy_model(held=None, dtype="float32"):
    mx.random.seed(0)
    model = sdar({**_TOY, "experts_held": held}, prefix="sdar_")
    model.initialize(init=mx.initializer.Normal(0.1), ctx=mx.cpu(0))
    if dtype != "float32":
        model.cast(dtype)
    return model


def _toy_batch(rows=2, length=32, seed=0):
    models = _chip_models()
    cfg = {**_TOY, "mask_token_id": 63, "noise_level_min": 1e-3}
    return models.host_batch(cfg, {"batch": rows, "seq_len": length},
                             np.random.default_rng(seed))


def _chip_models():
    _ref_mod()
    return importlib.import_module("models.sdar")


def test_the_model_is_the_references_loss_and_gradients_in_float32():
    """The whole model, float32, through the framework's path: the loss and
    every leaf's gradient against ``reference/sdar.py``'s at ``highest``."""
    ref = _ref_mod()
    model = _toy_model()
    fn, params = functionalize(model, training=True, ctx=mx.cpu(0))
    batch = [jnp.asarray(a) for a in _toy_batch()]
    trained = {k: v for k, v in params.items() if "running_" not in k}
    rest = {k: v for k, v in params.items() if "running_" in k}
    cfg = {**_TOY, "experts_held": [0, 16], "layers_held": [0, 2]}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: fn(
            {**p, **rest}, jax.random.PRNGKey(0), *batch).sum())(trained)
        want, want_g = jax.value_and_grad(lambda p: ref.loss_sum(
            cfg, {**p, **rest}, batch, lambda f: f))(trained)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    for k in want_g:
        scale = float(jnp.abs(want_g[k]).max()) + 1e-12
        assert float(jnp.abs(grads[k] - want_g[k]).max()) / scale < 1e-4, k


def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    """The test of a cut by experts: 8 chips each hold 2 of the 16
    experts of a program layer; what each returns less what every chip
    computes alike (x + attention, counted once) adds up to the uncut
    reference layer."""
    ref = _ref_mod()
    whole = _toy_model()
    p = {k: v.data() for k, v in whole.collect_params().items()}
    rng = np.random.default_rng(3)
    x = rng.normal(size=(1, 32, 32)).astype("float32")
    pos = np.tile(np.arange(16, dtype=np.int32), (1, 2))
    total = None
    for s in range(8):
        share = _toy_model(held=[2 * s, 2 * s + 2])
        for name, param in share.collect_params().items():
            if "experts_" in name:
                param.set_data(p[name][2 * s:2 * s + 2])
            elif "running_" not in name:
                param.set_data(p[name])
        part = share.layers[0](mx.nd.array(x), mx.nd.array(pos, dtype="int32"))._data
        total = part if total is None else total + part
    w = {k: v._data for k, v in p.items()}
    cfg = {**_TOY, "experts_held": [0, 16]}
    u = ref._rms(jnp.asarray(x), w["sdar_layer0_attn_norm_gamma"], 1e-6)
    with jax.default_matmul_precision("highest"):
        shared = jnp.asarray(x) + ref._attention(cfg, lambda f: f, w, "sdar_layer0_attn_",
                                                 u, jnp.asarray(pos))
        want = ref._layer(cfg, lambda f: f, w, 0, jnp.asarray(x), jnp.asarray(pos))
    _close(total - 7 * shared, want, 1e-4)


def test_the_noise_is_the_seeds_and_masks_a_share_of_mean_t():
    """The noise is data from the pool's generator: one seed, one batch, which
    the program and the reference both read; over a pool the masked share is
    E[t] = (1 + 0.001) / 2, a masked token weighs 1/t of its block (at least
    1), the clean ids never draw the mask token, the targets are the clean
    ids."""
    first, again = _toy_batch(8, 8192, seed=11), _toy_batch(8, 8192, seed=11)
    for a, b in zip(first, again):
        assert a.dtype == b.dtype and (a == b).all()
    clean, noisy, positions, targets, weights = first
    masked = noisy == 63
    assert abs(masked.mean() - 0.5005) < 0.01
    assert (clean != 63).all() and (targets == clean).all()
    assert (noisy[~masked] == clean[~masked]).all()
    assert (weights[~masked] == 0).all() and (weights[masked] >= 1).all()
    # one level a block: the weights of a block's masked tokens are equal
    m = masked.reshape(8, -1, 4)
    w = weights.reshape(8, -1, 4)
    assert ((w.max(-1) == np.where(m, w, np.inf).min(-1)) | ~m.any(-1)).all()
    assert (positions == np.arange(8192)).all()


def test_the_model_counts_its_block_diffusion_calls_on_the_build_span(monkeypatch):
    from mxnet_tpu.profiler import _EVENTS

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    model = _toy_model(dtype="bfloat16")
    model.hybridize()
    batch = [mx.nd.array(a, dtype=str(a.dtype)) for a in _toy_batch(1, 256)]
    names = ("bdiff_calls", "bdiff_tiles", "bdiff_tiles_live")
    before = mx.profiler.counters(device=False)
    mx.profiler.set_state("run")
    try:
        with autograd.record():
            loss = model(*batch)
        loss.backward()
    finally:
        mx.profiler.set_state("stop")
    after = mx.profiler.counters(device=False)
    span = [e["args"] for e in _EVENTS if e["name"] == "mxtpu/cachedop/build"][-1]
    # two layers, one forward call each over 4 query heads, 512 positions in
    # one (512, 512) tile
    assert (span["bdiff_calls"], span["bdiff_tiles"], span["bdiff_tiles_live"]) == (2, 8, 8)
    assert after["bdiff_calls"] - before["bdiff_calls"] >= 4      # + the backward's
