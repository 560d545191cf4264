"""What Keye-VL-2.0's language model forced into the framework, on the CPU at
small sizes: rotary positions with sections against the plain reference's
rotation; the flash kernel with a pair mask that is data against a dense
masked softmax, forward and both backward arms, grouped heads on and a dead
tile among the tiles, and unchanged with the operand absent; the indexer's
scores and selection against ``lax.top_k``; the indexer's loss and where the
two losses' gradients go; the softmax-routed expert layer against a loop
over the experts, and its eight shares against the uncut layer; a remat'd
layer with a scalar second output under ``remat_rows``."""
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
from mxnet_tpu.gluon.model_zoo import keye_vl2
from mxnet_tpu.ops.pallas import dsa

flash_mod = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
F32 = jnp.float32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def reference():
    chip = os.path.join(ROOT, "benchmark", "chip")
    sys.path.insert(0, chip)
    yield importlib.import_module("reference.keye_vl2")
    sys.path.remove(chip)


def _normal(seed, *shapes):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), s, F32)
            for i, s in enumerate(shapes)]


def _close(got, want, tol):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) / scale < tol


# ---- rotary positions ----------------------------------------------------------------------

@pytest.mark.parametrize("shape,sections", [((2, 3, 16, 32), (4, 6, 6)),
                                            ((2, 16, 16), (2, 3, 3))])
def test_rope_with_sections_is_the_references_rotation(reference, shape, sections):
    (x,) = _normal(0, shape)
    rng = np.random.default_rng(1)
    seq = shape[-2]
    positions = jnp.asarray(rng.integers(0, 500, (2, 3, seq)), jnp.int32)  # unequal streams
    got = mx.nd.rope(mx.nd.array(x), mx.nd.array(positions, dtype="int32"),
                     theta=1e7, sections=sections)._data
    heads_last = jnp.moveaxis(x, 1, 2) if x.ndim == 4 else x[:, :, None, :]
    want = reference._rotate(heads_last, positions, 1e7, list(sections))
    want = jnp.moveaxis(want, 2, 1) if x.ndim == 4 else want[:, :, 0]
    _close(got, want, 1e-5)
    # a rotation: norms of the pairs are kept
    half = shape[-1] // 2
    _close(got[..., :half] ** 2 + got[..., half:] ** 2,
           x[..., :half] ** 2 + x[..., half:] ** 2, 1e-5)


def test_rope_with_equal_streams_is_plain_rotary():
    (x,) = _normal(2, (2, 3, 16, 32))
    t = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    streams = jnp.broadcast_to(t[:, None, :], (2, 3, 16))
    plain = mx.nd.rope(mx.nd.array(x), mx.nd.array(t, dtype="int32"), theta=1e4)
    sectioned = mx.nd.rope(mx.nd.array(x), mx.nd.array(streams, dtype="int32"),
                           theta=1e4, sections=(4, 6, 6))
    assert bool(jnp.array_equal(plain._data, sectioned._data))
    # the textbook form: the pair (i, i + d/2) turned by t * theta^(-2i/d)
    angle = np.arange(16)[:, None] * 1e4 ** (-np.arange(16) / 16)
    a, b = np.asarray(x[..., :16]), np.asarray(x[..., 16:])
    want = np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                           b * np.cos(angle) + a * np.sin(angle)], -1)
    _close(plain._data, jnp.asarray(want), 1e-5)
    with pytest.raises(ValueError, match="sections"):
        mx.nd.rope(mx.nd.array(x), mx.nd.array(streams, dtype="int32"),
                   sections=(4, 4, 4))


# ---- the flash kernel with a pair mask that is data --------------------------------------------

def _dense_masked(q, k, v, mask, scale):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    seen = mask[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    p = jnp.where(seen.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _random_mask(seed, batch, length, dead_tile):
    rng = np.random.default_rng(seed)
    m = rng.random((batch, length, length)) < 0.3
    m &= np.tril(np.ones((length, length), bool))
    m[:, np.arange(length), np.arange(length)] = True
    (q0, q1), (k0, k1) = dead_tile
    m[:, q0:q1, k0:k1] = False              # a causal tile the mask leaves empty
    return jnp.asarray(m)


@pytest.mark.parametrize("length,arm,heads,kv_heads", [
    (256, "fused", 4, 2), (512, "split", 4, 2), (512, "split", 2, 2)])
def test_flash_with_a_pair_mask_matches_a_dense_masked_softmax(
        monkeypatch, length, arm, heads, kv_heads):
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", 64)
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", 128)
    assert (length // 128 <= 2) == (arm == "fused")
    q, = _normal(3, (2, heads, length, 32))
    k, v = _normal(4, *[(2, kv_heads, length, 32)] * 2)
    mask = _random_mask(5, 2, length, ((128, 192), (0, 128)))
    weight = jnp.cos(jnp.arange(32.0))

    def program(q, k, v):
        return (flash_mod.flash_attention(q, k, v, None, True, 0, True, None, None,
                                          None, mask.astype(jnp.int8)) * weight).sum()

    def dense(q, k, v):
        return (_dense_masked(q, k, v, mask, 32 ** -0.5) * weight).sum()

    out = flash_mod.flash_attention(q, k, v, None, True, 0, True, None, None, None,
                                    mask.astype(jnp.int8))
    _close(out, _dense_masked(q, k, v, mask, 32 ** -0.5), 1e-5)
    _close(jax.grad(program, (0, 1, 2))(q, k, v), jax.grad(dense, (0, 1, 2))(q, k, v),
           1e-5)


def test_the_pair_masks_tile_summary_marks_the_dead_tile():
    mask = _random_mask(6, 2, 256, ((128, 192), (0, 128))).astype(jnp.int8)
    padded, live = flash_mod._prep_pair_mask(mask, 256, 256, 64, 128)
    assert padded.shape == (2, 256, 256) and live.shape == (2, 4, 2)
    assert live.dtype == jnp.int32
    assert np.asarray(live)[:, 2, 0].tolist() == [0, 0]        # the dead tile
    causal = np.asarray(live).astype(bool)
    causal[:, 2, 0] = True
    assert causal[:, :, 0].all() and causal[:, 2:, 1].all()
    assert not causal[:, :2, 1].any()                          # above the diagonal


def test_flash_refuses_a_pair_mask_of_another_shape_or_with_a_window():
    q, = _normal(7, (1, 2, 64, 32))
    mask = jnp.ones((1, 64, 64), jnp.int8)
    with pytest.raises(ValueError, match="pair_mask"):
        flash_mod.flash_attention(q, q, q, None, True, 0, True, None, None, None,
                                  mask[:, :32])
    with pytest.raises(ValueError, match="pair_mask"):
        flash_mod.flash_attention(q, q, q, None, True, 0, True, None, None, 16, mask)
    with pytest.raises(ValueError, match="pair_mask"):
        mx.nd.flash_attention(mx.nd.array(q), mx.nd.array(q), mx.nd.array(q),
                              mx.nd.array(np.ones((1,)), dtype="int32"),
                              causal=True, pair_mask=mx.nd.array(mask, dtype="int8"))


_CALLS = {   # name: (q heads, kv heads, head width, causal, window): the other cells' calls
    "bert": (4, 4, 64, False, None),
    "kimi_mla": (4, 4, 256, True, None),
    "phi_window": (4, 2, 128, True, 64),
    "phi_full": (4, 2, 128, True, None),
}


@pytest.mark.parametrize("call", sorted(_CALLS))
@pytest.mark.parametrize("length", [128, 512], ids=["fused", "split"])
def test_flash_without_the_pair_mask_is_todays_call(monkeypatch, call, length):
    """The operand absent: the same jaxpr as a call that does not name it,
    forward and backward (both arms), for the calls the other cells make."""
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", 64)
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", 128)
    heads, kv_heads, width, causal, window = _CALLS[call]
    q, = _normal(8, (1, heads, length, width))
    k, v = _normal(9, *[(1, kv_heads, length, width)] * 2)

    def grads(*extra):
        return jax.grad(lambda q, k, v: flash_mod.flash_attention(
            q, k, v, None, causal, 0, True, None, None, window, *extra).sum(),
            argnums=(0, 1, 2))

    today, named = grads(), grads(None)
    text = str(jax.make_jaxpr(today)(q, k, v))
    assert text == str(jax.make_jaxpr(named)(q, k, v))
    assert "i8[" not in text                     # no mask operand, no summary
    for a, b in zip(today(q, k, v), named(q, k, v)):
        assert bool(jnp.array_equal(a, b))


# ---- the indexer: scores, selection, loss ----------------------------------------------------

def _index_inputs(seed, batch=2, heads=3, length=64, width=8):
    q, = _normal(seed, (batch, heads, length, width))
    k, = _normal(seed + 1, (batch, length, width))
    w, = _normal(seed + 2, (batch, length, heads))
    return q, k, w


def _dense_scores(q, k, w):
    heads, width = q.shape[1], q.shape[-1]
    prod = jnp.einsum("bhqd,bkd->bhqk", q, k)
    scores = jnp.einsum("bqh,bhqk->bqk", w, jax.nn.relu(prod)) * (heads * width) ** -0.5
    return jnp.where(jnp.tril(jnp.ones(scores.shape[-2:], bool)), scores, -jnp.inf)


def _top_k_mask(scores, top_k):
    """``lax.top_k`` over each query's causal keys (the keys above the
    diagonal at -inf, behind every causal key of the same value by their
    index), as an int8 mask."""
    length = scores.shape[-1]
    causal = np.tril(np.ones((length, length), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(top_k, length))
    out = np.zeros(scores.shape, np.int8)
    np.put_along_axis(out, np.asarray(idx), 1, axis=-1)
    return out * causal


@pytest.mark.parametrize("block_q", [16, 64], ids=["blocks", "one_block"])
def test_index_scores_are_the_heads_written_out(block_q):
    q, k, w = _index_inputs(10)
    got, want = dsa.index_scores(q, k, w, block_q=block_q), _dense_scores(q, k, w)
    finite = np.isfinite(np.asarray(want))
    assert (np.asarray(got)[~finite] == -np.inf).all()
    _close(jnp.where(finite, got, 0.0), jnp.where(finite, want, 0.0), 1e-5)
    loss = lambda f: lambda *a: jnp.sum(jnp.where(finite, f(*a), 0.0) ** 2)  # noqa: E731
    _close(jax.grad(loss(lambda *a: dsa.index_scores(*a, block_q=block_q)), (0, 1, 2))(q, k, w),
           jax.grad(loss(_dense_scores), (0, 1, 2))(q, k, w), 1e-5)


def test_the_index_score_kernel_is_its_twin(monkeypatch):
    """``mxtpu_dsa_index_fwd`` and its backward pair ``mxtpu_dsa_index_bwd_dq``
    / ``_dk`` in interpret mode, several tiles and heads: the twin's values and
    the twin's gradients."""
    monkeypatch.setattr(dsa, "_TILE_Q", 64)
    monkeypatch.setattr(dsa, "_TILE_K", 128)
    q, k, w = _index_inputs(30, length=256)
    got = dsa.index_scores(q, k, w, use_kernel=True, interpret=True)
    want = _dense_scores(q, k, w)
    finite = np.isfinite(np.asarray(want))
    assert (np.asarray(got)[~finite] == -np.inf).all()
    _close(jnp.where(finite, got, 0.0), jnp.where(finite, want, 0.0), 1e-5)
    assert "mxtpu_dsa_index_fwd" in str(jax.make_jaxpr(
        lambda *a: dsa.index_scores(*a, use_kernel=True, interpret=True))(q, k, w))
    loss = lambda kern: lambda *a: jnp.sum(jnp.where(finite, dsa.index_scores(  # noqa: E731
        *a, use_kernel=kern, interpret=True), 0.0) ** 2)
    _close(jax.grad(loss(True), (0, 1, 2))(q, k, w),
           jax.grad(loss(False), (0, 1, 2))(q, k, w), 1e-5)
    text = str(jax.make_jaxpr(jax.grad(loss(True), (0, 1, 2)))(q, k, w))
    assert "mxtpu_dsa_index_bwd_dq" in text and "mxtpu_dsa_index_bwd_dk" in text
    # a length its tiles do not divide: the twin
    q, k, w = _index_inputs(31, length=96)
    assert "pallas" not in str(jax.make_jaxpr(
        lambda *a: dsa.index_scores(*a, use_kernel=True, interpret=True))(q, k, w))


def _planted_scores(length=512):
    """(2, length, length) float32 with what a selection can trip over."""
    rng = np.random.default_rng(13)
    scores = rng.standard_normal((2, length, length)).astype(np.float32)
    above = np.triu(np.ones((length, length), bool), 1)
    scores[0][above] = -np.inf                                 # as the indexer leaves it
    scores[1][above] = 1e9                                     # nothing above the diagonal is read
    scores[0, 300, 100:290] = 1.5       # 190 equals at the top, over three 128-column chunks
    scores[0, 301, 120:140] = np.sort(scores[0, 301, :302])[-50]   # equals at 64's threshold, over two
    scores[1, 400, :401] = 0.0                                 # a row of equals
    scores[1, 400, 7] = -0.0                                   # below +0 in the total order
    scores[0, 511, :] = np.where(np.arange(length) % 2, 0.25, -0.25)
    scores[1, 260, :200] = -np.inf                             # -inf among the causal keys
    return scores


def _select(form, scores, top_k):
    if form == "kernel":
        return dsa.topk_mask(scores, top_k, use_kernel=True, interpret=True)
    return dsa.topk_mask(scores, top_k, block_q=64)


@pytest.fixture
def small_selection_tiles(monkeypatch):
    """Eight row blocks and four column chunks at 512 tokens."""
    monkeypatch.setattr(dsa, "_TOPK_ROWS", 64)
    monkeypatch.setattr(dsa, "_TOPK_CHUNK", 128)


@pytest.mark.parametrize("top_k", [16, 64, 200, 600])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_selection_is_lax_top_ks_with_ties_planted(small_selection_tiles, form, top_k):
    """Both forms against ``lax.top_k``, bit for bit, two rows of 512 tokens:
    at 64 one block of the kernel's lies under ``top_k`` (the causal mask,
    no search), at 200 three do and one straddles it, at 600 all do; equals
    at a threshold across column chunks, more of them than there is room; a
    row of equals; -0 against +0; -inf among and above the causal keys."""
    scores = jnp.asarray(_planted_scores())
    mask = np.asarray(_select(form, scores, top_k))
    assert mask.dtype == np.int8 and mask.shape == scores.shape
    want = np.minimum(top_k, np.arange(512) + 1)
    assert (mask.sum(-1) == want).all()                        # exactly, never more
    assert (mask == _top_k_mask(scores, top_k)).all()
    assert not np.triu(mask, 1).any()


def test_the_selection_kernel_is_taken_where_its_tiles_divide_the_length():
    """``mxtpu_dsa_topk`` at 512 tokens; at 96, which no tile divides, and
    without ``use_kernel`` the XLA form: no Pallas call in the jaxpr."""
    def text(length, **how):
        scores, = _normal(5, (1, length, length))
        return str(jax.make_jaxpr(lambda s: dsa.topk_mask(s, 32, **how))(scores))

    assert "mxtpu_dsa_topk" in text(512, use_kernel=True, interpret=True)
    assert "pallas" not in text(96, use_kernel=True, interpret=True)
    assert "pallas" not in text(512)


def test_the_selection_kernel_tallies_the_chunks_it_visits(small_selection_tiles):
    """``dsa_topk_chunks`` / ``dsa_topk_chunks_live`` from the shapes, when the
    kernel is traced: 8 row blocks x 4 column chunks x 33 passes a row of 512
    tokens, of which the first block (under ``top_k`` 64) visits one chunk
    once and the others their 1, 2, 2, 3, 3, 4, 4 causal chunks 33 times; the
    XLA form adds nothing; a block's build span carries the trace's two."""
    def tally(fn):
        before = mx.profiler.counters(device=False)
        fn()
        after = mx.profiler.counters(device=False)
        return tuple(after[n] - before[n] for n in ("dsa_topk_chunks", "dsa_topk_chunks_live"))

    scores, = _normal(6, (2, 512, 512))
    counts = (2 * 8 * 4 * 33, 2 * (1 + 33 * 19))
    assert tally(lambda: dsa.topk_mask(scores, 64, use_kernel=True, interpret=True)) == counts
    assert tally(lambda: dsa.topk_mask(scores, 64)) == (0, 0)

    class Select(gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            return F.dsa_topk_mask(x, top_k=64)[0]

    def build(block):
        mx.profiler.set_state("run")
        try:
            block(mx.nd.array(np.asarray(scores))).wait_to_read()
        finally:
            mx.profiler.set_state("stop")
        from mxnet_tpu.profiler import _EVENTS
        return [e["args"] for e in _EVENTS if e["name"] == "mxtpu/cachedop/build"][-1]

    block = Select()
    block.hybridize()
    span = build(block)                 # on the CPU: the XLA form
    assert (span["dsa_topk_chunks"], span["dsa_topk_chunks_live"]) == (0, 0)
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
        block = Select()
        block.hybridize()
        span = build(block)
    assert (span["dsa_topk_chunks"], span["dsa_topk_chunks_live"]) == counts


def _attention_probs(seed, mask):
    q, = _normal(seed, (2, 4, 64, 16))
    k, = _normal(seed + 1, (2, 2, 64, 16))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, 1)) * 16 ** -0.5
    s = jnp.where(mask[:, None] != 0, s, -jnp.inf)
    return q, k, jax.nn.logsumexp(s, -1), jnp.mean(jax.nn.softmax(s, -1), 1)


def test_head_mean_probs_are_the_softmaxes_mean_and_sum_to_one():
    mask = dsa.topk_mask(_dense_scores(*_index_inputs(16)), 16)
    q, k, lse, want = _attention_probs(19, mask)
    got = dsa.head_mean_probs(q, k, lse, mask, sm_scale=16 ** -0.5, block_q=16)
    _close(got, want, 1e-5)
    _close(got.sum(-1), jnp.ones((2, 64)), 1e-5)
    assert not np.asarray(got)[np.asarray(mask) == 0].any()


def test_head_mean_probs_over_row_blocks_grouped_heads_and_an_empty_region():
    """Four blocks of query rows, two query heads a key head, a stretch of
    keys the mask leaves empty for a block of queries: the dense softmaxes'
    mean, whatever the block."""
    mask = _random_mask(33, 2, 256, ((128, 192), (0, 128))).astype(jnp.int8)
    q, = _normal(34, (2, 4, 256, 16))
    k, = _normal(35, (2, 2, 256, 16))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, 1)) * 16 ** -0.5
    s = jnp.where(mask[:, None] != 0, s, -jnp.inf)
    lse, want = jax.nn.logsumexp(s, -1), jnp.mean(jax.nn.softmax(s, -1), 1)
    got = dsa.head_mean_probs(q, k, lse, mask, sm_scale=16 ** -0.5, block_q=64)
    _close(got, want, 1e-5)
    _close(got, dsa.head_mean_probs(q, k, lse, mask, sm_scale=16 ** -0.5), 1e-6)
    _close(got.sum(-1), jnp.ones((2, 256)), 1e-5)
    assert not np.asarray(got)[:, 128:192, :128].any()


def test_the_index_loss_and_its_gradient_softmax_minus_p_bar():
    q, k, w = _index_inputs(21)
    scores = dsa.index_scores(q, k, w)
    mask = dsa.topk_mask(scores, 16)
    _, _, _, p_bar = _attention_probs(24, mask)
    kept = np.asarray(mask) != 0
    logp = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), -1)
    want = jnp.sum(jnp.where(kept & (p_bar > 0),
                             p_bar * (jnp.log(jnp.where(p_bar > 0, p_bar, 1.0))
                                      - jnp.where(kept, logp, 0.0)), 0.0))
    assert float(dsa.index_loss(scores, mask, p_bar)) == pytest.approx(float(want), rel=1e-5)
    grad = jax.grad(lambda s: dsa.index_loss(s, mask, p_bar))(scores)
    soft = jax.nn.softmax(jnp.where(kept, scores, -jnp.inf), -1)
    _close(grad, jnp.where(kept, soft - p_bar, 0.0), 1e-5)
    assert not np.asarray(grad)[~kept].any()
    # a Kullback-Leibler sum: not negative, and zero where the indexer already agrees
    assert float(dsa.index_loss(scores, mask, p_bar)) > 0
    agree = jnp.where(kept, jnp.log(jnp.where(kept, p_bar, 1.0)), -jnp.inf)
    assert abs(float(dsa.index_loss(agree, mask, p_bar))) < 1e-3


# ---- the attention block: where the two losses' gradients go -----------------------------------

_TOY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6, rope_theta=1e7,
            rope_scaling={"mrope_section": [2, 2, 4]},
            sa_config=dict(indexer_num_heads=2, indexer_head_dim=8, topk=16),
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            norm_topk_prob=True, vocab_size=256, loss_chunks=2)


def _positions(batch, length):
    return jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32), (batch, 3, length))


@pytest.fixture(scope="module")
def toy_model():
    model = keye_vl2(_TOY, prefix="keye_")
    model.initialize(init=mx.initializer.Normal(0.05), ctx=mx.cpu(0))
    fn, params = functionalize(model, training=True, ctx=mx.cpu(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 256, (2, 64)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 256, (2, 64)), jnp.int32)
    positions = jnp.moveaxis(_positions(2, 64), 1, 0)           # (3, B, S)

    def losses(p):
        token, index = fn(p, jax.random.PRNGKey(0), ids, positions, labels)
        return token.sum(), index.sum()

    return params, losses


@pytest.mark.parametrize("which", ["token_loss", "index_loss"])
def test_the_two_losses_share_no_gradient_path(toy_model, which):
    params, losses = toy_model
    trained = {k: v for k, v in params.items() if "running_" not in k}
    rest = {k: v for k, v in params.items() if "running_" in k}
    pick = 0 if which == "token_loss" else 1
    grads = jax.grad(lambda p: losses({**p, **rest})[pick])(trained)
    indexer = {k for k in grads if "_index_" in k}
    assert len(indexer) == 2 * 5
    for name, g in grads.items():
        moved = float(jnp.abs(g).max()) > 0
        if which == "token_loss":       # nothing for the indexer, something for the rest
            assert moved == (name not in indexer), name
        else:                            # the indexer's loss: its own parameters only
            assert moved == (name in indexer), name


def test_the_attention_block_counts_its_pairs_on_the_device():
    block = nn.SparseGQAttention(32, 4, 2, 16, 2, 8, 16, mrope_section=(2, 2, 4),
                                 prefix="a_")
    block.initialize(ctx=mx.cpu(0))
    block.hybridize()
    x = mx.nd.array(np.random.default_rng(1).normal(size=(2, 64, 32)).astype("float32"))
    pos = mx.nd.array(_positions(2, 64), dtype="int32")
    before = mx.profiler.counters()
    for _ in range(2):
        out, loss = block(x, pos)
    after = mx.profiler.counters()
    assert out.shape == (2, 64, 32) and loss.shape == ()
    kept = 2 * 2 * (16 * 17 // 2 + 48 * 16)
    assert after["dsa_pairs_selected"] - before.get("dsa_pairs_selected", 0) == kept
    assert after["dsa_pairs_causal"] - before.get("dsa_pairs_causal", 0) \
        == 2 * 2 * (64 * 65 // 2)
    assert after["dsa_layers"] > before["dsa_layers"]
    with pytest.raises(ValueError, match="mrope_section"):
        nn.SparseGQAttention(32, 4, 2, 16, 2, 8, 16, mrope_section=(1, 3, 4))


# ---- the expert layer with a softmax score ------------------------------------------------------

def _experts(held, seed=0):
    layer = nn.HeldExperts(32, 16, 8, 2, experts_held=held, num_shared_experts=0,
                           score="softmax", prefix="moe_")
    layer.initialize(init=mx.initializer.Normal(0.3), ctx=mx.cpu(0))
    return layer


def _loop_over_experts(x, router, gate_up, down, lo, top_k=2):
    scores = jax.nn.softmax(x @ router.T, axis=-1)
    picked, chosen = jax.lax.top_k(scores, top_k)
    picked = picked / picked.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(gate_up.shape[0]):
        weight = jnp.where(chosen == e + lo, picked, 0.0).sum(-1, keepdims=True)
        h = x @ gate_up[e].T
        y = y + weight * ((jax.nn.silu(h[..., :16]) * h[..., 16:]) @ down[e].T)
    return y


@pytest.mark.parametrize("held", [None, (2, 6)], ids=["all", "a_share"])
def test_held_experts_with_a_softmax_score_and_no_shared_expert(held):
    layer = _experts(held)
    assert "moe_router_running_bias" not in layer.collect_params()
    assert layer.shared is None
    x = np.random.default_rng(2).normal(size=(2, 24, 32)).astype("float32")
    got = layer(mx.nd.array(x))._data
    p = {k: v.data()._data for k, v in layer.collect_params().items()}
    want = _loop_over_experts(jnp.asarray(x), p["moe_router_weight"],
                              p["moe_experts_gate_up_weight"],
                              p["moe_experts_down_weight"], 0 if held is None else held[0])
    _close(got, want, 1e-5)
    with pytest.raises(ValueError, match="score"):
        nn.HeldExperts(32, 16, 8, 2, score="tanh")


def test_the_eight_shares_partial_sums_add_up_to_the_uncut_layer():
    """The guide's test of a cut by experts: each of 8 chips holds one expert of
    8 and returns its partial sum; the sums add up to the whole layer's output."""
    whole = _experts(None)
    p = {k: v.data() for k, v in whole.collect_params().items()}
    x = mx.nd.array(np.random.default_rng(3).normal(size=(2, 24, 32)).astype("float32"))
    total = None
    for e in range(8):
        share = _experts((e, e + 1))
        share.router_weight.set_data(p["moe_router_weight"])
        share.experts_gate_up_weight.set_data(p["moe_experts_gate_up_weight"][e:e + 1])
        share.experts_down_weight.set_data(p["moe_experts_down_weight"][e:e + 1])
        part = share(x)._data
        total = part if total is None else total + part
    _close(total, whole(x)._data, 1e-5)


# ---- a remat'd layer with a scalar second output ------------------------------------------------

class _WithTerm(gluon.HybridBlock):
    """(x, scale) -> (a Dense of x, a scalar: the sum of its squares times the
    rows' own scale)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.proj = nn.Dense(8, flatten=False, in_units=8)

    def hybrid_forward(self, F, x, scale):
        y = self.proj(x)
        return y, F.sum(F.square(y) * scale).reshape(())


class _Net(gluon.HybridBlock):
    def __init__(self, rows, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.layer = _WithTerm()
        if rows is not None:
            self.layer.hybridize(active=False, remat=True, remat_rows=rows)

    def hybrid_forward(self, F, x, scale):
        y, term = self.layer(x, scale)
        return F.sum(y) + term


@pytest.mark.parametrize("rows", [1, 2, 4], ids=["row_by_row", "pairs", "whole_batch"])
def test_a_remat_layers_scalar_output_is_summed_over_the_rows(rows):
    rng = np.random.default_rng(4)
    x = mx.nd.array(rng.normal(size=(4, 6, 8)).astype("float32"))
    scale = mx.nd.array(rng.normal(size=(4, 1, 1)).astype("float32"))
    results = []
    for r in (None, rows):
        net = _Net(r, prefix="n_")
        net.initialize(init=mx.initializer.Constant(0.1), ctx=mx.cpu(0))
        net.hybridize()
        with autograd.record():
            out = net(x, scale)
        out.backward()
        results.append((out._data, net.layer.proj.weight.grad()._data))
    _close(results[1], results[0], 1e-5)
