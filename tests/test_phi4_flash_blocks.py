"""What Phi-4-mini-flash (SambaY) forced into the framework, on the CPU at
small sizes: the selective scan (kernel in interpret mode and the
``jax.numpy`` twin) against the token recurrence; the flash kernel with a
window and with grouped key/value heads against a dense masked softmax,
forward and both backward arms, and unchanged with both off; the three
blocks; the tied head's gradient; a remat'd layer with two array inputs and
two outputs under ``remat_rows``."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import phi4_flash
from mxnet_tpu.gluon.model_zoo.phi4_flash import layer_kind
from mxnet_tpu.ops.pallas import ssm

flash_mod = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
F32 = jnp.float32


def _normal(seed, *shapes):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), s, F32)
            for i, s in enumerate(shapes)]


def _close(got, want, tol):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) / scale < tol


# ---- the selective scan ------------------------------------------------------------------

def _token_recurrence(u, delta, a_log, b, c, skip):
    a = -jnp.exp(a_log)
    dt = jax.nn.softplus(delta)

    def token(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t[:, :, None] * a) * h
             + dt_t[:, :, None] * b_t[:, None, :] * u_t[:, :, None])
        return h, jnp.einsum("bcn,bn->bc", h, c_t) + skip * u_t

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, b, c))
    h0 = jnp.zeros((u.shape[0], u.shape[2], a_log.shape[1]), F32)
    return jnp.moveaxis(jax.lax.scan(token, h0, xs)[1], 0, 1)


@pytest.mark.parametrize("case,rows,length,channels,states,kernel", [
    ("twin", 2, 64, 128, 4, False),
    ("kernel_one_block", 2, 40, 256, 4, True),          # a padded time block
    ("kernel_blocks_16_states", 1, 300, 1024, 16, True),  # 3 time x 2 channel blocks
])
def test_selective_scan_matches_the_token_recurrence(case, rows, length, channels,
                                                     states, kernel):
    u, delta, a_log, b, c, skip, w = _normal(
        1, (rows, length, channels), (rows, length, channels), (channels, states),
        (rows, length, states), (rows, length, states), (channels,),
        (rows, length, channels))
    args = (u, delta - 2.0, a_log, b, c, skip)

    def through(fn):
        return jax.value_and_grad(lambda *a: (fn(*a) * w).sum(),
                                  argnums=tuple(range(6)))(*args)

    got = through(lambda *a: ssm.selective_scan(*a, use_kernel=kernel,
                                                interpret=True))
    _close(got, through(_token_recurrence), 1e-4)


def test_selective_scan_op_runs_the_kernel_in_interpret_mode(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    u, delta, a_log, b, c, skip = _normal(
        2, (1, 16, 128), (1, 16, 128), (128, 4), (1, 16, 4), (1, 16, 4), (128,))
    y = mx.nd.selective_scan(*(mx.nd.array(np.asarray(x)) for x in
                               (u, delta, a_log, b, c, skip)))
    _close(y._data, _token_recurrence(u, delta, a_log, b, c, skip), 1e-5)


# ---- the flash kernel: a window, grouped heads -------------------------------------------

def _dense(q, k, v, window):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    row, col = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = col <= row
    if window is not None:
        seen = seen & (row - col < window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(seen, scores, -1e30), -1), v)


@pytest.mark.parametrize("case,length,heads,kv_heads,window,tiles", [
    ("grouped_fused", 256, 4, 2, None, (64, 128)),       # two kv tiles: one pass
    ("grouped_split", 512, 4, 2, None, (64, 128)),       # four: dq + dkv
    ("window_grouped_split", 512, 4, 2, 100, (64, 128)),  # narrowed walks
    ("window_split", 512, 4, 4, 100, (64, 128)),
    ("window_fused", 200, 4, 1, 64, (64, 128)),          # padded, one pass
    ("window_wider_than_a_tile", 384, 2, 2, 130, (128, 128)),
])
def test_flash_window_and_grouped_heads_match_a_dense_masked_softmax(
        monkeypatch, case, length, heads, kv_heads, window, tiles):
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", tiles[0])
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", tiles[1])
    q, k, v, w = _normal(0, (2, heads, length, 32), (2, kv_heads, length, 32),
                         (2, kv_heads, length, 32), (2, heads, length, 32))

    def through(fn):
        return jax.value_and_grad(lambda q, k, v: (fn(q, k, v) * w).sum(),
                                  argnums=(0, 1, 2))(q, k, v)

    got = through(lambda q, k, v: flash_mod.flash_attention(
        q, k, v, None, True, 0, True, None, None, window))
    _close(got, through(lambda q, k, v: _dense(q, k, v, window)), 2e-4)


@pytest.mark.parametrize("length,tiles", [(128, (64, 128)), (512, (64, 128))])
def test_flash_with_window_and_groups_off_is_todays_call(monkeypatch, length, tiles):
    """The new static arguments at their defaults: the same jaxpr as a call
    that does not name them, forward and backward (both arms)."""
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", tiles[0])
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", tiles[1])
    q, k, v = _normal(3, *[(1, 2, length, 32)] * 3)

    def grads(*extra):
        return jax.grad(lambda q, k, v: flash_mod.flash_attention(
            q, k, v, None, True, 0, True, *extra).sum(), argnums=(0, 1, 2))

    today, named = grads(), grads(None, None, None)
    assert str(jax.make_jaxpr(today)(q, k, v)) == str(jax.make_jaxpr(named)(q, k, v))
    for a, b in zip(today(q, k, v), named(q, k, v)):
        assert bool(jnp.array_equal(a, b))


def test_flash_tallies_its_tiles_and_the_live_ones(monkeypatch):
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", 64)
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", 128)
    q, k, v = _normal(4, *[(1, 2, 512, 32)] * 3)

    def tally(window):
        before = mx.profiler.counters(device=False)
        flash_mod.flash_attention(q, k, v, None, True, 0, True, None, None, window)
        after = mx.profiler.counters(device=False)
        return tuple(after[n] - before[n] for n in ("flash_tiles", "flash_tiles_live"))

    assert tally(None) == (2 * 8 * 4, 2 * 20)       # causal: 2 + 2 + 4 + 4 + 6 + 6 + 8 + 8 halves
    tiles, live = tally(64)                         # kv tiles of 128 (>= the window)
    assert tiles == 2 * 8 * 4 and live == 2 * 11


def test_flash_attention_op_refuses_a_window_without_causal():
    q = mx.nd.array(np.zeros((1, 2, 16, 8), "float32"))
    with pytest.raises(ValueError, match="causal"):
        mx.nd.flash_attention(q, q, q, window=4)


# ---- the blocks ------------------------------------------------------------------------

def _np(x):
    return np.asarray(x.asnumpy(), "float64")


def _params(block):
    return {k.split("_", 1)[1]: _np(p.data()) for k, p in block.collect_params().items()}


def _silu(x):
    return x / (1.0 + np.exp(-x))


def test_mamba_mixer_is_the_written_out_mixer():
    mx.random.seed(7)
    block = nn.MambaMixer(16, 32, d_state=4, d_conv=4, dt_rank=2, prefix="m_")
    block.initialize(mx.init.Normal(0.3))
    x = mx.nd.array(np.random.default_rng(0).normal(size=(2, 12, 16)).astype("float32"))
    out, s = block(x)
    w, xn = _params(block), _np(x)
    uz = xn @ w["in_weight"].T
    u, z = uz[..., :32], uz[..., 32:]
    up = np.pad(u, ((0, 0), (3, 0), (0, 0)))
    u = _silu(sum(up[:, i:i + 12] * w["conv_weight"][:, i] for i in range(4))
              + w["conv_bias"])
    dbc = u @ w["x_weight"].T
    dt = np.log1p(np.exp(dbc[..., :2] @ w["dt_weight"].T + w["dt_bias"]))
    a = -np.exp(w["a_log"])
    h, ys = np.zeros((2, 32, 4)), []
    for t in range(12):
        h = (np.exp(dt[:, t, :, None] * a) * h
             + dt[:, t, :, None] * dbc[:, t, None, 2:6] * u[:, t, :, None])
        ys.append((h * dbc[:, t, None, 6:]).sum(-1) + w["d"] * u[:, t])
    y = np.stack(ys, 1)
    np.testing.assert_allclose(_np(s), y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(_np(out), (y * _silu(z)) @ w["out_weight"].T,
                               rtol=2e-4, atol=2e-5)
    # the model zoo's own start: A_log = log(1..N), softplus(dt bias) in [1e-3, 1e-1]
    np.testing.assert_allclose(w["a_log"][5], np.log([1, 2, 3, 4]), rtol=1e-6)


def test_mamba_own_initialisers_follow_mambas_convention():
    block = nn.MambaMixer(16, 32, d_state=4, dt_rank=2, prefix="m_")
    block.initialize()
    w = _params(block)
    np.testing.assert_allclose(w["a_log"], np.tile(np.log([1, 2, 3, 4]), (32, 1)),
                               rtol=1e-6)
    step = np.log1p(np.exp(w["dt_bias"]))
    assert 0.9e-3 < step.min() and step.max() < 1.1e-1 and (w["d"] == 1).all()


def test_gated_memory_unit_is_the_written_out_unit():
    block = nn.GatedMemoryUnit(16, 32, prefix="g_")
    block.initialize(mx.init.Normal(0.3))
    rng = np.random.default_rng(1)
    x, m = (mx.nd.array(rng.normal(size=s).astype("float32"))
            for s in ((2, 5, 16), (2, 5, 32)))
    w = _params(block)
    want = (_np(m) * _silu(_np(x) @ w["in_weight"].T)) @ w["out_weight"].T
    np.testing.assert_allclose(_np(block(x, m)), want, rtol=2e-4, atol=2e-5)


def _diff_attention_by_hand(w, x, k, v, heads, kv_heads, d, lam_init, window):
    """Pairs and groups written out: per pair two softmax maps, their
    difference times the group's paired value, the sub-norm, W_o."""
    rows, s, _ = x.shape
    pairs, groups = heads // 2, kv_heads // 2
    if k is None:
        qkv = x @ w["qkv_weight"].T + w["qkv_bias"]
        q = qkv[..., :heads * d]
        k = qkv[..., heads * d:(heads + kv_heads) * d].reshape(rows, s, kv_heads, d)
        v = qkv[..., (heads + kv_heads) * d:].reshape(rows, s, groups, 2 * d)
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    else:
        q = x @ w["q_weight"].T + w["q_bias"]
    q = q.reshape(rows, s, heads, d).transpose(0, 2, 1, 3)
    lam = (np.exp(w["lambda_q1"] @ w["lambda_k1"])
           - np.exp(w["lambda_q2"] @ w["lambda_k2"]) + lam_init)
    row, col = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (col <= row) & ((row - col < window) if window else True)
    out = np.zeros((rows, s, pairs, 2 * d))
    for p in range(pairs):
        g = p // (pairs // groups)
        maps = []
        for i in range(2):
            sc = q[:, i * pairs + p] @ k[:, i * groups + g].transpose(0, 2, 1) / d ** 0.5
            sc = np.where(seen, sc, -np.inf)
            e = np.exp(sc - sc.max(-1, keepdims=True))
            maps.append(e / e.sum(-1, keepdims=True))
        o = (maps[0] - lam * maps[1]) @ v[:, g]
        o = o / np.sqrt((o ** 2).mean(-1, keepdims=True) + 1e-5) * w["subln_gamma"]
        out[:, :, p] = (1 - lam_init) * o
    return out.reshape(rows, s, -1) @ w["o_weight"].T + w["o_bias"], k, v


@pytest.mark.parametrize("window", [None, 5])
def test_diff_attention_self_and_cross_are_the_written_out_layers(window):
    heads, kv_heads, d = 8, 4, 4
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 12, 16)).astype("float32")
    own = nn.DiffAttention(16, heads, kv_heads, d, 0.35, window=window, prefix="a_")
    own.initialize(mx.init.Normal(0.3))
    out, k, v = own(mx.nd.array(x))
    want, k_hand, v_hand = _diff_attention_by_hand(
        _params(own), x.astype("float64"), None, None, heads, kv_heads, d, 0.35, window)
    np.testing.assert_allclose(_np(out), want, rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(_np(k), k_hand, rtol=5e-4, atol=5e-5)
    assert k.shape == (2, kv_heads, 12, d) and v.shape == (2, kv_heads // 2, 12, 2 * d)
    if window is None:      # the cross layer reads this layer's keys and values
        cross = nn.DiffAttention(16, heads, kv_heads, d, 0.5, cross=True, prefix="c_")
        cross.initialize(mx.init.Normal(0.3))
        x2 = rng.normal(size=(2, 12, 16)).astype("float32")
        want2, _, _ = _diff_attention_by_hand(
            _params(cross), x2.astype("float64"), k_hand, v_hand, heads, kv_heads, d,
            0.5, None)
        np.testing.assert_allclose(_np(cross(mx.nd.array(x2), k, v)), want2,
                                   rtol=5e-4, atol=5e-5)


# ---- the model: kinds, the tied head, remat with handed-on tensors --------------------------

_TINY = dict(hidden_size=32, num_hidden_layers=8, mb_per_layer=2, sliding_window=8,
             num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
             layer_norm_eps=1e-5, vocab_size=64, tie_word_embeddings=True,
             mamba_d_state=4, mamba_dt_rank=2, loss_chunks=2)


def test_layer_kinds_follow_the_published_rule():
    kinds = [layer_kind(i, 32) for i in range(32)]
    assert kinds[:16] == ["mamba", "window"] * 8
    assert kinds[16:18] == ["mamba_source", "attention_source"]
    assert kinds[18:] == ["gmu", "cross"] * 7


@pytest.mark.parametrize("held,missing", [((5, 8), "mamba_source"),
                                          ((7, 8), "attention_source")])
def test_a_held_range_without_its_source_raises(held, missing):
    with pytest.raises(ValueError, match=missing):
        phi4_flash({**_TINY, "layers_held": held}, prefix="x_")


def _loss_and_grads(net, ids, labels):
    with autograd.record():
        loss = net(ids, labels)
    loss.backward()
    return float(loss.asnumpy()[0]), {k: _np(p.grad())
                                      for k, p in net.collect_params().items()}


def _batch(rows=2, length=16, vocab=64, seed=3):
    rng = np.random.default_rng(seed)
    return tuple(mx.nd.array(rng.integers(0, vocab, (rows, length)), dtype="int32")
                 for _ in range(2))


def test_the_tied_heads_gradient_is_the_embeddings_plus_the_heads():
    """One parameter read twice. Untied, with the head started at the same
    values, the two leaves' gradients add up to the tied leaf's."""
    ids, labels = _batch()
    mx.random.seed(11)
    tied = phi4_flash(_TINY, prefix="t_")
    tied.initialize(mx.init.Normal(0.05))
    assert "t_head_weight" not in tied.collect_params()
    assert tied.lm_head.weight is tied.embed.weight
    untied = phi4_flash({**_TINY, "tie_word_embeddings": False}, prefix="u_")
    untied.initialize(mx.init.Normal(0.05))
    for name, p in tied.collect_params().items():
        untied.collect_params()["u_" + name[2:]].set_data(p.data())
    untied.collect_params()["u_head_weight"].set_data(tied.embed.weight.data())
    loss_t, g_t = _loss_and_grads(tied, ids, labels)
    loss_u, g_u = _loss_and_grads(untied, ids, labels)
    assert loss_t == pytest.approx(loss_u, rel=1e-5)
    np.testing.assert_allclose(g_t["t_embed_weight"],
                               g_u["u_embed_weight"] + g_u["u_head_weight"],
                               rtol=2e-4, atol=1e-6)
    assert np.abs(g_u["u_head_weight"]).max() > 0 < np.abs(g_u["u_embed_weight"]).max()


def test_remat_with_handed_on_tensors_under_remat_rows_changes_nothing():
    """Layers with several array inputs and outputs (16 -> 18: m; 17 -> 19:
    k, v) under per-layer remat, one row at a time, hybridized: the loss and
    every gradient of the plain eager model."""
    ids, labels = _batch()
    mx.random.seed(12)
    net = phi4_flash(_TINY, prefix="r_")
    net.initialize(mx.init.Normal(0.05))
    plain_loss, plain = _loss_and_grads(net, ids, labels)
    net.remat_per_layer(rows=1)
    net.hybridize()
    loss, grads = _loss_and_grads(net, ids, labels)
    assert loss == pytest.approx(plain_loss, rel=1e-5)
    for name, g in plain.items():
        np.testing.assert_allclose(grads[name], g, rtol=5e-4, atol=1e-6, err_msg=name)


def test_a_remat_block_with_two_inputs_and_two_outputs_splits_the_rows():
    class TwoInTwoOut(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.a = nn.Dense(8, flatten=False, in_units=8)

        def hybrid_forward(self, F, x, y):
            z = self.a(x) * y
            return z, z + x

    class Root(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.inner = TwoInTwoOut()

        def hybrid_forward(self, F, x, y):
            p, q = self.inner(x, y)
            return (p * q).sum()

    rng = np.random.default_rng(5)
    x, y = (mx.nd.array(rng.normal(size=(4, 3, 8)).astype("float32")) for _ in range(2))
    root = Root()
    root.initialize(mx.init.Normal(0.5))

    def run():
        x.attach_grad()
        y.attach_grad()
        with autograd.record():
            out = root(x, y)
        out.backward()
        return float(out.asnumpy()), _np(x.grad), _np(y.grad)

    plain = run()
    root.inner.hybridize(active=False, remat=True, remat_rows=1)
    root.hybridize()
    for got, want in zip(run(), plain):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
