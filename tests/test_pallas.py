"""Pallas kernel numerics (interpret mode on CPU).

Mirrors the reference's cross-backend golden harness
(tests/python/gpu/test_operator_gpu.py check_consistency): the fused
kernel path is compared against the plain jnp/XLA lowering.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.test_utils import device_tols, _on_tpu
RTOL, ATOL = device_tols("float32")
# near-zero grad rows (layernorm, masked attention) need absolute
# headroom on-chip; the CPU/interpret golden path keeps the tight floor
# so interpreted-kernel numeric regressions stay visible
ATOL = max(ATOL, 1e-4 if _on_tpu() else 1e-5)
import pytest

from mxnet_tpu.ops.pallas.flash_attention import (flash_attention,
                                                  flash_attention_with_lse)
from mxnet_tpu.ops.pallas.layer_norm import layer_norm_fused
from mxnet_tpu.ops.pallas.softmax_xent import softmax_xent_fused
from test_chip_compile import _FLASH, flash_caps, flash_mod

xent_mod = importlib.import_module("mxnet_tpu.ops.pallas.softmax_xent")


def _ln_ref(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    v = x.var(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(v + eps) * g + b


def _attn_ref(q, k, v, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        m = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(m, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("shape", [(37, 96), (8, 3, 128), (130, 768)])
def test_layer_norm_fused_fwd_bwd(shape):
    rng = np.random.RandomState(0)
    d = shape[-1]
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g = jnp.asarray(rng.randn(d).astype(np.float32))
    b = jnp.asarray(rng.randn(d).astype(np.float32))

    out = layer_norm_fused(x, g, b, 1e-5, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ln_ref(x, g, b)),
                               rtol=RTOL, atol=ATOL)

    # weighted sum so per-element grads differ
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))
    gp = jax.grad(lambda x, g, b: (layer_norm_fused(x, g, b, 1e-5, True) * w).sum(),
                  argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(lambda x, g, b: (_ln_ref(x, g, b) * w).sum(),
                  argnums=(0, 1, 2))(x, g, b)
    for a, c in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,skv", [(64, 64), (100, 100), (48, 120)])
def test_flash_attention_fwd_bwd(causal, sq, skv):
    rng = np.random.RandomState(1)
    B, H, D = 2, 3, 64
    q = jnp.asarray(rng.randn(B, H, sq, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, skv, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, skv, D).astype(np.float32))
    # end-aligned causal for sq != skv (KV-cache decode convention,
    # matches the op layer's q_offset wiring)
    q_off = skv - sq if causal else 0

    o = flash_attention(q, k, v, None, causal, q_off, True)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(_attn_ref(q, k, v, causal)),
                               rtol=RTOL, atol=ATOL)

    w = jnp.asarray(rng.randn(B, H, sq, D).astype(np.float32))
    gf = jax.grad(lambda q, k, v: (flash_attention(q, k, v, None, causal, q_off, True) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (_attn_ref(q, k, v, causal) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=RTOL, atol=ATOL)


def test_flash_attention_fully_masked_rows():
    # sq > skv causal (negative q_offset — the KV-cache shape
    # op_impl_nn.flash_attention_op produces): rows before the first
    # visible key must return exactly zero (not an average of V), with
    # lse at the -inf sentinel and zero gradients through those rows.
    rng = np.random.RandomState(4)
    B, H, sq, skv, D = 1, 2, 120, 48, 32
    q = jnp.asarray(rng.randn(B, H, sq, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, skv, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, skv, D).astype(np.float32))
    q_off = skv - sq
    nm = sq - skv  # rows 0..nm-1 see no keys

    o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                      q_offset=q_off, interpret=True)
    assert np.all(np.asarray(o[:, :, :nm]) == 0.0)
    assert np.all(np.asarray(lse[:, :, :nm]) <= -1e29)

    # visible region matches the jnp fallback (op_impl_nn masking)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    m = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
    p = jax.nn.softmax(jnp.where(m, s, -1e30), -1)
    p = jnp.where(m.any(-1, keepdims=True), p, 0.0)
    ref = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(np.asarray(o[:, :, nm:]),
                               np.asarray(ref[:, :, nm:]),
                               rtol=RTOL, atol=ATOL)

    w = jnp.asarray(rng.randn(B, H, sq, D).astype(np.float32))
    g = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, None, True, q_off, True) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    assert np.all(np.asarray(g[0][:, :, :nm]) == 0.0)
    for a in g:
        assert np.all(np.isfinite(np.asarray(a)))


def test_flash_attention_lse():
    rng = np.random.RandomState(2)
    B, H, S, D = 1, 2, 100, 32
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    o, lse = flash_attention_with_lse(q, k, v, causal=True, interpret=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    m = jnp.tril(jnp.ones((S, S), bool))
    ref = jax.scipy.special.logsumexp(jnp.where(m, s, -np.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


_XENT = [
    # n, v, dtype, (block_v, block_n) of 2-byte logits or None for the
    # module's; float32 blocks are half as tall (the same bytes a tile)
    (50, 1000, "float32", None),
    (64, 128, "float32", None),
    (33, 513, "float32", None),
    # one whole block of a vocabulary that is no multiple of 8
    (200, 1018, "float32", None),
    # the module's tiles, a partial last block on both axes
    (640, 2560, "bfloat16", None),
    (640, 2560, "float32", None),
    # several blocks: a vocabulary that is no multiple of 8, one that is a
    # multiple of 16 and not of 128, an aligned one; tokens that are no
    # multiple of the token block
    (300, 1018, "float32", (256, 128)),
    (300, 1018, "bfloat16", (256, 128)),
    (300, 1072, "float32", (256, 128)),
    (256, 1072, "bfloat16", (512, 128)),
    (384, 2048, "bfloat16", (512, 128)),
]


@pytest.mark.parametrize("n,v,dtype,tiles", _XENT)
def test_softmax_xent_fused(monkeypatch, n, v, dtype, tiles):
    """Loss and dlogits against `jax.nn.log_softmax`'s, in float32 on the
    same (rounded) logits."""
    if tiles:
        monkeypatch.setattr(xent_mod, "_BLOCK_V", tiles[0])
        monkeypatch.setattr(xent_mod, "_BLOCK_N", tiles[1])
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(n, v).astype(np.float32) * 3).astype(dtype)
    labels = jnp.asarray(rng.randint(0, v, n).astype(np.int32))
    w = jnp.asarray(rng.randn(n).astype(np.float32))

    def ref(x):
        return -jax.nn.log_softmax(x.astype(jnp.float32))[jnp.arange(n), labels]

    loss = softmax_xent_fused(logits, labels, True)
    assert loss.dtype == jnp.float32 and loss.shape == (n,)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref(logits)),
                               rtol=RTOL, atol=ATOL)

    gx = jax.grad(lambda x: (softmax_xent_fused(x, labels, True) * w).sum())(logits)
    gr = jax.grad(lambda x: (ref(x) * w).sum())(logits)
    assert gx.dtype == logits.dtype and gx.shape == (n, v)
    # a bfloat16 gradient is the float32 one rounded once
    rtol = 2 ** -7 if dtype == "bfloat16" else RTOL
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(gr, np.float32),
                               rtol=rtol, atol=ATOL)


def test_op_dispatch_interpret(monkeypatch):
    """mx.nd ops route through the Pallas path under the interpret env."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    import mxnet_tpu as mx

    rng = np.random.RandomState(4)
    x = mx.nd.array(rng.randn(10, 64).astype(np.float32))
    g = mx.nd.array(rng.randn(64).astype(np.float32))
    b = mx.nd.array(rng.randn(64).astype(np.float32))
    out = mx.nd.LayerNorm(x, g, b, axis=-1, eps=1e-5)
    ref = _ln_ref(x._data, g._data, b._data)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)

    # autograd through the fused op
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.LayerNorm(x, g, b, axis=-1, eps=1e-5)
        loss = (y * y).sum()
    loss.backward()
    gr = jax.grad(lambda x: (_ln_ref(x, g._data, b._data) ** 2).sum())(x._data)
    np.testing.assert_allclose(x.grad.asnumpy(), np.asarray(gr),
                               rtol=RTOL, atol=ATOL)

    q = mx.nd.array(rng.randn(2, 2, 32, 16).astype(np.float32))
    k = mx.nd.array(rng.randn(2, 2, 32, 16).astype(np.float32))
    v = mx.nd.array(rng.randn(2, 2, 32, 16).astype(np.float32))
    o = mx.nd.flash_attention(q, k, v, causal=True)
    ref = _attn_ref(q._data, k._data, v._data, causal=True)
    np.testing.assert_allclose(o.asnumpy(), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_flash_attention_under_high_matmul_precision():
    """Regression: the process-wide jax_default_matmul_precision='high'
    (set by mxnet_tpu/__init__.py for f32 parity) must not leak into the
    kernel's dots — Mosaic rejects HIGH ('Unsupported dot precision').
    Kernel dots carry explicit static precision chosen per input dtype."""
    from mxnet_tpu.ops.pallas.flash_attention import _dot_precision
    assert _dot_precision(jnp.float32) == jax.lax.Precision.HIGHEST
    assert _dot_precision(jnp.bfloat16) == jax.lax.Precision.DEFAULT
    k = jax.random.PRNGKey(3)
    q = jax.random.normal(k, (1, 2, 64, 32), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (1, 2, 64, 32),
                           jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (1, 2, 64, 32),
                          jnp.float32)
    # on the real chip run NON-interpreted so Mosaic actually compiles
    # the dots (interpret mode cannot reproduce the crash); the CPU
    # suite can only exercise the interpreter
    with jax.default_matmul_precision("high"):
        o = flash_attention(q, kk, v, causal=True, interpret=not _on_tpu())
    ref = _attn_ref(q, kk, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_flash_attention_large_asymmetric_blocks(monkeypatch):
    """seq 384 with FORCED 256x128 tiles: a genuine multi-block grid
    with bq != bk and causal block-skip — golden vs jnp. (The defaults
    clamp to one 384x384 block at this length, which would not cover
    the multi-block path the 512-cap defaults enable on-chip.)"""
    flash_caps(monkeypatch, 256, 128)
    rng = np.random.RandomState(6)
    B, H, S, D = 1, 2, 384, 64
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    for causal in (False, True):
        o = flash_attention(q, k, v, None, causal, 0, True)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(_attn_ref(q, k, v, causal)),
            rtol=RTOL, atol=ATOL)
    w = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    g = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, None, True, 0, True) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (_attn_ref(q, k, v, True) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=RTOL, atol=ATOL)


def test_flash_attention_fused_vs_split_bwd(monkeypatch):
    """The single-pass backward and the dq/dkv pair must produce
    identical gradients for the same q, k, v: S=300 (ragged padding)
    under kv caps that give nk=1 and nk=2 (both the fused kernel; nk=2
    is the LARGEST grid it takes, and exercises its multi-k dq partial
    sum and the causal invisible-pair zeroing branch) and nk=3 (the
    pair), causal and not."""
    rng = np.random.RandomState(7)
    B, H, S, D = 1, 2, 300, 64
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    w = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))

    for causal in (False, True):
        def f(q, k, v):
            return (flash_attention(q, k, v, None, causal, 0, True) * w).sum()

        grads = {}
        for nk, cap in ((1, 384), (2, 256), (3, 128)):
            flash_caps(monkeypatch, 128, cap)
            assert flash_mod._pad_len(S, cap) // cap == nk
            grads[nk] = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: (_attn_ref(q, k, v, causal) * w).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for one, two, split, r in zip(grads[1], grads[2], grads[3], gr):
            # fused vs split: same math, same f32 accumulation order up
            # to the cross-k partial sum — tight tolerance
            np.testing.assert_allclose(np.asarray(one), np.asarray(split),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(two), np.asarray(split),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(two), np.asarray(r),
                                       rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("case", sorted(_FLASH))
def test_flash_backward_kernel_follows_the_kv_grid(case, monkeypatch):
    """Which backward runs is chosen from the count of kv blocks alone,
    at the shapes the chip compiles (and the two cells run): the
    one-pass kernel up to two blocks, the dq/dkv pair beyond. Traced on
    shapes; nothing executes."""
    shape, causal, use_lens, use_segs, tiles = _FLASH[case]
    if tiles:
        flash_caps(monkeypatch, *tiles)
    b, _, s, _ = shape
    nk = -(-s // flash_mod._pick_blocks(s, s)[1])
    if case in ("b64_s512", "mla_s8192_d256_causal"):   # the cells' shapes
        assert nk == {"b64_s512": 1, "mla_s8192_d256_causal": 4}[case]

    def step(q, k, v, lens, segs):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, None, causal, 0, False,
            lens if use_lens else None, segs if use_segs else None)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = str(jax.make_jaxpr(step)(
        qkv, qkv, qkv, jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b, s), jnp.int32)))
    fused = "mxtpu_flash_bwd_fused" in text
    pair = "mxtpu_flash_bwd_dq" in text and "mxtpu_flash_bwd_dkv" in text
    assert "mxtpu_flash_fwd" in text
    assert (fused, pair) == ((True, False) if nk <= 2 else (False, True))


@pytest.mark.skipif(not _on_tpu(), reason="memory analysis needs the real chip")
def test_flash_attention_o_of_s_memory():
    """The flash kernel's compiled temp footprint must be O(S) — far
    below the composed path's materialized (B,H,S,S) score block (the
    ring fold relies on this per step: VERDICT r2 #6 'O(C) per-step
    memory')."""
    B, H, S, D = 1, 8, 2048, 64
    q = jnp.zeros((B, H, S, D), jnp.bfloat16)
    score_bytes = B * H * S * S * 4  # one f32 (S,S) block per (b,h)

    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, None, True, 0))
    ref = jax.jit(lambda q, k, v: _attn_ref(q, k, v, True))
    m_flash = flash.lower(q, q, q).compile().memory_analysis()
    m_ref = ref.lower(q, q, q).compile().memory_analysis()
    if m_flash is None or m_ref is None:
        pytest.skip("memory_analysis unavailable on this backend")
    assert m_flash.temp_size_in_bytes < score_bytes / 4, (
        m_flash.temp_size_in_bytes, score_bytes)
    assert m_ref.temp_size_in_bytes > m_flash.temp_size_in_bytes * 4, (
        m_ref.temp_size_in_bytes, m_flash.temp_size_in_bytes)


def test_lstm_bidirectional_final_states_match_a_step_loop():
    """Bidirectional two-layer gluon LSTM against a plain per-step
    NumPy loop, on outputs AND final states: each direction's final
    state is that of its last PROCESSED step (for the reverse direction
    the step at t=0, taken before its outputs are flipped back to
    forward-time order), not out[-1]."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rng = np.random.RandomState(31)
    T, N, I, H, L = 5, 4, 12, 8, 2
    x = rng.randn(T, N, I).astype(np.float32)
    h0 = (rng.randn(2 * L, N, H) * 0.5).astype(np.float32)
    c0 = (rng.randn(2 * L, N, H) * 0.5).astype(np.float32)
    net = mx.gluon.rnn.LSTM(H, num_layers=L, bidirectional=True)
    net.initialize()
    net(nd.array(x), net.begin_state(batch_size=N))     # shapes the params
    weights = {}
    for name, param in net.collect_params().items():
        value = (rng.randn(*param.shape) * 0.4).astype(np.float32)
        param.set_data(nd.array(value))
        weights[name.split("_", 1)[1]] = value
    out, (h, c) = net(nd.array(x), [nd.array(h0), nd.array(c0)])

    def sigmoid(z):
        return 1.0 / (1.0 + np.exp(-z))

    def one_direction(seq, tag, h, c):
        outs = []
        for x_t in seq:
            z = x_t @ weights[tag + "_i2h_weight"].T \
                + weights[tag + "_i2h_bias"] \
                + h @ weights[tag + "_h2h_weight"].T \
                + weights[tag + "_h2h_bias"]
            i, f, g, o = np.split(z, 4, axis=-1)
            c = sigmoid(f) * c + sigmoid(i) * np.tanh(g)
            h = sigmoid(o) * np.tanh(c)
            outs.append(h)
        return np.stack(outs), h, c

    seq, hs, cs = x, [], []
    for layer in range(L):
        fwd, h_f, c_f = one_direction(seq, "l%d" % layer,
                                      h0[2 * layer], c0[2 * layer])
        bwd, h_b, c_b = one_direction(seq[::-1], "r%d" % layer,
                                      h0[2 * layer + 1], c0[2 * layer + 1])
        seq = np.concatenate([fwd, bwd[::-1]], axis=-1)
        hs += [h_f, h_b]
        cs += [c_f, c_b]
    np.testing.assert_allclose(out.asnumpy(), seq, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h.asnumpy(), np.stack(hs), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c.asnumpy(), np.stack(cs), rtol=1e-5, atol=1e-5)


def _attn_len_ref(q, k, v, kv_lens, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    mask = jnp.arange(k.shape[2])[None, None, None, :] \
        < kv_lens[:, None, None, None]
    if causal:
        cm = jnp.tril(jnp.ones((s.shape[-2], s.shape[-1]), bool),
                      k=s.shape[-1] - s.shape[-2])
        mask = jnp.logical_and(mask, cm)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.broadcast_to(mask, s.shape).any(-1, keepdims=True),
                  p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("split_bwd", [False, True])
def test_flash_attention_variable_length(causal, split_bwd, monkeypatch):
    """Per-example kv_lens (VERDICT r3 #2): forward and all three
    gradients match the masked composed softmax, on both backward
    paths, with lengths crossing tile boundaries and the loss masking
    padded positions (the contract under which padded-row grads vanish
    identically)."""
    rng = np.random.RandomState(7)
    B, H, S, D = 3, 2, 40, 16
    if split_bwd:       # three kv blocks: the dq/dkv pair
        flash_caps(monkeypatch, 128, 128)
        S = 300
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    # incl. an EMPTY example
    kv_lens = jnp.asarray([S, S // 2 - 3, 0], jnp.int32)

    o = flash_attention(q, k, v, None, causal, 0, True, kv_lens)
    ref = _attn_len_ref(q, k, v, kv_lens, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    assert np.all(np.asarray(o[2]) == 0.0)  # empty example -> exact zeros

    wmask = (jnp.arange(S)[None, :] < kv_lens[:, None]) \
        .astype(jnp.float32)[:, None, :, None]
    w = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * wmask
    gf = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, None, causal, 0, True, kv_lens) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (_attn_len_ref(
        q, k, v, kv_lens, causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=RTOL, atol=ATOL)
    # keys past each example's length get identically-zero dk/dv
    for g in gf[1:]:
        arr = np.asarray(g)
        for b_ in range(B):
            assert np.all(arr[b_, :, int(kv_lens[b_]):] == 0.0)


def _attn_seg_ref(q, k, v, seg, kv_lens=None, causal=False):
    """Composed masked softmax with block-diagonal segment isolation —
    the golden the packed kernel must match exactly."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    mask = seg[:, None, :, None] == seg[:, None, None, :]
    if kv_lens is not None:
        mask = jnp.logical_and(
            mask, jnp.arange(k.shape[2])[None, None, None, :]
            < kv_lens[:, None, None, None])
    if causal:
        cm = jnp.tril(jnp.ones((s.shape[-2], s.shape[-1]), bool))
        mask = jnp.logical_and(mask, cm)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.broadcast_to(mask, s.shape).any(-1, keepdims=True),
                  p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _packed_case(rng, B, H, S, D):
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    seg = np.zeros((B, S), np.int32)
    # row 0: three segments + padding; row 1: one long segment + padding
    b0 = [0, S // 3, S // 2, int(S * 0.9)]
    seg[0, b0[0]:b0[1]] = 1
    seg[0, b0[1]:b0[2]] = 2
    seg[0, b0[2]:b0[3]] = 3
    seg[1, :int(S * 0.8)] = 1
    lens = jnp.asarray([int(S * 0.9), int(S * 0.8)], jnp.int32)
    return q, k, v, jnp.asarray(seg), lens


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("split_bwd", [False, True])
def test_flash_attention_segment_isolation(causal, split_bwd, monkeypatch):
    """Sequence packing: per-token segment_ids make attention exactly
    block-diagonal — forward and all three gradients match the composed
    masked softmax on BOTH backward paths, including causal mode and
    padding slots (id 0) that must emit exact zeros."""
    rng = np.random.RandomState(11)
    B, H, S, D = 2, 2, 40, 16
    if split_bwd:       # three kv blocks: the dq/dkv pair
        flash_caps(monkeypatch, 128, 128)
        S = 300
    q, k, v, seg, lens = _packed_case(rng, B, H, S, D)

    o = flash_attention(q, k, v, None, causal, 0, True, lens, seg)
    ref = _attn_seg_ref(q, k, v, seg, lens, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    # padding rows (segment 0 past each row's used length) -> exact 0
    pad = np.asarray(seg) == 0
    assert np.all(np.asarray(o)[pad[:, None, :].repeat(H, 1)] == 0.0)

    # loss masks padding (the packed-training contract)
    w = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) \
        * (np.asarray(seg)[:, None, :, None] > 0)
    gf = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, None, causal, 0, True, lens, seg) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (_attn_seg_ref(
        q, k, v, seg, lens, causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=RTOL, atol=ATOL)
    # padding slots get identically-zero dk/dv
    for g in gf[1:]:
        assert np.all(np.asarray(g)[pad[:, None, :, None]
                                    .repeat(H, 1).repeat(D, 3)] == 0.0)


def test_flash_attention_segment_multiblock(monkeypatch):
    """Multi-tile packed grid (forced 64x128 tiles over S=512): the
    SMEM segment-range whole-block skip and the lane-broadcast equality
    mask must agree with the composed reference across tile boundaries;
    128<block_k exercises the pltpu.repeat id layout."""
    flash_caps(monkeypatch, 64, 128)
    rng = np.random.RandomState(12)
    B, H, S, D = 2, 2, 512, 32
    q, k, v, seg, lens = _packed_case(rng, B, H, S, D)
    for causal in (False, True):
        o = flash_attention(q, k, v, None, causal, 0, True, lens, seg)
        ref = _attn_seg_ref(q, k, v, seg, lens, causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)
    w = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) \
        * (np.asarray(seg)[:, None, :, None] > 0)
    gf = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, None, False, 0, True, lens, seg) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (_attn_seg_ref(
        q, k, v, seg, lens, False) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=RTOL, atol=ATOL)
    # the repeat branch (block_k > 128) on the same case
    flash_caps(monkeypatch, 64, 256)
    o = flash_attention(q, k, v, None, False, 0, True, lens, seg)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(_attn_seg_ref(q, k, v, seg, lens, False)),
        rtol=RTOL, atol=ATOL)


def test_flash_attention_segments_reject_cross_attention():
    """segment_ids with Sq != Skv (KV-cache decode) has no packed
    meaning — the kernel refuses instead of mis-masking."""
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(1, 1, 8, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 1, 16, 8).astype(np.float32))
    seg = jnp.ones((1, 16), jnp.int32)
    with pytest.raises(ValueError):
        flash_attention(q, k, k, None, False, 0, True, None, seg)


def test_flash_attention_op_segment_dispatch(monkeypatch):
    """mx.nd.flash_attention(q, k, v, valid_len, segment_ids) routes
    the ids to the kernel AND the jnp fallback identically, and the
    packed output for each segment matches that segment run alone."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rng = np.random.RandomState(14)
    B, H, S, D = 1, 2, 32, 8
    q = mx.nd.array(rng.randn(B, H, S, D).astype(np.float32) * 0.3)
    k = mx.nd.array(rng.randn(B, H, S, D).astype(np.float32) * 0.3)
    v = mx.nd.array(rng.randn(B, H, S, D).astype(np.float32))
    seg_np = np.zeros((B, S), np.int32)
    seg_np[0, :12] = 1
    seg_np[0, 12:26] = 2
    seg = mx.nd.array(seg_np, dtype="int32")
    vl = mx.nd.array(np.array([26], np.float32))

    out_kernel = nd.flash_attention(q, k, v, vl, seg)
    monkeypatch.setenv("MXNET_TPU_DISABLE_PALLAS", "1")
    out_jnp = nd.flash_attention(q, k, v, vl, seg)
    monkeypatch.delenv("MXNET_TPU_DISABLE_PALLAS")
    np.testing.assert_allclose(out_kernel.asnumpy(), out_jnp.asnumpy(),
                               rtol=RTOL, atol=ATOL)

    # each packed segment == the same tokens run alone (unpacked golden)
    for lo, hi in ((0, 12), (12, 26)):
        alone = nd.flash_attention(
            q[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi])
        np.testing.assert_allclose(out_kernel.asnumpy()[:, :, lo:hi],
                                   alone.asnumpy(), rtol=RTOL, atol=ATOL)


def test_flash_attention_op_valid_len_dispatch(monkeypatch):
    """mx.nd.flash_attention(q, k, v, valid_len) routes the length to
    the kernel AND the jnp fallback identically."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rng = np.random.RandomState(8)
    B, H, S, D = 2, 2, 24, 8
    q = mx.nd.array(rng.randn(B, H, S, D).astype(np.float32) * 0.3)
    k = mx.nd.array(rng.randn(B, H, S, D).astype(np.float32) * 0.3)
    v = mx.nd.array(rng.randn(B, H, S, D).astype(np.float32))
    vl = mx.nd.array(np.array([24, 9], np.float32))

    out_kernel = nd.flash_attention(q, k, v, vl)
    monkeypatch.setenv("MXNET_TPU_DISABLE_PALLAS", "1")
    out_jnp = nd.flash_attention(q, k, v, vl)
    np.testing.assert_allclose(out_kernel.asnumpy(), out_jnp.asnumpy(),
                               rtol=RTOL, atol=ATOL)
    # sanity: the length actually masks (row attending to only 9 keys
    # differs from the unmasked result)
    monkeypatch.delenv("MXNET_TPU_DISABLE_PALLAS")
    full = nd.flash_attention(q, k, v)
    assert np.abs(out_kernel.asnumpy()[1] - full.asnumpy()[1]).max() > 1e-3


def test_transformer_valid_length_end_to_end(monkeypatch):
    """BERT-style MultiHeadAttention with valid_length: flash path ==
    composed attention_length_mask path, gradients included."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.nn.transformer import MultiHeadAttention

    rng = np.random.RandomState(9)
    B, S, C, Hd = 2, 20, 32, 4
    mx.random.seed(11)
    attn = MultiHeadAttention(C, Hd)
    x = mx.nd.array(rng.randn(B, S, C).astype(np.float32))
    attn.initialize(init=mx.initializer.Xavier())
    vl = mx.nd.array(np.array([20, 0], np.float32))

    wmask = mx.nd.array((np.arange(S)[None, :, None]
                         < np.array([20, 0])[:, None, None])
                        .astype(np.float32))

    x.attach_grad()
    with autograd.record():
        out_flash = attn(x, None, vl)
        (out_flash * wmask).sum().backward()
    g_flash = x.grad.asnumpy().copy()

    # force the composed path via a zero additive mask (same math)
    zero_mask = mx.nd.zeros((B, 1, S, S))
    x2 = mx.nd.array(x.asnumpy())
    x2.attach_grad()
    with autograd.record():
        out_comp = attn(x2, zero_mask, vl)
        (out_comp * wmask).sum().backward()

    # FULL-output agreement, including the empty (valid_len == 0)
    # example: both paths must emit the zero-attention result there
    # (attention_zero_empty_rows on the composed path, l==0 guard in
    # the kernel)
    np.testing.assert_allclose(out_flash.asnumpy(), out_comp.asnumpy(),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(g_flash, x2.grad.asnumpy(),
                               rtol=RTOL, atol=ATOL)
