"""Transformer layers + BERT model family tests (BASELINE config #3)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import bert_base
from mxnet_tpu.gluon.model_zoo.bert import BERTMLMHead, BERTNSPHead


def _mha_ref(x, qkv_w, qkv_b, out_w, out_b, heads, causal=False, mask=None):
    b, s, c = x.shape
    d = c // heads
    qkv = x @ qkv_w.T + qkv_b
    q, k, v = np.split(qkv, 3, axis=-1)

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q, k, v = split(q), split(k), split(v)
    sc = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if mask is not None:
        sc = sc + mask
    if causal:
        cm = np.tril(np.ones((s, s), bool))
        sc = np.where(cm, sc, -1e30)
    sc = sc - sc.max(-1, keepdims=True)
    p = np.exp(sc)
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bhkd->bhqd", p, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, c)
    return o @ out_w.T + out_b


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
def test_multi_head_attention_matches_numpy(causal, use_mask):
    rng = np.random.RandomState(0)
    B, S, C, H = 2, 24, 32, 4
    layer = nn.MultiHeadAttention(C, H, causal=causal)
    layer.initialize(init=mx.initializer.Normal(0.1))
    x = mx.nd.array(rng.randn(B, S, C).astype(np.float32))
    mask = None
    m_nd = None
    if use_mask:
        mask = np.zeros((B, 1, S, S), np.float32)
        mask[:, :, :, S - 6:] = -1e9
        m_nd = mx.nd.array(mask)
    with autograd.predict_mode():
        out = layer(x, m_nd)

    get = lambda suffix: next(v.data().asnumpy() for k, v in
                              layer.collect_params().items()
                              if k.endswith(suffix))
    ref = _mha_ref(x.asnumpy(), get("qkv_weight"), get("qkv_bias"),
                   get("out_weight"), get("out_bias"), H,
                   causal=causal, mask=mask)
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-4, atol=1e-5)


def test_transformer_encoder_shapes_and_grad():
    rng = np.random.RandomState(1)
    enc = nn.TransformerEncoder(num_layers=2, units=32, hidden_size=64,
                                num_heads=4, dropout=0.0)
    enc.initialize(init=mx.initializer.Normal(0.05))
    x = mx.nd.array(rng.randn(2, 16, 32).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        y = enc(x)
        loss = (y * y).sum()
    loss.backward()
    assert y.shape == (2, 16, 32)
    g = x.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_bert_forward_and_hybridize():
    rng = np.random.RandomState(2)
    net = bert_base(vocab_size=200, max_length=32, num_layers=2, units=32,
                    hidden_size=64, num_heads=4, dropout=0.0)
    net.initialize(init=mx.initializer.Normal(0.02))
    ids = mx.nd.array(rng.randint(0, 200, (2, 16)), dtype="int32")
    tt = mx.nd.zeros((2, 16), dtype="int32")
    with autograd.predict_mode():
        seq_e, pooled_e = net(ids, tt)
    net.hybridize()
    with autograd.predict_mode():
        seq_h, pooled_h = net(ids, tt)
    assert seq_e.shape == (2, 16, 32) and pooled_e.shape == (2, 32)
    np.testing.assert_allclose(seq_e.asnumpy(), seq_h.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_bert_mlm_nsp_training_step():
    rng = np.random.RandomState(3)
    V = 100
    net = bert_base(vocab_size=V, max_length=32, num_layers=1, units=32,
                    hidden_size=64, num_heads=4, dropout=0.0)
    mlm = BERTMLMHead(V, 32)
    nsp = BERTNSPHead()
    for b in (net, mlm, nsp):
        b.initialize(init=mx.initializer.Normal(0.02))
    params = {}
    for b in (net, mlm, nsp):
        params.update(b.collect_params())
    trainer = gluon.Trainer(params, "adam", {"learning_rate": 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    ids = mx.nd.array(rng.randint(0, V, (4, 16)), dtype="int32")
    tt = mx.nd.zeros((4, 16), dtype="int32")
    mlm_lab = mx.nd.array(rng.randint(0, V, (4, 16)), dtype="int32")
    nsp_lab = mx.nd.array(rng.randint(0, 2, (4,)), dtype="int32")

    losses = []
    for _ in range(5):
        with autograd.record():
            seq, pooled = net(ids, tt)
            l_mlm = loss_fn(mlm(seq).reshape((-1, V)), mlm_lab.reshape((-1,)))
            l_nsp = loss_fn(nsp(pooled), nsp_lab)
            loss = l_mlm.mean() + l_nsp.mean()
        loss.backward()
        trainer.step(4)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("shape", [(3, 8, 32), (24, 32)],
                         ids=["sequence", "gathered_positions"])
@pytest.mark.parametrize("hybridize", [False, True])
def test_mlm_head_keeps_its_inputs_leading_axes(shape, hybridize):
    """The vocabulary projection runs on rows (a 2-D product); the logits
    come back in the input's leading axes, equal to the plain composition."""
    rng = np.random.RandomState(5)
    head = BERTMLMHead(50, 32)
    head.initialize(init=mx.initializer.Normal(0.1))
    if hybridize:
        head.hybridize()
    x = rng.randn(*shape).astype(np.float32)
    out = head(mx.nd.array(x)).asnumpy()
    assert out.shape == shape[:-1] + (50,)

    p = {k.split("_", 1)[1]: v.data().asnumpy()
         for k, v in head.collect_params().items()}
    h = x @ p["transform_weight"].T + p["transform_bias"]
    h = 0.5 * h * (1 + np.vectorize(math.erf)(h / np.sqrt(2)))
    h = (h - h.mean(-1, keepdims=True)) / np.sqrt(h.var(-1, keepdims=True) + 1e-12)
    h = h * p["ln_gamma"] + p["ln_beta"]
    want = h @ p["decoder_weight"].T + p["decoder_bias"]
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)


def test_bert_padding_mask_isolates_padding():
    """Changing token ids in padded positions must not change valid
    positions' outputs — via the additive mask (fourth positional;
    the third is valid_length, GluonNLP order) AND via
    valid_length itself (the flash kernel's native length path)."""
    rng = np.random.RandomState(4)
    net = bert_base(vocab_size=50, max_length=32, num_layers=2, units=32,
                    hidden_size=64, num_heads=4, dropout=0.0)
    net.initialize(init=mx.initializer.Normal(0.02))
    ids = rng.randint(0, 50, (2, 16))
    tt = mx.nd.zeros((2, 16), dtype="int32")
    mask = np.zeros((2, 1, 16, 16), np.float32)
    mask[:, :, :, 12:] = -1e9
    m = mx.nd.array(mask)
    ids2 = ids.copy()
    ids2[:, 12:] = 3
    with autograd.predict_mode():
        s1, _ = net(mx.nd.array(ids, dtype="int32"), tt, None, m)
        s2, _ = net(mx.nd.array(ids2, dtype="int32"), tt, None, m)
    np.testing.assert_allclose(s1.asnumpy()[:, :12], s2.asnumpy()[:, :12],
                               rtol=1e-6, atol=1e-6)
    vl = mx.nd.array(np.array([12, 12], np.float32))
    with autograd.predict_mode():
        v1, _ = net(mx.nd.array(ids, dtype="int32"), tt, vl)
        v2, _ = net(mx.nd.array(ids2, dtype="int32"), tt, vl)
    np.testing.assert_allclose(v1.asnumpy()[:, :12], v2.asnumpy()[:, :12],
                               rtol=1e-6, atol=1e-6)
    # the two maskings agree on valid positions
    np.testing.assert_allclose(v1.asnumpy()[:, :12], s1.asnumpy()[:, :12],
                               rtol=1e-5, atol=1e-6)


def test_mha_segment_flash_vs_composed(monkeypatch):
    """Packed MultiHeadAttention: the flash path (kernel segment mask)
    and the composed path (attention_segment_mask +
    attention_zero_pad_rows) agree on outputs AND input grads,
    including exact zeros on padding rows."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(21)
    B, S, C, Hd = 2, 24, 32, 4
    mx.random.seed(5)
    attn = nn.MultiHeadAttention(C, Hd)
    attn.initialize(init=mx.initializer.Xavier())
    x = mx.nd.array(rng.randn(B, S, C).astype(np.float32))
    seg_np = np.zeros((B, S), np.int32)
    seg_np[0, :10] = 1
    seg_np[0, 10:20] = 2
    seg_np[1, :16] = 1
    seg = mx.nd.array(seg_np, dtype="int32")
    wmask = mx.nd.array((seg_np > 0).astype(np.float32)[:, :, None])

    x.attach_grad()
    with autograd.record():
        out_flash = attn(x, None, None, seg)  # valid_length derived
        (out_flash * wmask).sum().backward()
    g_flash = x.grad.asnumpy().copy()

    # zero additive mask forces the composed path, same math
    zero_mask = mx.nd.zeros((B, 1, S, S))
    x2 = mx.nd.array(x.asnumpy())
    x2.attach_grad()
    with autograd.record():
        out_comp = attn(x2, zero_mask, None, seg)
        (out_comp * wmask).sum().backward()

    np.testing.assert_allclose(out_flash.asnumpy(), out_comp.asnumpy(),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g_flash, x2.grad.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_bert_packed_matches_unpacked_fwd_and_grads(monkeypatch):
    """THE packing acceptance golden: a packed BERT batch (segment_ids
    + per-segment positions + valid_length) reproduces, per sequence,
    the outputs AND parameter gradients of the same sequences run
    unpacked — the flash path's cross-sequence attention is exactly
    zero and padding contributes nothing to the masked loss."""
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel
    from mxnet_tpu.io.packing import pack_sequences, unpack_sequences

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    rs = np.random.RandomState(22)
    vocab, units, L = 120, 32, 40
    mx.random.seed(6)
    net = BERTModel(vocab_size=vocab, units=units, hidden_size=64,
                    num_layers=2, num_heads=4, max_length=L, dropout=0.0,
                    attention_dropout=0.0, use_pooler=False)
    net.initialize(init=mx.initializer.Normal(0.02))

    seqs = [rs.randint(1, vocab, n).astype(np.int32)
            for n in (18, 13, 7, 26)]
    packed = pack_sequences(seqs, L)
    R = packed.data.shape[0]
    ids = mx.nd.array(packed.data, dtype="int32")
    tt = mx.nd.zeros((R, L), dtype="int32")
    seg = mx.nd.array(packed.segment_ids, dtype="int32")
    pos = mx.nd.array(packed.positions, dtype="int32")
    vl = mx.nd.array(packed.valid_length, dtype="int32")
    lmask = mx.nd.array((packed.segment_ids > 0).astype(np.float32))

    params = list(net.collect_params().values())
    with autograd.record():
        seq_out = net(ids, tt, vl, None, seg, pos)
        loss_p = (seq_out.square() * lmask.expand_dims(-1)).sum()
    loss_p.backward()
    packed_out = seq_out.asnumpy()
    packed_grads = {p.name: p.grad().asnumpy().copy() for p in params
                    if p.grad_req != "null"}

    # reference: every sequence alone; grads accumulate across runs
    per_seq = unpack_sequences(packed_out, packed.placements)
    ref_grads = None
    for s, got in zip(seqs, per_seq):
        one = mx.nd.array(s[None, :], dtype="int32")
        with autograd.record():
            ref = net(one, mx.nd.zeros((1, len(s)), dtype="int32"))
            loss_u = ref.square().sum()
        loss_u.backward()
        np.testing.assert_allclose(got, ref.asnumpy()[0],
                                   rtol=2e-5, atol=2e-5)
        g = {p.name: p.grad().asnumpy().copy() for p in params
             if p.grad_req != "null"}
        ref_grads = g if ref_grads is None else \
            {k: ref_grads[k] + g[k] for k in g}

    for name, gp in packed_grads.items():
        np.testing.assert_allclose(
            gp, ref_grads[name], rtol=2e-4, atol=2e-4,
            err_msg=f"param grad mismatch: {name}")
