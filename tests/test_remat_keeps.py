"""What a rematerialised block keeps: the flash kernel names its output and
log-sum-exp (`ops/pallas/flash_attention.py` `REMAT_KEEP`), and a block
marked ``hybridize(remat=True)`` with no ``remat_policy`` keeps values so
named, so its backward reads them instead of running the kernel's forward a
second time. Interpret mode, rehearsal widths: counts and values, no speed."""
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import HybridBlock, nn
from mxnet_tpu.gluon.block import _remat_policy, functionalize
from mxnet_tpu.gluon.nn.transformer import TransformerEncoder
from mxnet_tpu.ops.pallas.flash_attention import (REMAT_KEEP,
                                                  flash_attention_with_lse)

LAYERS, BATCH, SEQ, UNITS, HEADS = 2, 2, 128, 32, 2
RNG = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")


def _kernel_calls(fn, *args):
    """{kernel name: pallas_calls} in the jaxpr of ``fn(*args)``, nested
    jaxprs (a checkpoint's, a scan's body) included."""
    return Counter(re.findall(r"\bname=(mxtpu_\w+)$",
                              str(jax.make_jaxpr(fn)(*args)), re.M))


def _x(seed=0):
    return jnp.asarray(np.random.RandomState(seed)
                       .randn(BATCH, SEQ, UNITS).astype(np.float32))


def _weigh(out):
    # not a plain sum of squares: after a closing LayerNorm that is a constant
    return (out ** 2 * jnp.arange(1, out.shape[-1] + 1)).sum()


def _encoder(policy, **inputs):
    """Per-layer remat over a two-layer encoder, as the `bert_base` cells
    mark it; ``inputs``: valid_length / segment_ids."""
    mx.random.seed(0)
    enc = TransformerEncoder(LAYERS, UNITS, 2 * UNITS, HEADS)
    enc.initialize()
    extra = [None, inputs.get("valid_length"), inputs.get("segment_ids")]
    while extra and extra[-1] is None:
        extra.pop()
    enc(nd.zeros((BATCH, SEQ, UNITS)),
        *[e if e is None else nd.array(e) for e in extra])
    for cell in enc.cells:
        cell.hybridize(active=False, remat=True, remat_policy=policy)
    fn, params = functionalize(enc, training=True)
    tail = [e if e is None else jnp.asarray(e) for e in extra]
    return (lambda p, x: _weigh(fn(p, RNG, x, *tail))), params, _x()


class _Latent(HybridBlock):
    """A causal attention layer whose q.k width (24) is not its v width
    (16): `F.flash_attention` pads both to the kernel's one width."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.q = nn.Dense(HEADS * 24, flatten=False, in_units=UNITS)
            self.k = nn.Dense(HEADS * 24, flatten=False, in_units=UNITS)
            self.v = nn.Dense(HEADS * 16, flatten=False, in_units=UNITS)
            self.o = nn.Dense(UNITS, flatten=False, in_units=HEADS * 16)

    def hybrid_forward(self, F, x):
        def heads(t):
            return F.transpose(F.reshape(t, shape=(0, 0, HEADS, -1)),
                               axes=(0, 2, 1, 3))
        out = F.flash_attention(heads(self.q(x)), heads(self.k(x)),
                                heads(self.v(x)), causal=True)
        return x + self.o(F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                                    shape=(0, 0, -1)))


def _latent_rows(policy):
    """The Kimi cell's marks: per layer, one row at a time (a `lax.map`)."""
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(*[_Latent() for _ in range(LAYERS)])
    net.initialize()
    for layer in net:
        layer.hybridize(active=False, remat=True, remat_policy=policy,
                        remat_rows=1)
    fn, params = functionalize(net, training=True)
    return (lambda p, x: _weigh(fn(p, RNG, x))), params, _x()


def _with_lse(policy):
    """`flash_attention_with_lse` (ring attention's entry), both outputs
    used, under the checkpoint a block with these flags would make."""
    ckpt_policy, _ = _remat_policy({"remat_policy": policy})
    r = np.random.RandomState(1)
    params = [jnp.asarray(r.randn(UNITS, 3 * UNITS).astype(np.float32) * 0.2)
              for _ in range(LAYERS)]

    def layer(w, x):
        q, k, v = (t.reshape(BATCH, SEQ, HEADS, -1).transpose(0, 2, 1, 3)
                   for t in jnp.split(x @ w, 3, axis=-1))
        o, lse = flash_attention_with_lse(q, k, v, None, True)
        o = o * jax.nn.sigmoid(lse)[..., None]
        return x + o.transpose(0, 2, 1, 3).reshape(x.shape)

    def loss(ws, x):
        for w in ws:
            x = jax.checkpoint(layer, policy=ckpt_policy)(w, x)
        return _weigh(x)

    return loss, params, _x()


_SEGMENTS = np.repeat([[1, 2, 3, 0], [1, 1, 2, 2]], SEQ // 4, axis=1)

CASES = {
    # the BERT cells' layer: non-causal, one kv block -> the fused backward
    "encoder_cell": lambda policy: _encoder(policy),
    "causal_unequal_widths_remat_rows": _latent_rows,
    "valid_length": lambda policy: _encoder(
        policy, valid_length=np.array([SEQ, SEQ // 2 + 3], np.int32)),
    "segment_ids": lambda policy: _encoder(
        policy, segment_ids=_SEGMENTS.astype(np.int32)),
    "flash_attention_with_lse": _with_lse,
}


@pytest.mark.parametrize("case", CASES)
def test_remat_keeps_the_flash_output_and_lse(case):
    """Forward kernel calls in the gradient's jaxpr = layers (one in the
    forward pass, none in the rebuild), where keeping nothing gives twice
    that; the backward kernel's calls are the same; and the gradients are
    EQUAL, being the same kernel on the same values."""
    calls, grads = {}, {}
    for policy in (None, "nothing_saveable"):
        loss, params, x = CASES[case](policy)
        calls[policy] = _kernel_calls(jax.grad(loss), params, x)
        # op by op, not one jitted program: XLA may order a fused
        # reduction differently in two programs, and equal means equal
        grads[policy] = jax.grad(loss)(params, x)
    kept, rebuilt = calls[None], calls["nothing_saveable"]
    assert kept["mxtpu_flash_fwd"] == LAYERS
    assert rebuilt["mxtpu_flash_fwd"] == 2 * LAYERS
    backward = {k: n for k, n in kept.items() if "flash_bwd" in k}
    assert backward == {"mxtpu_flash_bwd_fused": LAYERS}
    assert backward == {k: n for k, n in rebuilt.items() if "flash_bwd" in k}
    a, b = (jax.tree_util.tree_leaves(grads[p])
            for p in (None, "nothing_saveable"))
    assert len(a) == len(b) > 0
    for ga, gb in zip(a, b):
        assert np.abs(np.asarray(ga)).sum() > 0
        np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))


@pytest.mark.parametrize("policy, forward_calls", [
    ("nothing_saveable", 2 * LAYERS),      # what every block did before
    ("names:flash_lse", 2 * LAYERS),       # the output is still to rebuild
    ("names:flash_out,flash_lse", LAYERS),
    ("everything_saveable", LAYERS),
])
def test_an_explicit_remat_policy_wins(policy, forward_calls):
    loss, params, x = _encoder(policy)
    assert _kernel_calls(jax.grad(loss), params, x)["mxtpu_flash_fwd"] \
        == forward_calls
    mine = jax.checkpoint_policies.dots_saveable
    assert _remat_policy({"remat_policy": mine}) == (mine, ())
    assert _remat_policy({})[1] == REMAT_KEEP == ("flash_out", "flash_lse")


@pytest.mark.parametrize("policy, forward_calls", [
    (None, LAYERS), ("nothing_saveable", 2 * LAYERS)])
def test_whole_block_remat_takes_the_same_policy(policy, forward_calls):
    """``net.hybridize(remat=True)``: one checkpoint around the CachedOp's
    whole trace, with the default and the explicit policy of the per-layer
    site (it used to pass neither)."""
    mx.random.seed(0)
    enc = TransformerEncoder(LAYERS, UNITS, 2 * UNITS, HEADS)
    enc.initialize()
    enc.hybridize(remat=True, remat_policy=policy)
    x = nd.array(np.asarray(_x()))
    x.attach_grad()
    with autograd.record():
        loss = (enc(x) ** 2 * nd.arange(1, UNITS + 1)).sum()
    loss.backward()
    assert np.abs(x.grad.asnumpy()).sum() > 0
    (op, *_), = enc._cached_graph.values()     # the one CachedOp
    program = op.fn                            # its jitted forward
    arrays = [RNG, x._data] + [p.data()._data
                               for p in enc.collect_params().values()]
    calls = _kernel_calls(
        jax.grad(lambda *a: _weigh(program(*a)[0]), argnums=1), *arrays)
    assert calls["mxtpu_flash_fwd"] == forward_calls
    assert calls["mxtpu_flash_bwd_fused"] == LAYERS


def test_a_block_that_names_nothing_traces_what_it_traced_before():
    """No flash call inside (a Dense + activation child, a convolution:
    `conv_out` is not among the default's names): the gradient's jaxpr is
    the one that keeping nothing gives, but for the policy's own name."""
    class Net(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.conv = nn.Conv2D(4, 3, padding=1, in_channels=2)
                self.dense = nn.Dense(8, in_units=4 * 6 * 6)

        def hybrid_forward(self, F, x):
            return self.dense(F.Activation(self.conv(x), act_type="relu"))

    def text(policy):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(Net())
        net.initialize()
        net[0].hybridize(active=False, remat=True, remat_policy=policy)
        fn, params = functionalize(net, training=True)
        x = jnp.ones((3, 2, 6, 6), jnp.float32)
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: (fn(p, RNG, x) ** 2).sum()))
        return re.sub(r"policy=[^\n]*", "policy=_", str(jaxpr(params)))

    default, nothing = text(None), text("nothing_saveable")
    assert "checkpoint" in default or "remat" in default
    assert default == nothing
    assert default != text("names:conv_out")
