"""The flash kernel at two head widths: q.k at ``d`` and v at its own ``dv``
(latent attention: q.k 192 = 128 + 64 against v 128).

In interpret mode on the CPU: the forward, the fused backward and the split
dq / dkv arms at unequal widths against a dense softmax and against the
padded single-width call (pad q, k, v to one width, call, slice), which is
how the op ran such calls before the kernel took v's width; grouped
key/value heads; the lse cotangent of ``flash_attention_with_lse``; the op
padding q and k only where v's multiple of 128 is narrower than q.k's; the
other cells' op calls tracing the jaxprs they traced before (sha256 pins);
and the two trace-time tallies ``flash_calls`` / ``flash_two_width_calls``.

Tolerances: float32 inputs, so the kernels' dots run at ``HIGHEST``; what is
left is summation order: 1e-5 of the largest value against the dense
softmax, 1e-6 against the padded call (the same products, the zero columns
left out)."""
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.ndarray import op_impl_nn

flash_mod = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
F32, BF16 = jnp.float32, jnp.bfloat16


def _normal(seed, *shapes):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), s, F32)
            for i, s in enumerate(shapes)]


def _close(got, want, tol):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) / scale < tol


def _dense(q, k, v, scale):
    """Causal softmax(q k^T * scale) v and the rows' log-sum-exp, in float32."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    n = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest"), lse


def _padded(q, k, v, scale):
    """The single-width call: q, k and v zero-padded to one multiple of 128,
    the output cut back to v's width."""
    wide = -(-max(q.shape[-1], v.shape[-1]) // 128) * 128
    q, k, vp = (jnp.pad(t, ((0, 0),) * 3 + ((0, wide - t.shape[-1]),)) for t in (q, k, v))
    o, lse = flash_mod.flash_attention_with_lse(q, k, vp, scale, True, 0, True)
    return o[..., :v.shape[-1]], lse


def _two_width(q, k, v, scale):
    return flash_mod.flash_attention_with_lse(q, k, v, scale, True, 0, True)


# (q.k width, v width, query heads, key/value heads); arm: (length, tile caps)
_WIDTHS = {"mla_192_128": (192, 128, 2, 2), "narrow_128_64": (128, 64, 2, 2),
           "mla_grouped": (192, 128, 4, 2)}
_ARMS = {"fused_s1024": (1024, None), "split_s1000": (1000, (128, 256))}


@pytest.mark.parametrize("arm", sorted(_ARMS))
@pytest.mark.parametrize("widths", sorted(_WIDTHS))
def test_two_widths_match_the_dense_softmax_and_the_padded_call(monkeypatch, widths, arm):
    d, dv, heads, kv_heads = _WIDTHS[widths]
    length, caps = _ARMS[arm]
    if caps:
        monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", caps[0])
        monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", caps[1])
    nk = -(-length // (caps[1] if caps else 2048))
    assert (nk <= 2) == arm.startswith("fused")
    q, k = _normal(1, (1, heads, length, d), (1, kv_heads, length, d))
    v, wo, wl = _normal(2, (1, kv_heads, length, dv), (1, heads, length, dv),
                        (1, heads, length))
    q, k = q * 0.3, k * 0.3
    scale = d ** -0.5

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v, scale)
            return (o * wo).sum() + (lse * wl).sum()      # both cotangents
        return f

    o, lse = _two_width(q, k, v, scale)
    assert o.shape == (1, heads, length, dv)
    grads = jax.grad(loss(_two_width), (0, 1, 2))(q, k, v)
    _close((o, lse), _dense(q, k, v, scale), 1e-5)
    _close(grads, jax.grad(loss(_dense), (0, 1, 2))(q, k, v), 1e-5)
    _close((o, lse), _padded(q, k, v, scale), 1e-6)
    _close(grads, jax.grad(loss(_padded), (0, 1, 2))(q, k, v), 1e-6)


@pytest.mark.parametrize("arm", sorted(_ARMS))
def test_the_kernels_run_v_at_its_own_width(monkeypatch, arm):
    """The Pallas calls' v, o, do and dv blocks and the forward's accumulator
    are v's width; q, k, dq and dk keep q.k's."""
    length, caps = _ARMS[arm]
    if caps:
        monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", caps[0])
        monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", caps[1])
    q = jax.ShapeDtypeStruct((1, 2, length, 256), BF16)
    v = jax.ShapeDtypeStruct((1, 2, length, 128), BF16)
    grad = jax.grad(lambda q, k, v: flash_mod.flash_attention(
        q, k, v, None, True, 0, True).astype(F32).sum(), (0, 1, 2))
    text = str(jax.make_jaxpr(grad)(q, q, v))
    names = ("mxtpu_flash_fwd", "mxtpu_flash_bwd_fused") if arm.startswith("fused") \
        else ("mxtpu_flash_fwd", "mxtpu_flash_bwd_dq", "mxtpu_flash_bwd_dkv")
    for name in names:
        assert f"name={name}" in text
    pad = -(-length // 128) * 128
    assert f"bf16[2,{pad},128]" in text and f"bf16[2,{pad},256]" in text
    bq, bk = flash_mod._pick_blocks(length, length)
    # the forward's accumulator and the backward's dv sum, at v's width
    assert f"Ref<vmem>{{f32[{bq},128]}}" in text and f"Ref<vmem>{{f32[{bk},128]}}" in text
    dq, dk, dvg = jax.eval_shape(grad, q, q, v)
    assert (dq.shape[-1], dk.shape[-1], dvg.shape[-1]) == (256, 256, 128)


# ---- the op: q and k padded, v at its own width -------------------------------------------

@pytest.mark.parametrize("widths,pads", [((192, 128), 2), ((136, 16), 3), ((64, 128), 3)],
                         ids=["mla", "narrow_v", "phi"])
def test_the_op_pads_v_only_where_its_width_is_not_the_kernels(monkeypatch, widths, pads):
    """Latent attention's call pads q and k to 256 and passes v as it is; a v
    narrower than 128 is padded to 128 alone; Phi's (q.k 64 under v 128)
    pads all three to 128 as before. The output is v's width either way, and
    it and the gradients are the jnp fallback's."""
    d, dv = widths
    # 128 rows: one tile each way, so the kernel wrapper pads no rows
    q, k = (t * 0.3 for t in _normal(3, (1, 2, 128, d), (1, 2, 128, d)))
    v, = _normal(4, (1, 2, 128, dv))
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    text = str(jax.make_jaxpr(lambda q, k, v: op_impl_nn.flash_attention_op(
        q, k, v, causal=True))(q, k, v))
    assert text.count("name=_pad") == pads
    w, = _normal(5, (1, 2, 128, dv))

    def both():
        out = op_impl_nn.flash_attention_op(q, k, v, causal=True)
        grads = jax.grad(lambda q, k, v: (op_impl_nn.flash_attention_op(
            q, k, v, causal=True) * w).sum(), (0, 1, 2))(q, k, v)
        return out, grads
    got = both()
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET")
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda q, k, v: op_impl_nn.flash_attention_op(q, k, v, causal=True))(q, k, v))
    _close(got, both(), 1e-5)                        # the jnp fallback's


# ---- the other cells' op calls trace the parent's jaxprs -----------------------------------

# (q shape, k shape, v shape, causal, window, valid_len, pair mask): BERT's call
# (64 / 64, the rows' lengths), Phi's two (q.k 64 under v 128, grouped heads,
# the window and the full layer), Keye's (128 / 128, grouped, the pair mask)
_OP_CALLS = {
    "bert": ((2, 12, 512, 64), (2, 12, 512, 64), (2, 12, 512, 64), False, None, True, False),
    "phi_window": ((1, 20, 8192, 64), (1, 10, 8192, 64), (1, 10, 8192, 128), True, 512,
                   False, False),
    "phi_full": ((1, 20, 8192, 64), (1, 10, 8192, 64), (1, 10, 8192, 128), True, None,
                 False, False),
    "keye": ((1, 32, 8192, 128), (1, 4, 8192, 128), (1, 4, 8192, 128), True, None, False,
             True),
}

# sha256 of the jaxprs before the kernel took v's width, in interpret mode,
# bfloat16. BERT's calls are those jaxprs still; Phi's full layer and Keye's
# masked call were re-pinned when the kernel began to run a tile that its
# static masks leave wholly live without building them and a partly live one
# a 512-column chunk at a time (tests/test_flash_chunks.py), and those two and
# Phi's window call when the kernels began to walk a table of the tiles the
# static masks leave live (tests/test_flash_walk.py), their outputs and
# gradients the parent's bit for bit
_PARENT = {
    ("bert", "forward"): "76e523f4abe738791a22a359b75b96d9a561734397f09bb6be385d854ab20859",
    ("bert", "gradient"): "add6e79f79175e15edebffbeecfa87cef010e04a4761d74d300965898fb0003f",
    ("keye", "forward"): "4d3b8871ba1df1866f546974da1d7583005ad05f10b39830b55573f36af12ff4",
    ("keye", "gradient"): "dc79f56c1ffe395885db0b18b6be2c5afb0325cabd99d5fd31139c897a81eecd",
    ("phi_full", "forward"): "e236cf47b354e550cddee3df4dd00c20b243320d21c88569519cd3e897ac7657",
    ("phi_full", "gradient"): "ef8f4cf871e1f8eb71e8f7e6efcfa07c15f24d6c7e5e843c6ee5187599955735",
    ("phi_window", "forward"): "a27898c958b7876f92296de700634cbd01d29b731d6fa5b8d841f77ee74d996f",
    ("phi_window", "gradient"): "61bb231a94d9f0ce7257cda65029d4e1cdea9e7b6abae704e87e91e5242c8d08",
}


def _op_jaxpr(call, what):
    qs, ks, vs, causal, window, lens, mask = _OP_CALLS[call]
    extra = [jax.ShapeDtypeStruct((qs[0],), jnp.int32) if lens else None,
             jax.ShapeDtypeStruct((qs[0], qs[2], ks[2]), jnp.int8) if mask else None]

    def op(q, k, v, valid_len, pair_mask):
        return op_impl_nn.flash_attention_op(q, k, v, valid_len, causal=causal,
                                             window=window, pair_mask=pair_mask)
    f = op if what == "forward" else jax.grad(
        lambda *a: op(*a).astype(F32).sum(), (0, 1, 2))
    return str(jax.make_jaxpr(f)(*[jax.ShapeDtypeStruct(s, BF16) for s in (qs, ks, vs)],
                                 *extra))


@pytest.mark.parametrize("call,what", sorted(_PARENT))
def test_the_other_cells_op_calls_are_the_parents(monkeypatch, call, what):
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    text = _op_jaxpr(call, what)
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT[call, what]


# ---- the two tallies: on the build span and in profiler.counters() ------------------------

def _build_span_after(run):
    """``run()`` under a profiler session; the last build span's arguments and
    the counters' increments over the run."""
    from mxnet_tpu.profiler import _EVENTS
    before = mx.profiler.counters(device=False)
    mx.profiler.set_state("run")
    try:
        run()
    finally:
        mx.profiler.set_state("stop")
    after = mx.profiler.counters(device=False)
    span = [e["args"] for e in _EVENTS if e["name"] == "mxtpu/cachedop/build"][-1]
    grew = {k: after[k] - before[k] for k in ("flash_calls", "flash_two_width_calls")}
    return span, grew


def test_every_latent_attention_call_counts_at_two_widths_and_bert_at_one(monkeypatch):
    from mxnet_tpu.gluon.model_zoo import joyai_llm_flash
    from mxnet_tpu.gluon.nn.transformer import MultiHeadAttention
    from test_joyai_llm_flash_blocks import _TOY, _batch

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    # JoyAI's toy at a q.k of 128 + 8 (256 in the kernel) and a v of 128: two
    # main layers and the module's, three latent attention calls a pass
    mx.random.seed(0)
    model = joyai_llm_flash({**_TOY, "qk_nope_head_dim": 128, "v_head_dim": 128},
                            prefix="joyai_")
    model.initialize(init=mx.initializer.Normal(0.05), ctx=mx.cpu(0))
    model.hybridize()
    ids, labels = (mx.nd.array(a, dtype="int32") for a in _batch(1, length=16))

    def joyai():
        with autograd.record():
            loss = model(ids, labels)
        loss.backward()

    span, grew = _build_span_after(joyai)
    assert (span["flash_calls"], span["flash_two_width_calls"]) == (3, 3)
    # the backward's traces add calls, every one of them at two widths
    assert grew["flash_two_width_calls"] == grew["flash_calls"] > 3

    mx.random.seed(0)
    bert = MultiHeadAttention(128, 2, prefix="mha_")                # heads of 64
    bert.initialize(init=mx.initializer.Normal(0.05), ctx=mx.cpu(0))
    bert.hybridize()
    x = mx.nd.array(np.ones((2, 16, 128), "float32"))
    x.attach_grad()

    def mha():
        with autograd.record():
            out = bert(x)
        out.backward()

    span, grew = _build_span_after(mha)
    assert (span["flash_calls"], span["flash_two_width_calls"]) == (1, 0)
    assert grew["flash_calls"] > 1 and grew["flash_two_width_calls"] == 0
