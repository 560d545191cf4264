"""The short convolution's Pallas pair (``ops/pallas/conv1d.py``:
``mxtpu_conv1d_fwd`` / ``mxtpu_conv1d_bwd``) in interpret mode on the CPU,
against the twin (``F.causal_conv1d``'s own ``jax.numpy`` body): forward, dx,
dweight, dbias over taps, bias, activation, type and rows; what crosses a
token block's edge, forward and backward; the dispatch by shape with its two
tallies; the twin's jaxprs as the parent traced them.

The tiles are made small for these cases (two token blocks of two loop trips
of two passes at 128 tokens): interpret mode checks neither alignment nor
VMEM, which ``tests/test_chip_compile.py`` does at the real tiles."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray import op_impl_nn
from mxnet_tpu.ops.pallas import conv1d

F32, BF16 = jnp.float32, jnp.bfloat16
T_BLOCK, SUB, ROWS = 64, 32, 16


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(conv1d, "_T_BLOCKS", (T_BLOCK,))
    monkeypatch.setattr(conv1d, "_SUB", SUB)
    monkeypatch.setattr(conv1d, "_ROWS", ROWS)
    monkeypatch.setattr(conv1d, "_C_BLOCKS", (128,))


def _inputs(seed, rows, length, channels, taps, bias, dtype):
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(key[0], (rows, length, channels), F32).astype(dtype)
    w = (0.3 * jax.random.normal(key[1], (channels, taps), F32)).astype(dtype)
    b = (0.1 * jax.random.normal(key[2], (channels,), F32)).astype(dtype) \
        if bias else None
    dy = jax.random.normal(key[3], (rows, length, channels), F32).astype(dtype)
    return x, w, b, dy


def _with_grads(fn, x, w, b, dy):
    """(y, dx, dweight[, dbias]) of ``fn`` under the cotangent ``dy``."""
    def loss(x, w, b):
        return (fn(x, w, b).astype(F32) * dy.astype(F32)).sum()

    grads = jax.grad(loss, (0, 1) if b is None else (0, 1, 2))(x, w, b)
    return (fn(x, w, b),) + tuple(grads)


def _twin(activation):
    return lambda x, w, b: op_impl_nn._causal_conv1d_twin(
        x, w, b, activation=activation)


def _op(activation):
    return lambda x, w, b: op_impl_nn.causal_conv1d(x, w, b,
                                                    activation=activation)


@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("activation", ["silu", None])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("taps", [2, 4])
def test_the_kernel_pair_matches_the_twin(taps, bias, activation, dtype, rows):
    """y, dx, dweight and dbias through ``F.causal_conv1d``: 2 token blocks x
    2 channel blocks a row, each block 2 loop trips of 2 passes."""
    x, w, b, dy = _inputs(taps, rows, 2 * T_BLOCK, 256, taps, bias, dtype)
    before = mx.profiler.counters(device=False)
    got = _with_grads(_op(activation), x, w, b, dy)
    after = mx.profiler.counters(device=False)
    assert after["conv1d_kernel_calls"] > before["conv1d_kernel_calls"]
    want = _with_grads(_twin(activation), x, w, b, dy)
    # bfloat16: both round one float32 value, equal but for a sum's order
    tol = 1e-2 if dtype == BF16 else 2e-6
    for name, g, t in zip(("y", "dx", "dweight", "dbias"), got, want):
        assert g.dtype == t.dtype and g.shape == t.shape, name
        scale = float(jnp.abs(t.astype(F32)).max()) + 1e-9
        gap = float(jnp.abs(g.astype(F32) - t.astype(F32)).max()) / scale
        assert gap < tol, (name, gap)


@pytest.mark.parametrize("edge", [SUB, T_BLOCK], ids=["loop_trip", "grid_step"])
def test_an_impulse_crosses_a_blocks_edge_forward_and_backward(edge):
    """An impulse in the last row before an edge shows in the first K - 1
    rows after it, weighted by the taps; a cotangent in the first row after
    the edge flows back to the K - 1 rows before it."""
    taps, channels = 4, 128
    w = jnp.arange(1, taps + 1, dtype=F32)[None, :] * jnp.ones((channels, 1), F32)
    x = jnp.zeros((1, 2 * T_BLOCK, channels), F32).at[0, edge - 1].set(1.0)
    y = conv1d.causal_conv1d(x, w)
    want = np.zeros(2 * T_BLOCK)
    want[edge - 1:edge + taps - 1] = [4.0, 3.0, 2.0, 1.0]   # tap K-1 is the token's own
    np.testing.assert_allclose(np.asarray(y[0, :, 5]), want)
    dy = jnp.zeros_like(x).at[0, edge].set(1.0)
    dx, dw = jax.grad(lambda x, w: (conv1d.causal_conv1d(x, w) * dy).sum(),
                      (0, 1))(x, w)
    want = np.zeros(2 * T_BLOCK)
    want[edge - taps + 1:edge + 1] = [1.0, 2.0, 3.0, 4.0]
    np.testing.assert_allclose(np.asarray(dx[0, :, 5]), want)
    # the one product of x's impulse and dy's: the tap that reaches back one row
    np.testing.assert_allclose(np.asarray(dw[5]), [0.0, 0.0, 1.0, 0.0])


def test_a_second_row_starts_from_zeros_not_from_the_first_rows_tail():
    taps, channels = 4, 128
    w = jnp.ones((channels, taps), F32)
    x = jnp.ones((2, 2 * T_BLOCK, channels), F32)
    y = conv1d.causal_conv1d(x, w)
    for row in (0, 1):
        np.testing.assert_allclose(np.asarray(y[row, :taps, 0]), [1.0, 2.0, 3.0, 4.0])
    dx = jax.grad(lambda x: conv1d.causal_conv1d(x, w).sum())(x)
    for row in (0, 1):    # the last tokens have fewer tokens after them
        np.testing.assert_allclose(np.asarray(dx[row, -taps:, 0]), [4.0, 3.0, 2.0, 1.0])


@pytest.mark.parametrize("shape,dtype,taps,takes", [
    ((2, 2 * T_BLOCK, 256), BF16, 4, True),
    ((2, 100, 64), BF16, 4, False),             # neither tile divides
    ((2, 2 * T_BLOCK, 64), BF16, 4, False),     # a channel count under a lane group
    ((2, 100, 256), BF16, 4, False),            # a length no token block divides
    ((2, 2 * T_BLOCK, 256), jnp.float16, 4, False),
    ((2, 2 * T_BLOCK, 256), F32, conv1d.MAX_TAPS + 1, False),
], ids=["divides", "c64_s100", "c64", "s100", "float16", "nine_taps"])
def test_the_dispatch_reads_the_shape_and_tallies_both_forms(shape, dtype, taps,
                                                             takes):
    x = jnp.ones(shape, dtype)
    w = jnp.ones((shape[2], taps), dtype)
    before = mx.profiler.counters(device=False)
    text = str(jax.make_jaxpr(lambda x, w: op_impl_nn.causal_conv1d(
        x, w, activation="silu"))(x, w))
    after = mx.profiler.counters(device=False)
    assert after["conv1d_calls"] - before["conv1d_calls"] == 1
    assert after["conv1d_kernel_calls"] - before["conv1d_kernel_calls"] == takes
    assert ("mxtpu_conv1d_fwd" in text) == takes
    assert (conv1d.tiles(shape, taps, dtype) is not None) == takes


def test_off_the_chip_the_twin_runs(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET")
    x, w = jnp.ones((1, 2 * T_BLOCK, 256), BF16), jnp.ones((256, 4), BF16)
    before = mx.profiler.counters(device=False)
    text = str(jax.make_jaxpr(lambda x, w: op_impl_nn.causal_conv1d(x, w))(x, w))
    after = mx.profiler.counters(device=False)
    assert "pallas_call" not in text
    assert after["conv1d_calls"] - before["conv1d_calls"] == 1
    assert after["conv1d_kernel_calls"] == before["conv1d_kernel_calls"]


def test_an_unknown_activation_is_refused_in_both_forms():
    x, w = jnp.ones((1, 2 * T_BLOCK, 128), F32), jnp.ones((128, 4), F32)
    for channels in (128, 64):
        with pytest.raises(ValueError, match="unknown activation"):
            op_impl_nn.causal_conv1d(x[..., :channels], w[:channels],
                                     activation="gelu")


# the parent's (PR 35) jaxprs of the op at the cells' rehearsal widths: the
# twin's body and its `jax.checkpoint` wrapper are what they were
_PARENT = {
    ("kimi", "forward"): "ef025edfc54cda599f14beaa9fa500630b996983ea6db1898f685e50b16ac427",
    ("kimi", "gradient"): "8e9152411089a832133aa8ce3bbf1817c48d6c7d2a79b017b58c1bb9191c0b25",
    ("phi", "forward"): "9f418b4e64a919eeac745736c47507c0cf7602b69972f29b17f1680859943a2a",
    ("phi", "gradient"): "34813eb86f4c99ef0a3d2863e912bfec392e745836c62952a2a96c5dc1f365d3",
}


@pytest.mark.parametrize("cell,what", sorted(_PARENT))
def test_the_twins_jaxpr_at_toy_widths_is_the_parents(cell, what):
    """Kimi's rehearsal (no bias) and Phi's (bias): 2 rows of 64 tokens, 64
    channels, 4 taps, SiLU, bfloat16; the shape takes the twin."""
    x = jax.ShapeDtypeStruct((2, 64, 64), BF16)
    w = jax.ShapeDtypeStruct((64, 4), BF16)
    b = jax.ShapeDtypeStruct((64,), BF16)
    if cell == "phi":
        def fn(x, w, b):
            return op_impl_nn.causal_conv1d(x, w, b, activation="silu")
    else:
        def fn(x, w, b):
            return op_impl_nn.causal_conv1d(x, w, None, activation="silu")
    if what == "gradient":
        fn = jax.grad(lambda x, w, b, fn=fn: fn(x, w, b).astype(F32).sum(),
                      (0, 1, 2) if cell == "phi" else (0, 1))
    text = str(jax.make_jaxpr(fn)(x, w, b))
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT[cell, what]


def test_the_block_reaches_the_kernel_through_gluon():
    """`gluon.nn.CausalConv1D` under `autograd.record`: the gradients of a
    hybridized block in the two forms agree."""
    from mxnet_tpu import autograd, gluon

    def grads(form, monkeypatch):
        if form == "twin":
            monkeypatch.setattr(conv1d, "tiles", lambda *a: None)
        mx.random.seed(3)
        block = gluon.nn.CausalConv1D(128, 4, use_bias=True, prefix=f"{form}_")
        block.initialize(init=mx.initializer.Normal(0.3))
        block.hybridize()
        x = mx.nd.array(np.random.RandomState(0).randn(2, 2 * T_BLOCK, 128)
                        .astype("float32"))
        x.attach_grad()
        with autograd.record():
            loss = (block(x) ** 2).sum()
        loss.backward()
        return [x.grad.asnumpy()] + [p.grad().asnumpy() for _, p in
                                     sorted(block.collect_params().items())]

    with pytest.MonkeyPatch.context() as patch:
        before = mx.profiler.counters(device=False)["conv1d_kernel_calls"]
        kernel = grads("kernel", patch)
        assert mx.profiler.counters(device=False)["conv1d_kernel_calls"] > before
        twin = grads("twin", patch)
    for got, want in zip(kernel, twin):
        np.testing.assert_allclose(got, want, rtol=2e-5,
                                   atol=2e-5 * np.abs(want).max())
