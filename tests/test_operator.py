"""Per-operator forward/backward sweep — the `test_operator.py` of the
reference test strategy (SURVEY §4: "the largest file", per-op
forward + numeric-gradient + golden checks gate everything).

Organization:
- family tables map every registered op to at least one executed case
  (golden numpy reference where one is cheap to state, shape/validity
  otherwise);
- a numeric-gradient pass runs central finite differences vs autograd
  for a representative differentiable subset (check_numeric_gradient);
- `test_every_op_is_covered` asserts the union of the tables, the
  random-op statistical tests, the optimizer golden tests
  (test_optimizer_ops.py) and the explicit SKIP list covers the ENTIRE
  registry — adding an op without a test fails this suite.
"""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ndarray.register import _OPS, get_op, invoke
from mxnet_tpu.test_utils import (assert_almost_equal,
                                  check_numeric_gradient, device_tols)

# device-aware float32 tolerances: tight on CPU, widened on TPU where
# f32 matmuls ride bf16 MXU passes (reference per-device tol tables)
RTOL_F32, ATOL_F32 = device_tols("float32")
RTOL_L, ATOL_L = max(1e-3, RTOL_F32), max(1e-4, ATOL_F32)

RS = np.random.RandomState(42)


@pytest.fixture(autouse=True)
def _fresh_rs():
    """Deterministic inputs regardless of which subset of tests runs."""
    global RS
    RS = np.random.RandomState(42)
    yield


def _pos(shape):  # strictly positive floats
    return (RS.rand(*shape) + 0.5).astype(np.float32)


def _unit(shape):  # in (-0.9, 0.9) — safe for arc*/erfinv/arctanh
    return (RS.rand(*shape) * 1.8 - 0.9).astype(np.float32)


def _any(shape):
    return RS.randn(*shape).astype(np.float32)


def _np_erf(x):
    return np.vectorize(math.erf)(x).astype(np.float32)


def _np_gamma(x):
    return np.vectorize(math.gamma)(x).astype(np.float32)


def _np_gammaln(x):
    return np.vectorize(math.lgamma)(x).astype(np.float32)


# ---------------------------------------------------------------------------
# unary: (input generator, numpy reference)
# ---------------------------------------------------------------------------
UNARY = {
    "abs": (_any, np.abs),
    "exp": (_any, np.exp),
    "log": (_pos, np.log),
    "log2": (_pos, np.log2),
    "log10": (_pos, np.log10),
    "log1p": (_pos, np.log1p),
    "expm1": (_any, np.expm1),
    "sqrt": (_pos, np.sqrt),
    "rsqrt": (_pos, lambda x: 1.0 / np.sqrt(x)),
    "cbrt": (_any, np.cbrt),
    "rcbrt": (_pos, lambda x: 1.0 / np.cbrt(x)),
    "square": (_any, np.square),
    "reciprocal": (_pos, lambda x: 1.0 / x),
    "negative": (_any, np.negative),
    "sin": (_any, np.sin),
    "cos": (_any, np.cos),
    "tan": (_unit, np.tan),
    "arcsin": (_unit, np.arcsin),
    "arccos": (_unit, np.arccos),
    "arctan": (_any, np.arctan),
    "sinh": (_any, np.sinh),
    "cosh": (_any, np.cosh),
    "tanh": (_any, np.tanh),
    "arcsinh": (_any, np.arcsinh),
    "arccosh": (lambda s: _pos(s) + 1.0, np.arccosh),
    "arctanh": (_unit, np.arctanh),
    "sigmoid": (_any, lambda x: 1.0 / (1.0 + np.exp(-x))),
    "softsign": (_any, lambda x: x / (1.0 + np.abs(x))),
    "relu": (_any, lambda x: np.maximum(x, 0)),
    "gamma": (_pos, _np_gamma),
    "gammaln": (_pos, _np_gammaln),
    "erf": (_any, _np_erf),
    "degrees": (_any, np.degrees),
    "radians": (_any, np.radians),
    "identity": (_any, lambda x: x),
    "copy": (_any, lambda x: x),
    "BlockGrad": (_any, lambda x: x),
    "make_loss": (_any, lambda x: x),
    "MakeLoss": (_any, lambda x: x),
    "round": (_any, np.round),
    "rint": (_any, np.rint),
    "fix": (_any, np.trunc),
    "floor": (_any, np.floor),
    "ceil": (_any, np.ceil),
    "trunc": (_any, np.trunc),
    "sign": (_any, np.sign),
    "logical_not": (_any, lambda x: (~x.astype(bool)).astype(np.float32)),
    "isnan": (_any, np.isnan),
    "isinf": (_any, np.isinf),
    "isfinite": (_any, np.isfinite),
    "zeros_like": (_any, np.zeros_like),
    "ones_like": (_any, np.ones_like),
    "gelu": (_any, lambda x: x * 0.5 * (1.0 + _np_erf(x / np.sqrt(2.0)))),
    "swish": (_any, lambda x: x / (1.0 + np.exp(-x))),
    "log_sigmoid": (_any, lambda x: -np.log1p(np.exp(-x))),
    "mish": (_any, lambda x: x * np.tanh(np.log1p(np.exp(x)))),
    "softplus": (_any, lambda x: np.log1p(np.exp(x))),
    "hard_sigmoid": (_any, lambda x: np.clip(0.2 * x + 0.5, 0, 1)),
    "smooth_l1": (_any, lambda x: np.where(np.abs(x) < 1, 0.5 * x * x,
                                           np.abs(x) - 0.5)),
    "erfinv": (_unit, None),  # checked via erf(erfinv(x)) == x below
}


@pytest.mark.parametrize("name", sorted(UNARY))
def test_unary_forward(name):
    gen, ref = UNARY[name]
    x = gen((3, 4))
    out = getattr(nd, name)(nd.array(x)).asnumpy()
    if ref is None:
        return
    assert_almost_equal(out.astype(np.float32), ref(x).astype(np.float32),
                        rtol=RTOL_F32, atol=ATOL_F32)


def test_erfinv_inverts_erf():
    x = _unit((3, 4))
    y = nd.erfinv(nd.array(x))
    back = nd.erf(y).asnumpy()
    assert_almost_equal(back, x, rtol=RTOL_L, atol=ATOL_L)


# ---------------------------------------------------------------------------
# binary broadcast + scalar variants
# ---------------------------------------------------------------------------
BINARY = {
    "broadcast_add": np.add, "broadcast_sub": np.subtract,
    "broadcast_mul": np.multiply, "broadcast_div": np.divide,
    "broadcast_mod": np.mod, "broadcast_power": None,
    "broadcast_maximum": np.maximum, "broadcast_minimum": np.minimum,
    "broadcast_hypot": np.hypot,
    "broadcast_equal": lambda a, b: (a == b).astype(np.float32),
    "broadcast_not_equal": lambda a, b: (a != b).astype(np.float32),
    "broadcast_greater": lambda a, b: (a > b).astype(np.float32),
    "broadcast_greater_equal": lambda a, b: (a >= b).astype(np.float32),
    "broadcast_lesser": lambda a, b: (a < b).astype(np.float32),
    "broadcast_lesser_equal": lambda a, b: (a <= b).astype(np.float32),
    "broadcast_logical_and": lambda a, b: (a.astype(bool) & b.astype(bool)).astype(np.float32),
    "broadcast_logical_or": lambda a, b: (a.astype(bool) | b.astype(bool)).astype(np.float32),
    "broadcast_logical_xor": lambda a, b: (a.astype(bool) ^ b.astype(bool)).astype(np.float32),
    "arctan2": np.arctan2,
    "maximum": np.maximum, "minimum": np.minimum,
}


@pytest.mark.parametrize("name", sorted(BINARY))
def test_binary_broadcast_forward(name):
    ref = BINARY[name]
    a = _pos((3, 4))
    b = _pos((1, 4))  # broadcast across rows
    if ref is None:  # power: keep base positive, exponent small
        ref = np.power
        b = (RS.rand(1, 4) * 2).astype(np.float32)
    out = getattr(nd, name)(nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(out.astype(np.float32), ref(a, b).astype(np.float32),
                        rtol=RTOL_F32, atol=ATOL_F32)


SCALAR = {
    "broadcast_add_scalar": lambda x, s: x + s,
    "broadcast_sub_scalar": lambda x, s: x - s,
    "broadcast_mul_scalar": lambda x, s: x * s,
    "broadcast_div_scalar": lambda x, s: x / s,
    "broadcast_mod_scalar": lambda x, s: np.mod(x, s),
    "broadcast_power_scalar": lambda x, s: np.power(x, s),
    "broadcast_maximum_scalar": lambda x, s: np.maximum(x, s),
    "broadcast_minimum_scalar": lambda x, s: np.minimum(x, s),
    "broadcast_equal_scalar": lambda x, s: (x == s).astype(np.float32),
    "broadcast_not_equal_scalar": lambda x, s: (x != s).astype(np.float32),
    "broadcast_greater_scalar": lambda x, s: (x > s).astype(np.float32),
    "broadcast_greater_equal_scalar": lambda x, s: (x >= s).astype(np.float32),
    "broadcast_lesser_scalar": lambda x, s: (x < s).astype(np.float32),
    "broadcast_lesser_equal_scalar": lambda x, s: (x <= s).astype(np.float32),
    "_rdiv_scalar": lambda x, s: s / x,
    "_rminus_scalar": lambda x, s: s - x,
    "_rmod_scalar": lambda x, s: np.mod(s, x),
    "_rpower_scalar": lambda x, s: np.power(s, x),
}


@pytest.mark.parametrize("name", sorted(SCALAR))
def test_scalar_op_forward(name):
    ref = SCALAR[name]
    x = _pos((3, 4))
    s = 1.5
    out = invoke(get_op(name), [nd.array(x)], {"scalar": s}).asnumpy()
    assert_almost_equal(out.astype(np.float32), ref(x, s).astype(np.float32),
                        rtol=RTOL_F32, atol=ATOL_F32)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
REDUCE = {
    "sum": np.sum, "mean": np.mean, "max": np.max, "min": np.min,
    "prod": np.prod, "nansum": np.nansum, "nanprod": np.nanprod,
}


@pytest.mark.parametrize("name", sorted(REDUCE))
@pytest.mark.parametrize("axis,keepdims", [(None, False), (1, False), (1, True)])
def test_reduce_forward(name, axis, keepdims):
    ref = REDUCE[name]
    x = _pos((2, 3, 4)) * 0.9
    if name.startswith("nan"):
        x[0, 0, 0] = np.nan
    out = getattr(nd, name)(nd.array(x), axis=axis, keepdims=keepdims).asnumpy()
    want = ref(x, axis=axis, keepdims=keepdims)
    assert_almost_equal(np.asarray(out, np.float32).reshape(np.shape(want)),
                        np.asarray(want, np.float32), rtol=RTOL_F32, atol=ATOL_F32)


def test_norm_argmax_argmin():
    x = _any((3, 4))
    assert_almost_equal(nd.norm(nd.array(x)).asnumpy().reshape(()),
                        np.linalg.norm(x).astype(np.float32), rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.norm(nd.array(x), ord=1, axis=1).asnumpy(),
                        np.abs(x).sum(1), rtol=RTOL_F32, atol=ATOL_F32)
    assert (nd.argmax(nd.array(x), axis=1).asnumpy() == x.argmax(1)).all()
    assert (nd.argmin(nd.array(x), axis=1).asnumpy() == x.argmin(1)).all()
    x4 = _any((2, 3, 4))
    assert (nd.argmax_channel(nd.array(x4)).asnumpy() == x4.argmax(1).astype(np.float32)).all()


def test_l2_normalization():
    x = _any((3, 4))
    out = nd.L2Normalization(nd.array(x)).asnumpy()
    want = x / (np.sqrt((x ** 2).sum(axis=1, keepdims=True)) + 1e-10)
    assert_almost_equal(out, want, rtol=RTOL_F32, atol=ATOL_F32)


# ---------------------------------------------------------------------------
# shape / layout ops
# ---------------------------------------------------------------------------
def test_shape_ops():
    x = _any((2, 3, 4))
    a = nd.array(x)
    assert_almost_equal(nd.reshape(a, shape=(4, 6)).asnumpy(), x.reshape(4, 6))
    assert_almost_equal(nd.reshape_like(a, nd.zeros((4, 6))).asnumpy(), x.reshape(4, 6))
    assert (nd.shape_array(a).asnumpy() == [2, 3, 4]).all()
    assert int(nd.size_array(a).asnumpy()) == 24
    assert_almost_equal(nd.transpose(a, axes=(2, 0, 1)).asnumpy(), x.transpose(2, 0, 1))
    assert_almost_equal(nd.swapaxes(a, dim1=0, dim2=2).asnumpy(), x.swapaxes(0, 2))
    assert_almost_equal(nd.Flatten(a).asnumpy(), x.reshape(2, 12))
    assert_almost_equal(nd.expand_dims(a, axis=1).asnumpy(), x[:, None])
    assert_almost_equal(nd.squeeze(nd.expand_dims(a, axis=1)).asnumpy(), x)
    assert_almost_equal(nd.flip(a, axis=1).asnumpy(), x[:, ::-1])
    assert_almost_equal(nd.tile(a, reps=(2, 1, 1)).asnumpy(), np.tile(x, (2, 1, 1)))
    assert_almost_equal(nd.repeat(a, repeats=2, axis=1).asnumpy(), np.repeat(x, 2, 1))
    assert_almost_equal(nd.broadcast_to(nd.array(x[:1]), shape=(2, 3, 4)).asnumpy(),
                        np.broadcast_to(x[:1], (2, 3, 4)))
    assert_almost_equal(nd.broadcast_axis(nd.array(x[:1]), axis=0, size=2).asnumpy(),
                        np.broadcast_to(x[:1], (2, 3, 4)))
    assert_almost_equal(nd.broadcast_like(nd.array(x[:1]), a).asnumpy(),
                        np.broadcast_to(x[:1], (2, 3, 4)))
    assert_almost_equal(nd.Cast(a, dtype="float64").asnumpy(), x.astype(np.float64))
    assert_almost_equal(nd.amp_cast(a, dtype="float32").asnumpy(), x)
    assert_almost_equal(nd.clip(a, a_min=-0.5, a_max=0.5).asnumpy(),
                        np.clip(x, -0.5, 0.5))
    assert_almost_equal(nd.cumsum(a, axis=1).asnumpy(), np.cumsum(x, 1))


@pytest.mark.parametrize("lhs, rhs, ranges, want", [
    ((6, 5), (2, 3, 7), dict(lhs_begin=0, lhs_end=1, rhs_begin=0, rhs_end=-1),
     (2, 3, 5)),
    ((6, 5), (6, 9), dict(lhs_begin=0, lhs_end=1, rhs_begin=0, rhs_end=-1),
     (6, 5)),
    ((2, 12, 5), (3, 4), dict(lhs_begin=1, lhs_end=2), (2, 3, 4, 5)),
    ((30,), (7, 5, 6), dict(rhs_begin=-2), (5, 6)),
])
def test_reshape_like_over_ranges_of_axes(lhs, rhs, ranges, want):
    x = _any(lhs)
    out = nd.reshape_like(nd.array(x), nd.zeros(rhs), **ranges)
    assert out.shape == want
    assert_almost_equal(out.asnumpy(), x.reshape(want))


def test_pad_depth_space_diag():
    x = _any((2, 4, 3, 3))
    want = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)), constant_values=2.0)
    out = nd.pad(nd.array(x), mode="constant",
                 pad_width=(0, 0, 0, 0, 1, 1, 1, 1), constant_value=2.0)
    assert_almost_equal(out.asnumpy(), want)
    d2s = nd.depth_to_space(nd.array(x), block_size=2).asnumpy()
    assert d2s.shape == (2, 1, 6, 6)
    s2d = nd.space_to_depth(nd.array(d2s), block_size=2).asnumpy()
    assert_almost_equal(s2d, x)
    m = _any((3, 3))
    assert_almost_equal(nd.diag(nd.array(m)).asnumpy(), np.diag(m))
    v = _any((3,))
    assert_almost_equal(nd.diag(nd.array(v)).asnumpy(), np.diag(v))


def test_slice_family():
    x = _any((4, 5, 6))
    a = nd.array(x)
    assert_almost_equal(nd.slice(a, begin=(1, 0, 2), end=(3, 4, 6)).asnumpy(),
                        x[1:3, 0:4, 2:6])
    assert_almost_equal(nd.slice_axis(a, axis=1, begin=1, end=4).asnumpy(),
                        x[:, 1:4])
    assert_almost_equal(nd.slice_like(a, nd.zeros((2, 2, 2))).asnumpy(),
                        x[:2, :2, :2])
    got = invoke(get_op("_slice_get"), [a], {"key": (slice(0, 2),)})
    assert got.shape[0] == 2
    assert_almost_equal(got.asnumpy(), x[0:2])


def test_concat_stack_split():
    xs = [_any((2, 3)) for _ in range(3)]
    assert_almost_equal(nd.concat(*[nd.array(x) for x in xs], dim=1).asnumpy(),
                        np.concatenate(xs, 1))
    assert_almost_equal(nd.stack(*[nd.array(x) for x in xs], axis=0).asnumpy(),
                        np.stack(xs, 0))
    x = _any((2, 6))
    parts = nd.split(nd.array(x), num_outputs=3, axis=1)
    for i, p in enumerate(parts):
        assert_almost_equal(p.asnumpy(), x[:, 2 * i:2 * i + 2])
    parts = nd.split_v2(nd.array(x), indices_or_sections=(2, 5), axis=1)
    assert_almost_equal(parts[0].asnumpy(), x[:, :2])
    assert_almost_equal(parts[1].asnumpy(), x[:, 2:5])
    assert_almost_equal(parts[2].asnumpy(), x[:, 5:])


def test_init_like_ops():
    x = _any((3, 4))
    full = invoke(get_op("_full_like"), [nd.array(x)], {"value": 7.0})
    assert (full.asnumpy() == 7.0).all()
    ar = invoke(get_op("_arange_like"), [nd.array(x)], {"axis": 1})
    assert (ar.asnumpy() == np.arange(4, dtype=np.float32)).all()
    oh = nd.one_hot(nd.array(np.array([0, 2, 1], np.int32)), depth=3)
    assert_almost_equal(oh.asnumpy(), np.eye(3, dtype=np.float32)[[0, 2, 1]])
    w = nd.where(nd.array(np.array([1.0, 0.0, 1.0])), nd.array(np.array([1.0, 2.0, 3.0])),
                 nd.array(np.array([4.0, 5.0, 6.0])))
    assert (w.asnumpy() == [1.0, 5.0, 3.0]).all()
    assert_almost_equal(nd.add_n(nd.ones((2, 2)), nd.ones((2, 2)), nd.ones((2, 2))).asnumpy(),
                        np.full((2, 2), 3.0, np.float32))
    outs = invoke(get_op("amp_multicast"),
                  [nd.ones((2,)), nd.ones((2,))], {"num_outputs": 2})
    assert len(outs) == 2


# ---------------------------------------------------------------------------
# indexing / ordering
# ---------------------------------------------------------------------------
def test_indexing_ops():
    x = _any((5, 3))
    idx = np.array([0, 4, 2], np.int32)
    assert_almost_equal(nd.take(nd.array(x), nd.array(idx)).asnumpy(), x[idx])
    bt = nd.batch_take(nd.array(x), nd.array(np.array([0, 2, 1, 0, 2], np.int32)))
    assert_almost_equal(bt.asnumpy(), x[np.arange(5), [0, 2, 1, 0, 2]])
    pk = nd.pick(nd.array(x), nd.array(np.array([0, 2, 1, 0, 2], np.float32)), axis=1)
    assert_almost_equal(pk.asnumpy(), x[np.arange(5), [0, 2, 1, 0, 2]])
    gidx = np.array([[0, 1], [2, 0]], np.int32)  # (2 coords, 2 points)
    g = nd.gather_nd(nd.array(x), nd.array(gidx))
    assert_almost_equal(g.asnumpy(), x[[0, 1], [2, 0]])
    sc = invoke(get_op("scatter_nd"),
                [nd.array(np.array([9.0, 8.0], np.float32)), nd.array(gidx)],
                {"shape": (5, 3)})
    want = np.zeros((5, 3), np.float32)
    want[0, 2] = 9.0
    want[1, 0] = 8.0
    assert_almost_equal(sc.asnumpy(), want)
    emb = nd.Embedding(nd.array(idx), nd.array(x), input_dim=5, output_dim=3)
    assert_almost_equal(emb.asnumpy(), x[idx])


def test_ordering_ops():
    x = _any((4, 6))
    assert_almost_equal(nd.sort(nd.array(x), axis=1).asnumpy(), np.sort(x, 1))
    assert_almost_equal(nd.sort(nd.array(x), axis=1, is_ascend=False).asnumpy(),
                        -np.sort(-x, 1))
    assert (nd.argsort(nd.array(x), axis=1).asnumpy() == np.argsort(x, 1)).all()
    tk = nd.topk(nd.array(x), axis=1, k=2, ret_typ="value")
    assert_almost_equal(tk.asnumpy(), -np.sort(-x, 1)[:, :2])
    ti = nd.topk(nd.array(x), axis=1, k=2, ret_typ="indices")
    assert (ti.asnumpy().astype(int) == np.argsort(-x, 1)[:, :2]).all()


# ---------------------------------------------------------------------------
# linalg / matmul family
# ---------------------------------------------------------------------------
def test_matmul_family():
    a, b = _any((3, 4)), _any((4, 5))
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)).asnumpy(), a @ b,
                        rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.dot(nd.array(a.T), nd.array(b), transpose_a=True).asnumpy(),
                        a @ b, rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.matmul(nd.array(a), nd.array(b)).asnumpy(), a @ b,
                        rtol=RTOL_F32, atol=ATOL_F32)
    ba, bb = _any((2, 3, 4)), _any((2, 4, 5))
    assert_almost_equal(nd.batch_dot(nd.array(ba), nd.array(bb)).asnumpy(),
                        ba @ bb, rtol=RTOL_F32, atol=ATOL_F32)
    k = nd.khatri_rao(nd.array(_any((2, 3))), nd.array(_any((4, 3))))
    assert k.shape == (8, 3)


def test_linalg_ops():
    a, b, c = _any((3, 4)), _any((4, 5)), _any((3, 5))
    assert_almost_equal(
        nd.linalg_gemm(nd.array(a), nd.array(b), nd.array(c), alpha=2.0, beta=0.5).asnumpy(),
        2.0 * (a @ b) + 0.5 * c, rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.linalg_gemm2(nd.array(a), nd.array(b)).asnumpy(),
                        a @ b, rtol=RTOL_F32, atol=ATOL_F32)
    m = _any((3, 3))
    spd = m @ m.T + 3.0 * np.eye(3, dtype=np.float32)
    L = nd.linalg_potrf(nd.array(spd)).asnumpy()
    assert_almost_equal(L @ L.T, spd, rtol=RTOL_L, atol=ATOL_L)
    # trsm: solve L X = B
    B = _any((3, 2))
    X = nd.linalg_trsm(nd.array(L), nd.array(B)).asnumpy()
    assert_almost_equal(L @ X, B, rtol=RTOL_L, atol=ATOL_L)
    assert_almost_equal(
        nd.linalg_sumlogdiag(nd.array(spd)).asnumpy().reshape(()),
        np.log(np.diag(spd)).sum().astype(np.float32), rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.linalg_extractdiag(nd.array(spd)).asnumpy(), np.diag(spd))
    assert_almost_equal(nd.linalg_syrk(nd.array(a)).asnumpy(), a @ a.T,
                        rtol=RTOL_F32, atol=ATOL_F32)


# ---------------------------------------------------------------------------
# NN operators
# ---------------------------------------------------------------------------
def test_fully_connected():
    x, w, b = _any((4, 6)), _any((3, 6)), _any((3,))
    out = nd.FullyConnected(nd.array(x), nd.array(w), nd.array(b), num_hidden=3)
    assert_almost_equal(out.asnumpy(), x @ w.T + b, rtol=RTOL_F32, atol=ATOL_F32)


def test_convolution_1x1_golden():
    x, w = _any((2, 3, 5, 5)), _any((4, 3, 1, 1))
    out = nd.Convolution(nd.array(x), nd.array(w), kernel=(1, 1), num_filter=4,
                         no_bias=True)
    want = np.einsum("bchw,oc->bohw", x, w[:, :, 0, 0])
    assert_almost_equal(out.asnumpy(), want, rtol=RTOL_L, atol=ATOL_L)


def test_convolution_3x3_vs_manual():
    x, w = _any((1, 2, 4, 4)), _any((3, 2, 3, 3))
    b = _any((3,))
    out = nd.Convolution(nd.array(x), nd.array(w), nd.array(b), kernel=(3, 3),
                         num_filter=3, pad=(1, 1)).asnumpy()
    xp = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)))
    want = np.zeros((1, 3, 4, 4), np.float32)
    for o in range(3):
        for i in range(4):
            for j in range(4):
                want[0, o, i, j] = (xp[0, :, i:i + 3, j:j + 3] * w[o]).sum() + b[o]
    assert_almost_equal(out, want, rtol=RTOL_L, atol=ATOL_L)


def test_deconvolution_shape_and_grad_of_conv():
    x, w = _any((1, 2, 4, 4)), _any((2, 3, 2, 2))
    out = nd.Deconvolution(nd.array(x), nd.array(w), kernel=(2, 2), stride=(2, 2),
                           num_filter=3).asnumpy()
    assert out.shape == (1, 3, 8, 8)


def test_pooling_golden():
    x = _any((1, 2, 4, 4))
    mx_out = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2), pool_type="max").asnumpy()
    want = x.reshape(1, 2, 2, 2, 2, 2).max(axis=(3, 5))
    assert_almost_equal(mx_out, want)
    avg = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2), pool_type="avg").asnumpy()
    assert_almost_equal(avg, x.reshape(1, 2, 2, 2, 2, 2).mean(axis=(3, 5)),
                        rtol=RTOL_F32, atol=ATOL_F32)
    gp = nd.Pooling(nd.array(x), global_pool=True, pool_type="avg").asnumpy()
    assert_almost_equal(gp, x.mean(axis=(2, 3), keepdims=True), rtol=RTOL_F32, atol=ATOL_F32)


def test_upsampling():
    x = _any((1, 2, 3, 3))
    out = nd.UpSampling(nd.array(x), scale=2, sample_type="nearest").asnumpy()
    assert_almost_equal(out, np.repeat(np.repeat(x, 2, 2), 2, 3))


def test_activation_variants():
    x = _any((3, 4))
    for act, ref in [("relu", lambda v: np.maximum(v, 0)),
                     ("sigmoid", lambda v: 1 / (1 + np.exp(-v))),
                     ("tanh", np.tanh),
                     ("softrelu", lambda v: np.log1p(np.exp(v)))]:
        out = nd.Activation(nd.array(x), act_type=act).asnumpy()
        assert_almost_equal(out, ref(x).astype(np.float32), rtol=RTOL_F32, atol=ATOL_F32)
    lr = nd.LeakyReLU(nd.array(x), act_type="leaky", slope=0.1).asnumpy()
    assert_almost_equal(lr, np.where(x > 0, x, 0.1 * x), rtol=RTOL_F32, atol=ATOL_F32)
    el = nd.LeakyReLU(nd.array(x), act_type="elu", slope=1.0).asnumpy()
    assert_almost_equal(el, np.where(x > 0, x, np.exp(x) - 1), rtol=RTOL_F32, atol=ATOL_F32)


def _np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def test_softmax_family():
    x = _any((3, 5))
    assert_almost_equal(nd.softmax(nd.array(x)).asnumpy(), _np_softmax(x),
                        rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.log_softmax(nd.array(x)).asnumpy(),
                        np.log(_np_softmax(x)), rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.softmin(nd.array(x)).asnumpy(), _np_softmax(-x),
                        rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.SoftmaxActivation(nd.array(x)).asnumpy(),
                        _np_softmax(x), rtol=RTOL_F32, atol=ATOL_F32)
    assert_almost_equal(nd.SoftmaxOutput(nd.array(x), nd.array(np.zeros(3, np.float32))).asnumpy(),
                        _np_softmax(x), rtol=RTOL_F32, atol=ATOL_F32)
    lbl = np.array([1, 0, 4], np.float32)
    sce = nd.softmax_cross_entropy(nd.array(x), nd.array(lbl)).asnumpy()
    want = -np.log(_np_softmax(x))[np.arange(3), lbl.astype(int)].sum()
    assert_almost_equal(sce.reshape(()), np.float32(want), rtol=RTOL_F32, atol=ATOL_F32)


def test_attention_helper_ops():
    q, k, v = _any((2, 2, 3, 4)), _any((2, 2, 5, 4)), _any((2, 2, 5, 4))
    s = nd.batch_dot_attention_scores(nd.array(q), nd.array(k)).asnumpy()
    assert_almost_equal(s, np.einsum("bhqd,bhkd->bhqk", q, k),
                        rtol=RTOL_F32, atol=ATOL_F32)
    p = _np_softmax(s)
    o = nd.batch_dot_attention_apply(nd.array(p.astype(np.float32)), nd.array(v)).asnumpy()
    assert_almost_equal(o, np.einsum("bhqk,bhkd->bhqd", p, v), rtol=RTOL_F32, atol=ATOL_F32)
    sq = _any((2, 4, 4))
    masked = nd.causal_mask_scores(nd.array(sq)).asnumpy()
    iu = np.triu_indices(4, 1)
    assert (masked[:, iu[0], iu[1]] < -1e29).all()
    il = np.tril_indices(4)
    assert_almost_equal(masked[:, il[0], il[1]], sq[:, il[0], il[1]])


def test_flash_attention_vs_composed():
    q, k, v = _any((2, 2, 8, 4)), _any((2, 2, 8, 4)), _any((2, 2, 8, 4))
    out = nd.flash_attention(nd.array(q), nd.array(k), nd.array(v)).asnumpy()
    s = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(4.0)
    want = _np_softmax(s) @ v
    assert_almost_equal(out, want, rtol=RTOL_L, atol=ATOL_L)


def test_norm_layers_golden():
    x = _any((2, 3, 4))
    g, b = _pos((4,)), _any((4,))
    ln = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b)).asnumpy()
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    assert_almost_equal(ln, (x - mu) / np.sqrt(var + 1e-5) * g + b,
                        rtol=RTOL_L, atol=ATOL_L)

    x4 = _any((2, 4, 3, 3))
    g4, b4 = _pos((4,)), _any((4,))
    inn = nd.InstanceNorm(nd.array(x4), nd.array(g4), nd.array(b4)).asnumpy()
    mu = x4.mean((2, 3), keepdims=True)
    var = x4.var((2, 3), keepdims=True)
    assert_almost_equal(
        inn, (x4 - mu) / np.sqrt(var + 1e-3) * g4[None, :, None, None] + b4[None, :, None, None],
        rtol=1e-3, atol=1e-3)

    gn = nd.GroupNorm(nd.array(x4), nd.array(np.ones(4, np.float32)),
                      nd.array(np.zeros(4, np.float32)), num_groups=2).asnumpy()
    xg = x4.reshape(2, 2, 2, 3, 3)
    mu = xg.mean((2, 3, 4), keepdims=True)
    var = xg.var((2, 3, 4), keepdims=True)
    assert_almost_equal(gn, ((xg - mu) / np.sqrt(var + 1e-5)).reshape(x4.shape),
                        rtol=1e-3, atol=1e-3)


def test_batchnorm_train_and_inference():
    x = _any((4, 3, 2, 2))
    g, b = _pos((3,)), _any((3,))
    mm, mv = np.zeros(3, np.float32), np.ones(3, np.float32)
    with mx.autograd.record(train_mode=True):  # batch-stats path
        out = nd.BatchNorm(nd.array(x), nd.array(g), nd.array(b),
                           nd.array(mm.copy()), nd.array(mv.copy()),
                           fix_gamma=False)
    mu = x.mean((0, 2, 3))
    var = x.var((0, 2, 3))
    want = ((x - mu[None, :, None, None]) / np.sqrt(var[None, :, None, None] + 1e-5)
            * g[None, :, None, None] + b[None, :, None, None])
    assert_almost_equal(out.asnumpy(), want, rtol=1e-3, atol=1e-3)
    # inference path uses the moving stats
    infer = nd.BatchNorm(nd.array(x), nd.array(g), nd.array(b),
                         nd.array(mm), nd.array(mv), use_global_stats=True,
                         fix_gamma=False)
    want_inf = x * g[None, :, None, None] + b[None, :, None, None]
    assert_almost_equal(infer.asnumpy(), want_inf, rtol=1e-3, atol=1e-3)


def test_dropout_modes():
    x = _pos((50, 50))
    mx.random.seed(5)
    with mx.autograd.record(train_mode=True):
        y = nd.Dropout(nd.array(x), p=0.5)
    kept = (y.asnumpy() != 0)
    assert 0.3 < kept.mean() < 0.7
    assert_almost_equal(y.asnumpy()[kept], (x * 2.0)[kept], rtol=RTOL_F32, atol=ATOL_F32)
    y_eval = nd.Dropout(nd.array(x), p=0.5)  # predict mode: identity
    assert_almost_equal(y_eval.asnumpy(), x)


def test_sequence_ops():
    x = _any((4, 2, 3))  # (seq, batch, feat)
    slen = np.array([2, 4], np.float32)
    m = nd.SequenceMask(nd.array(x), nd.array(slen), use_sequence_length=True,
                        value=-1.0).asnumpy()
    assert (m[2:, 0] == -1.0).all() and (m[:, 1] == x[:, 1]).all()
    last = nd.SequenceLast(nd.array(x), nd.array(slen), use_sequence_length=True).asnumpy()
    assert_almost_equal(last[0], x[1, 0])
    assert_almost_equal(last[1], x[3, 1])
    rev = nd.SequenceReverse(nd.array(x), nd.array(slen), use_sequence_length=True).asnumpy()
    assert_almost_equal(rev[0, 0], x[1, 0])
    assert_almost_equal(rev[:, 1], x[::-1, 1])


def test_regression_outputs():
    x, y = _any((3, 4)), _any((3, 4))
    assert_almost_equal(nd.LinearRegressionOutput(nd.array(x), nd.array(y)).asnumpy(), x)
    assert_almost_equal(nd.MAERegressionOutput(nd.array(x), nd.array(y)).asnumpy(), x)
    assert_almost_equal(nd.LogisticRegressionOutput(nd.array(x), nd.array(y)).asnumpy(),
                        1 / (1 + np.exp(-x)), rtol=RTOL_F32, atol=ATOL_F32)


def test_bilinear_sampler_identity_grid():
    x = _any((1, 2, 4, 4))
    ys, xs = np.meshgrid(np.linspace(-1, 1, 4), np.linspace(-1, 1, 4), indexing="ij")
    grid = np.stack([xs, ys])[None].astype(np.float32)  # (1, 2, H, W)
    out = nd.BilinearSampler(nd.array(x), nd.array(grid)).asnumpy()
    assert_almost_equal(out, x, rtol=1e-4, atol=1e-4)


def test_rnn_op_forward_shapes():
    """Fused RNN op smoke (deep coverage lives in tests/test_gluon.py's
    rnn_layer/rnn_cell golden tests)."""
    from mxnet_tpu.gluon import rnn
    layer = rnn.LSTM(5, num_layers=1, layout="NTC")
    layer.initialize()
    x = nd.array(_any((2, 3, 4)))
    out = layer(x)
    assert out.shape == (2, 3, 5)
    assert np.isfinite(out.asnumpy()).all()


# ---------------------------------------------------------------------------
# numeric gradient sweep (representative differentiable subset)
# ---------------------------------------------------------------------------
GRAD_UNARY = ["exp", "log", "sqrt", "square", "sigmoid", "tanh", "relu",
              "sin", "cosh", "arctan", "reciprocal", "softsign", "gelu",
              "swish", "mish", "softplus", "smooth_l1", "erf"]


@pytest.mark.parametrize("name", GRAD_UNARY)
def test_unary_numeric_grad(name):
    gen = UNARY[name][0]
    check_numeric_gradient(lambda a: getattr(nd, name)(a), [gen((3, 4))])


@pytest.mark.parametrize("name", ["broadcast_add", "broadcast_mul",
                                  "broadcast_div", "broadcast_sub",
                                  "broadcast_maximum", "arctan2"])
def test_binary_numeric_grad(name):
    check_numeric_gradient(lambda a, b: getattr(nd, name)(a, b),
                           [_pos((3, 4)), _pos((3, 1))])


@pytest.mark.parametrize("case", [
    ("sum", {"axis": 1}), ("mean", {}), ("max", {"axis": 1}),
    ("min", {}), ("prod", {"axis": 0}), ("norm", {}),
])
def test_reduce_numeric_grad(case):
    name, kw = case
    check_numeric_gradient(lambda a: getattr(nd, name)(a, **kw), [_pos((3, 4))])


def test_nn_numeric_grads():
    # explicit tolerances are authoritative on every backend, so widen
    # them here for the real chip (bf16-MXU finite differences)
    from mxnet_tpu.test_utils import _on_tpu
    wide = dict(rtol=5e-2, atol=5e-3) if _on_tpu() else \
        dict(rtol=2e-2, atol=2e-3)
    check_numeric_gradient(
        lambda x, w, b: nd.FullyConnected(x, w, b, num_hidden=3),
        [_any((3, 4)), _any((3, 4)), _any((3,))])
    check_numeric_gradient(
        lambda x, w: nd.Convolution(x, w, kernel=(3, 3), num_filter=2,
                                    pad=(1, 1), no_bias=True),
        [_any((1, 2, 4, 4)), _any((2, 2, 3, 3))], **wide)
    check_numeric_gradient(lambda x: nd.Pooling(x, kernel=(2, 2), stride=(2, 2),
                                                pool_type="avg"),
                           [_any((1, 1, 4, 4))])
    check_numeric_gradient(lambda x: nd.softmax(x), [_any((3, 5))])
    check_numeric_gradient(lambda x: nd.log_softmax(x), [_any((3, 5))])
    check_numeric_gradient(
        lambda x, g, b: nd.LayerNorm(x, g, b),
        [_any((2, 6)), _pos((6,)), _any((6,))], **wide)
    check_numeric_gradient(lambda a, b: nd.dot(a, b), [_any((3, 4)), _any((4, 2))])
    check_numeric_gradient(lambda a, b: nd.batch_dot(a, b),
                           [_any((2, 3, 4)), _any((2, 4, 2))])
    check_numeric_gradient(lambda x: nd.take(x, nd.array(np.array([0, 2], np.int32))),
                           [_any((4, 3))])


# ---------------------------------------------------------------------------
# random ops: shapes + determinism + crude moments
# ---------------------------------------------------------------------------
def test_random_ops_statistics():
    mx.random.seed(9)
    u = nd.random_uniform(low=0.0, high=1.0, shape=(2000,)).asnumpy()
    assert 0.45 < u.mean() < 0.55 and u.min() >= 0.0 and u.max() <= 1.0
    n = nd.random_normal(loc=0.0, scale=1.0, shape=(2000,)).asnumpy()
    assert abs(n.mean()) < 0.1 and 0.9 < n.std() < 1.1
    g = nd.random_gamma(alpha=2.0, beta=1.0, shape=(2000,)).asnumpy()
    assert g.min() > 0 and 1.6 < g.mean() < 2.4
    e = nd.random_exponential(lam=2.0, shape=(2000,)).asnumpy()
    assert e.min() >= 0 and 0.4 < e.mean() < 0.6
    p = nd.random_poisson(lam=3.0, shape=(2000,)).asnumpy()
    assert 2.7 < p.mean() < 3.3
    nb = nd.random_negative_binomial(k=2, p=0.5, shape=(2000,)).asnumpy()
    assert nb.min() >= 0
    ri = nd.random_randint(low=0, high=10, shape=(2000,)).asnumpy()
    assert ri.min() >= 0 and ri.max() <= 9
    b = nd.bernoulli(prob=0.3, shape=(2000,)).asnumpy()
    assert 0.2 < b.mean() < 0.4
    mx.random.seed(9)
    u2 = nd.random_uniform(low=0.0, high=1.0, shape=(2000,)).asnumpy()
    assert (u == u2).all()  # seeding is deterministic


def test_sample_ops():
    mu = nd.array(np.array([0.0, 10.0], np.float32))
    sg = nd.array(np.array([1.0, 1.0], np.float32))
    s = nd.sample_normal(mu, sg, shape=(500,)).asnumpy()
    assert s.shape == (2, 500)
    assert abs(s[0].mean()) < 0.3 and abs(s[1].mean() - 10.0) < 0.3
    su = nd.sample_uniform(nd.array(np.array([0.0], np.float32)),
                           nd.array(np.array([1.0], np.float32)), shape=(500,)).asnumpy()
    assert su.min() >= 0 and su.max() <= 1
    sgam = nd.sample_gamma(nd.array(np.array([2.0], np.float32)),
                           nd.array(np.array([1.0], np.float32)), shape=(500,)).asnumpy()
    assert sgam.min() > 0
    probs = nd.array(np.array([[0.7, 0.2, 0.1]], np.float32))
    sm = nd.sample_multinomial(probs, shape=(1000,)).asnumpy()
    assert (np.bincount(sm.reshape(-1).astype(int), minlength=3)[0] > 500)
    x = np.arange(10, dtype=np.float32)
    sh = nd.shuffle(nd.array(x)).asnumpy()
    assert sorted(sh.tolist()) == x.tolist()


# ---------------------------------------------------------------------------
# registry coverage gate
# ---------------------------------------------------------------------------
# ops exercised by OTHER dedicated test files or modules
def test_op_invocation_recording_works():
    """The coverage gate is RECORDED now (conftest pytest_sessionfinish
    gates a full run on the ops actually dispatched — VERDICT r2 weak
    #8 replaced the hand-maintained trust list). This test checks the
    recording machinery itself on both dispatch paths."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.ndarray import register as reg

    seen = set()
    prev = reg._INVOCATION_RECORD
    reg.record_invocations(seen)
    try:
        nd.array([1.0, 2.0]) + nd.array([3.0, 4.0])  # eager
        x = mx.sym.Variable("x")
        y = mx.sym.sqrt(x)
        e = y.bind(mx.current_context(), {"x": nd.array([4.0])})
        e.forward()  # symbolic executor
    finally:
        reg.record_invocations(prev)
        if prev is not None:
            prev |= seen
    assert "broadcast_add" in seen, seen
    assert "sqrt" in seen, seen

# ---------------------------------------------------------------------------
# cross-dtype consistency (SURVEY §4: check_consistency is the
# cpu-vs-backend golden gate; here f32 vs bf16 on the default backend —
# under MXNET_TPU_TEST_REAL_DEVICE=1 the same cases run on the chip)
# ---------------------------------------------------------------------------
from mxnet_tpu.test_utils import check_consistency


def _consistency_ctx_list():
    # default_context() resolves to the REAL chip under
    # MXNET_TPU_TEST_REAL_DEVICE=1 and to cpu on the virtual mesh — so
    # the same cases are the cpu golden run and the on-chip run
    from mxnet_tpu.test_utils import default_context
    ctx = default_context()
    return [{"ctx": ctx, "dtype": "float32"},
            {"ctx": ctx, "dtype": "bfloat16"}]


@pytest.mark.parametrize("case", [
    ("fc", lambda x, w: nd.FullyConnected(x, w, None, num_hidden=4,
                                          no_bias=True),
     [(3, 6), (4, 6)], None),
    ("conv", lambda x, w: nd.Convolution(x, w, kernel=(3, 3), num_filter=2,
                                         pad=(1, 1), no_bias=True),
     [(1, 2, 6, 6), (2, 2, 3, 3)], None),
    # sum-loss makes softmax/normalization grads ~0: the comparison is
    # absolute-error dominated, so bf16 needs a looser atol
    ("softmax", lambda x: nd.softmax(x), [(4, 7)], 5e-3),
    ("layernorm", lambda x, g, b: nd.LayerNorm(x, g, b),
     [(3, 8), (8,), (8,)], 2e-2),
    ("tanh_chain", lambda x: nd.tanh(nd.exp(x) * 0.3), [(4, 5)], None),
    ("lrn", lambda x: nd.LRN(x, nsize=3), [(1, 5, 4, 4)], None),
])
def test_check_consistency_f32_vs_bf16(case):
    name, fn, shapes, atol = case
    inputs = [RS.randn(*s).astype(np.float32) * 0.5 for s in shapes]
    check_consistency(fn, _consistency_ctx_list(), inputs, atol=atol)


def test_check_consistency_stn_forward():
    """STN forward f32 vs bf16 with the whole grid path in the leg's
    dtype. FORWARD ONLY: bilinear-sampling gradients bucket by pixel
    boundary, so a bf16 grid coordinate that rounds across a boundary
    legitimately changes the gradient — grad comparison is
    ill-conditioned for this op by construction."""
    x = RS.randn(2, 2, 4, 4).astype(np.float32) * 0.5
    t = RS.randn(2, 6).astype(np.float32) * 0.5

    def fn(x, t):
        ident = nd.Cast(nd.array(np.array([1, 0, 0, 0, 1, 0], np.float32)),
                        dtype=str(t.dtype))
        return nd.SpatialTransformer(x, nd.broadcast_add(t * 0.1, ident),
                                     target_shape=(4, 4))

    check_consistency(fn, _consistency_ctx_list(), [x, t], atol=2e-2,
                      grad_check=False)


# ---------------------------------------------------------------------------
# round-3 op additions: LRN / ROI pooling / STN family / ravel / digamma
# ---------------------------------------------------------------------------
def test_lrn_golden():
    """LRN vs naive channel-window loop (reference src/operator/nn/lrn.cc)."""
    rng = np.random.RandomState(0)
    x = rng.randn(2, 7, 3, 3).astype(np.float32)
    nsize, alpha, beta, k = 5, 1e-4, 0.75, 2.0
    half = nsize // 2
    ref = np.empty_like(x)
    for c in range(7):
        lo, hi = max(0, c - half), min(7, c + half + 1)
        s = (x[:, lo:hi] ** 2).sum(1)
        ref[:, c] = x[:, c] / (k + alpha / nsize * s) ** beta
    got = nd.LRN(nd.array(x), nsize=nsize, alpha=alpha, beta=beta,
                 knorm=k).asnumpy()
    assert_almost_equal(got, ref, rtol=1e-5, atol=1e-6)
    check_numeric_gradient(
        lambda a: nd.LRN(a, nsize=3, alpha=1e-3, beta=0.5, knorm=1.0),
        [rng.randn(1, 4, 2, 2).astype(np.float32)])


def test_roi_pooling_golden():
    """ROIPooling vs naive bin loop (reference src/operator/roi_pooling.cc)."""
    rng = np.random.RandomState(1)
    x = rng.randn(1, 2, 8, 8).astype(np.float32)
    rois = np.array([[0, 0, 0, 7, 7], [0, 2, 2, 5, 5], [0, 6, 6, 7, 7]],
                    np.float32)
    got = nd.ROIPooling(nd.array(x), nd.array(rois), pooled_size=(2, 2),
                        spatial_scale=1.0).asnumpy()
    for r, roi in enumerate(rois):
        b, x1, y1, x2, y2 = (int(round(v)) for v in roi)
        rh, rw = max(y2 - y1 + 1, 1), max(x2 - x1 + 1, 1)
        for i in range(2):
            for j in range(2):
                hs = y1 + int(np.floor(i * rh / 2))
                he = max(y1 + int(np.ceil((i + 1) * rh / 2)), hs + 1)
                ws = x1 + int(np.floor(j * rw / 2))
                we = max(x1 + int(np.ceil((j + 1) * rw / 2)), ws + 1)
                ref = x[b, :, max(hs, 0):min(he, 8),
                        max(ws, 0):min(we, 8)].max(axis=(1, 2))
                assert_almost_equal(got[r, :, i, j], ref)
    # spatial_scale: rois in image coords, features downscaled 2x
    got2 = nd.ROIPooling(nd.array(x), nd.array(np.array([[0, 0, 0, 15, 15]],
                                                        np.float32)),
                         pooled_size=(1, 1), spatial_scale=0.5).asnumpy()
    assert_almost_equal(got2[0, :, 0, 0], x[0].max(axis=(1, 2)))


def test_spatial_transformer_and_grid_generator():
    """Identity affine reproduces the input; warp with zero flow is the
    identity grid; gradients flow to the localization input (reference
    src/operator/spatial_transformer.cc)."""
    rng = np.random.RandomState(2)
    x = rng.randn(2, 3, 5, 6).astype(np.float32)
    theta = np.tile(np.array([1, 0, 0, 0, 1, 0], np.float32), (2, 1))
    out = nd.SpatialTransformer(nd.array(x), nd.array(theta),
                                target_shape=(5, 6)).asnumpy()
    assert_almost_equal(out, x, rtol=1e-4, atol=1e-5)

    g = nd.GridGenerator(nd.array(np.zeros((1, 2, 4, 4), np.float32)),
                         transform_type="warp").asnumpy()
    assert np.allclose(g[0, 0, :, 0], -1) and np.allclose(g[0, 0, :, -1], 1)
    assert np.allclose(g[0, 1, 0, :], -1) and np.allclose(g[0, 1, -1, :], 1)

    from mxnet_tpu import autograd
    a = nd.array(theta)
    a.attach_grad()
    with autograd.record():
        y = nd.SpatialTransformer(nd.array(x), a, target_shape=(5, 6))
        s = (y * y).sum()
    s.backward()
    assert np.isfinite(a.grad.asnumpy()).all()
    assert np.abs(a.grad.asnumpy()).sum() > 0


def test_ravel_unravel_and_digamma():
    """ravel.cc pair round-trips; digamma matches scipy-free goldens."""
    flat = nd.array(np.array([5, 11, 0], np.int64))
    u = nd.unravel_index(flat, shape=(3, 4))
    assert u.asnumpy().tolist() == [[1, 2, 0], [1, 3, 0]]
    r = nd.ravel_multi_index(u, shape=(3, 4))
    assert r.asnumpy().tolist() == [5, 11, 0]
    d = nd.digamma(nd.array(np.array([1.0, 0.5, 2.0], np.float32))).asnumpy()
    # psi(1) = -gamma, psi(1/2) = -gamma - 2 ln 2, psi(2) = 1 - gamma
    eg = 0.5772156649
    assert_almost_equal(d, np.array([-eg, -eg - 2 * np.log(2), 1 - eg],
                                    np.float32), rtol=1e-4, atol=1e-5)


def test_batch_norm_train_fused_matches_composed():
    """BatchNormTrain (fused 2-pass fwd / hand-written 2-pass VJP) vs
    the composed mean/centered-var/normalize graph: outputs, batch
    stats, and dx/dgamma/dbeta must agree (reference batch_norm.cc
    training path semantics)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ndarray.op_impl_nn import _bn_train_core
    z16 = jnp.zeros(16, jnp.float32)

    rng = np.random.RandomState(42)
    x = jnp.asarray(rng.randn(8, 16, 9, 7).astype(np.float32)) * 2.0 + 0.7
    g = jnp.asarray(rng.rand(16).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(16).astype(np.float32))
    eps = 1e-5

    def composed(x, g, b):
        mean = x.mean((0, 2, 3))
        diff = x - mean.reshape(1, -1, 1, 1)
        var = (diff * diff).mean((0, 2, 3))
        out = diff * jax.lax.rsqrt(var.reshape(1, -1, 1, 1) + eps) \
            * g.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)
        return out, mean, var

    out, mean, var = _bn_train_core(x, g, b, z16, eps, 1, False)
    ro, rm, rv = composed(x, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(rm), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(var), np.asarray(rv), rtol=2e-5, atol=2e-5)

    w = jnp.asarray(rng.randn(8, 16, 9, 7).astype(np.float32))
    gf = jax.grad(lambda x, g, b: (_bn_train_core(x, g, b, z16, eps, 1, False)[0] * w).sum(),
                  argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(lambda x, g, b: (composed(x, g, b)[0] * w).sum(),
                  argnums=(0, 1, 2))(x, g, b)
    for a, c in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=3e-4, atol=3e-4)

    # fix_gamma: gamma ignored (ones) and its grad is exactly zero
    out_fg, _, _ = _bn_train_core(x, g, b, z16, eps, 1, True)
    ro_fg, _, _ = composed(x, jnp.ones_like(g), b)
    np.testing.assert_allclose(np.asarray(out_fg), np.asarray(ro_fg),
                               rtol=2e-5, atol=2e-5)
    dg = jax.grad(lambda g: (_bn_train_core(x, g, b, z16, eps, 1, True)[0] * w).sum())(g)
    assert np.all(np.asarray(dg) == 0.0)

    # external cotangents on the stat outputs flow (mean/var feed the
    # running-stat EMA when not stop-gradiented)
    dm = jax.grad(lambda x: _bn_train_core(x, g, b, z16, eps, 1, False)[1].sum())(x)
    np.testing.assert_allclose(np.asarray(dm),
                               np.full(x.shape, 1.0 / (8 * 9 * 7)), rtol=1e-6)

    # the stat shift is an exact identity: any per-channel shift gives
    # the same stats/output (it exists to re-center the one-pass
    # variance; the layer passes the running mean)
    shift = jnp.asarray(rng.randn(16).astype(np.float32)) * 10
    o2, m2, v2 = _bn_train_core(x, g, b, shift, eps, 1, False)
    # identity holds in real arithmetic; f32 rounding differs by ~1e-4
    np.testing.assert_allclose(np.asarray(o2), np.asarray(out), rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(mean), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(var), rtol=1e-3,
                               atol=1e-4)

    # cancellation guard: |mean| >> std breaks the unshifted one-pass
    # E[x^2]-E[x]^2 variance (f32), but a mean-scale shift keeps it
    # accurate — the running mean provides exactly this in steady state
    big = jnp.asarray((rng.randn(8, 16, 9, 7) * 0.01 + 3000.0)
                      .astype(np.float32))
    true_var = np.var(np.asarray(big, np.float64), axis=(0, 2, 3))
    _, _, v_shift = _bn_train_core(big, g, b,
                                   jnp.full(16, 3000.0, jnp.float32),
                                   eps, 1, False)
    np.testing.assert_allclose(np.asarray(v_shift), true_var, rtol=5e-3)
    _, _, v_noshift = _bn_train_core(big, g, b, z16, eps, 1, False)
    assert not np.allclose(np.asarray(v_noshift), true_var, rtol=5e-2), \
        "unshifted variance unexpectedly survived cancellation"


def test_batch_norm_layer_train_vs_eval_running_stats():
    """Gluon BatchNorm: training uses fused batch stats and updates the
    EMA; predict mode uses the running stats."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    rng = np.random.RandomState(3)
    bn = gluon.nn.BatchNorm(momentum=0.5)
    bn.initialize()
    x = nd.array(rng.randn(4, 5, 6, 6).astype(np.float32) * 3 + 1)
    with autograd.record():
        out = bn(x)
        out.backward()
    xm = x.asnumpy().mean((0, 2, 3))
    xv = x.asnumpy().var((0, 2, 3))
    got = out.asnumpy()
    want = (x.asnumpy() - xm.reshape(1, -1, 1, 1)) / np.sqrt(
        xv.reshape(1, -1, 1, 1) + 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(bn.running_mean.data().asnumpy(), 0.5 * xm,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(bn.running_var.data().asnumpy(),
                               0.5 * 1.0 + 0.5 * xv, rtol=1e-4, atol=1e-4)
    # predict mode: running stats, not batch stats
    out_eval = bn(x).asnumpy()
    rm = bn.running_mean.data().asnumpy()
    rv = bn.running_var.data().asnumpy()
    want_eval = (x.asnumpy() - rm.reshape(1, -1, 1, 1)) / np.sqrt(
        rv.reshape(1, -1, 1, 1) + 1e-5)
    np.testing.assert_allclose(out_eval, want_eval, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Op-breadth tail (VERDICT r3 #6): linalg potri/trmm/makediag/maketrian/
# extracttrian, im2col/col2im, registered ctc_loss, contrib.boolean_mask
# ---------------------------------------------------------------------------

def test_linalg_potri_trmm():
    rs = np.random.RandomState(0)
    m = rs.rand(4, 4).astype(np.float32)
    spd = m @ m.T + 4 * np.eye(4, dtype=np.float32)
    L = nd.linalg_potrf(nd.array(spd))
    inv = nd.linalg_potri(L)
    np.testing.assert_allclose(inv.asnumpy(), np.linalg.inv(spd),
                               rtol=1e-3, atol=1e-4)
    B = nd.array(rs.rand(4, 3).astype(np.float32))
    out = nd.linalg_trmm(L, B, alpha=2.0)
    # device tolerances: on TPU these matmuls ride bf16 MXU passes
    np.testing.assert_allclose(out.asnumpy(),
                               2.0 * np.tril(L.asnumpy()) @ B.asnumpy(),
                               rtol=RTOL_F32, atol=ATOL_F32)
    # rightside + transpose
    B2 = nd.array(rs.rand(3, 4).astype(np.float32))
    out2 = nd.linalg_trmm(L, B2, rightside=True, transpose=True)
    np.testing.assert_allclose(out2.asnumpy(),
                               B2.asnumpy() @ np.tril(L.asnumpy()).T,
                               rtol=RTOL_F32, atol=ATOL_F32)


def test_linalg_makediag_maketrian_roundtrip():
    rs = np.random.RandomState(1)
    v = rs.rand(2, 5).astype(np.float32)
    d = nd.linalg_makediag(nd.array(v))
    assert d.shape == (2, 5, 5)
    np.testing.assert_allclose(d.asnumpy()[1], np.diag(v[1]), rtol=1e-6)
    d1 = nd.linalg_makediag(nd.array(v), offset=1)
    assert d1.shape == (2, 6, 6)
    np.testing.assert_allclose(d1.asnumpy()[0], np.diag(v[0], k=1),
                               rtol=1e-6)

    m = rs.rand(3, 4, 4).astype(np.float32)
    packed = nd.linalg_extracttrian(nd.array(m))
    assert packed.shape == (3, 10)
    rows, cols = np.tril_indices(4)
    np.testing.assert_allclose(packed.asnumpy(), m[:, rows, cols],
                               rtol=1e-6)
    back = nd.linalg_maketrian(packed)
    np.testing.assert_allclose(back.asnumpy(), np.tril(m), rtol=1e-6)
    # upper triangle with offset
    up = nd.linalg_extracttrian(nd.array(m), offset=1, lower=False)
    assert up.shape == (3, 6)
    back_up = nd.linalg_maketrian(up, offset=1, lower=False)
    np.testing.assert_allclose(back_up.asnumpy(), np.triu(m, k=1),
                               rtol=1e-6)


def test_linalg_tail_numeric_grad():
    from mxnet_tpu.test_utils import check_numeric_gradient
    rs = np.random.RandomState(2)
    L = np.tril(rs.rand(3, 3).astype(np.float32)) + 2 * np.eye(3, dtype=np.float32)
    check_numeric_gradient(lambda a: nd.linalg_potri(a).sum(), [L])
    B = nd.array(rs.rand(3, 2).astype(np.float32))
    check_numeric_gradient(lambda a: nd.linalg_trmm(a, B).sum(), [L])
    check_numeric_gradient(lambda v: nd.linalg_maketrian(v).sum(),
                           [rs.rand(6).astype(np.float32)])


def test_im2col_col2im():
    rs = np.random.RandomState(3)
    x = rs.rand(2, 3, 6, 7).astype(np.float32)
    cols = nd.im2col(nd.array(x), kernel=(3, 2), stride=(2, 1),
                     dilate=(1, 1), pad=(1, 0))
    oh = (6 + 2 - 3) // 2 + 1
    ow = (7 - 2) // 1 + 1
    assert cols.shape == (2, 3 * 3 * 2, oh * ow)
    # golden: manual window extraction, channel-major then (ki, kj)
    xp = np.pad(x, ((0, 0), (0, 0), (1, 1), (0, 0)))
    got = cols.asnumpy().reshape(2, 3, 3, 2, oh, ow)
    for ki in range(3):
        for kj in range(2):
            want = xp[:, :, ki:ki + 2 * (oh - 1) + 1:2,
                      kj:kj + (ow - 1) + 1:1]
            np.testing.assert_allclose(got[:, :, ki, kj], want, rtol=1e-6)

    # col2im is im2col's adjoint: <col2im(c), x> == <c, im2col(x)>
    c = rs.rand(2, 18, oh * ow).astype(np.float32)
    back = nd.col2im(nd.array(c), output_size=(6, 7), kernel=(3, 2),
                     stride=(2, 1), dilate=(1, 1), pad=(1, 0))
    lhs = float((back.asnumpy() * x).sum())
    rhs = float((c * cols.asnumpy()).sum())
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4)


def test_ctc_loss_registered_op():
    rs = np.random.RandomState(4)
    T, N, C, L = 10, 3, 6, 4
    data = rs.randn(T, N, C).astype(np.float32)
    labels_first = np.array([[1, 2, 3, 0], [2, 2, 0, 0], [5, 4, 3, 2]],
                            np.float32)  # 0 = padding (blank reserved)
    out = nd.ctc_loss(nd.array(data), nd.array(labels_first))
    assert out.shape == (N,)
    assert np.all(out.asnumpy() > 0)

    # blank_label='last' maps onto the same math: rolled alphabet +
    # shifted labels must give identical losses
    data_last = np.concatenate([data[..., 1:], data[..., :1]], axis=-1)
    labels_last = np.where(labels_first > 0, labels_first - 1, -1)
    out_last = nd.ctc_loss(nd.array(data_last), nd.array(labels_last),
                           blank_label="last")
    np.testing.assert_allclose(out_last.asnumpy(), out.asnumpy(),
                               rtol=1e-5, atol=1e-5)

    # gradient flows
    d = nd.array(data)
    d.attach_grad()
    with mx.autograd.record():
        loss = nd.ctc_loss(d, nd.array(labels_first)).sum()
    loss.backward()
    g = d.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_contrib_boolean_mask():
    rs = np.random.RandomState(5)
    x = nd.array(rs.rand(6, 4).astype(np.float32))
    mask = nd.array(np.array([1, 0, 1, 1, 0, 1], np.float32))
    out = nd.contrib.boolean_mask(x, mask)
    np.testing.assert_allclose(out.asnumpy(),
                               x.asnumpy()[[0, 2, 3, 5]], rtol=1e-6)
    # axis=1
    m2 = nd.array(np.array([0, 1, 1, 0], np.float32))
    out2 = nd.contrib.boolean_mask(x, m2, axis=1)
    np.testing.assert_allclose(out2.asnumpy(), x.asnumpy()[:, [1, 2]],
                               rtol=1e-6)
    # gradients scatter back through take's VJP
    x.attach_grad()
    with mx.autograd.record():
        y = nd.contrib.boolean_mask(x, mask)
        y.sum().backward()
    g = x.grad.asnumpy()
    np.testing.assert_allclose(g[[0, 2, 3, 5]], 1.0)
    np.testing.assert_allclose(g[[1, 4]], 0.0)
