"""Autograd (reference tests/python/unittest/test_autograd.py scope)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient


def test_simple_grad():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    y.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy())


def test_chain_rule():
    x = nd.array([[0.5, -0.5], [1.0, 2.0]])
    x.attach_grad()
    with autograd.record():
        y = nd.exp(x) * 2
        z = y.sum()
    z.backward()
    assert_almost_equal(x.grad, 2 * np.exp(x.asnumpy()), rtol=1e-5)


def test_grad_req_add():
    x = nd.array([1.0, 2.0])
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with autograd.record():
            y = (x * 2).sum()
        y.backward()
    assert_almost_equal(x.grad, np.full(2, 6.0, np.float32))


def test_grad_req_null():
    x = nd.array([1.0])
    x.attach_grad(grad_req="null")
    with autograd.record():
        y = x * 2
    y.backward()  # should not raise; no grad written


def test_backward_non_scalar_uses_ones():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
    y.backward()
    assert_almost_equal(x.grad, np.full(3, 3.0, np.float32))


def test_head_grad():
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    y.backward(nd.array([1.0, 10.0]))
    assert_almost_equal(x.grad, np.array([2.0, 40.0], np.float32))


def test_detach_stops_grad():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        z = y.detach() * x
    z.backward()
    # dz/dx through detach path only: z = const(4)*x -> grad 4... wait
    # y.detach() is constant 4; z = 4*x; dz/dx = 4
    assert_almost_equal(x.grad, np.array([4.0], np.float32))


def test_stop_gradient_op():
    x = nd.array([3.0])
    x.attach_grad()
    with autograd.record():
        y = nd.BlockGrad(x) * x
    y.backward()
    assert_almost_equal(x.grad, np.array([3.0], np.float32))


def test_pause_scope():
    x = nd.array([1.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        with autograd.pause():
            w = x * 100  # not recorded
        z = y + w.detach()
    z.backward()
    assert_almost_equal(x.grad, np.array([2.0], np.float32))


def test_is_training_flags():
    assert not autograd.is_training()
    assert not autograd.is_recording()
    with autograd.record():
        assert autograd.is_training()
        assert autograd.is_recording()
        with autograd.predict_mode():
            assert not autograd.is_training()
            assert autograd.is_recording()
    with autograd.train_mode():
        assert autograd.is_training()


def test_grad_function():
    x = nd.array([1.0, 2.0])
    with autograd.record():
        y = (x * x * x).sum()
    grads = autograd.grad([y], [x])
    assert_almost_equal(grads[0], 3 * x.asnumpy() ** 2)


def test_multi_input_op_grads():
    a = nd.array([1.0, 2.0])
    b = nd.array([3.0, 4.0])
    a.attach_grad()
    b.attach_grad()
    with autograd.record():
        c = (a * b).sum()
    c.backward()
    assert_almost_equal(a.grad, b.asnumpy())
    assert_almost_equal(b.grad, a.asnumpy())


def test_integer_input_no_grad():
    w = nd.array(np.random.rand(5, 3).astype(np.float32))
    idx = nd.array([0, 2], dtype="int32")
    w.attach_grad()
    with autograd.record():
        out = nd.Embedding(idx, w, input_dim=5, output_dim=3).sum()
    out.backward()
    expected = np.zeros((5, 3), np.float32)
    expected[[0, 2]] = 1
    assert_almost_equal(w.grad, expected)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_embedding_gradient_adds_repeated_rows(dtype):
    """Ids that repeat heavily: every occurrence's cotangent is ADDED
    into its table row (np.add.at), in the table's dtype. The
    cotangents are small integers, so the sums are exact in bfloat16
    too."""
    rng = np.random.RandomState(3)
    rows, dim = 6, 8
    ids = rng.randint(0, rows - 1, size=(4, 16))        # row 5 never looked up
    cot = rng.randint(0, 4, size=ids.shape + (dim,)).astype(np.float32)
    w = nd.array(rng.rand(rows, dim).astype(np.float32)).astype(dtype)
    w.attach_grad()
    with autograd.record():
        out = nd.Embedding(nd.array(ids, dtype="int32"), w,
                           input_dim=rows, output_dim=dim)
        loss = (out * nd.array(cot).astype(dtype)).sum()
    loss.backward()
    expected = np.zeros((rows, dim), np.float32)
    np.add.at(expected, ids, cot)
    assert expected.max() > 16 and not expected[rows - 1].any()
    assert str(w.grad.dtype) == dtype
    assert_almost_equal(w.grad.astype("float32"), expected, rtol=0, atol=0)


def test_retain_graph():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    y.backward(retain_graph=True)
    g1 = x.grad.asnumpy().copy()
    y.backward()
    assert_almost_equal(x.grad, g1)


def test_mark_variables():
    x = nd.array([1.0, 1.0])
    g = nd.zeros((2,))
    autograd.mark_variables([x], [g])
    with autograd.record():
        y = (x * 5).sum()
    y.backward()
    assert_almost_equal(g, np.full(2, 5.0, np.float32))


def test_custom_function():
    class Sigmoid(autograd.Function):
        def forward(self, x):
            y = 1 / (1 + nd.exp(-x))
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            y, = self.saved_tensors
            return dy * y * (1 - y)

    x = nd.array([0.0, 1.0])
    x.attach_grad()
    f = Sigmoid()
    with autograd.record():
        y = f(x)
    y.backward()
    s = 1 / (1 + np.exp(-x.asnumpy()))
    assert_almost_equal(x.grad, s * (1 - s), rtol=1e-5)


def test_numeric_gradient_matmul():
    check_numeric_gradient(
        lambda a, b: nd.dot(a, b),
        [np.random.rand(3, 4), np.random.rand(4, 2)],
        rtol=5e-2, atol=5e-3)


def test_sgd_update_inplace_during_record():
    """Optimizer writes must not corrupt earlier tape state (versioning)."""
    w = nd.array([1.0, 2.0])
    w.attach_grad()
    with autograd.record():
        loss = (w * w).sum()
    loss.backward()
    old_grad = w.grad.asnumpy().copy()
    # in-place update outside record
    nd.sgd_update(w, w.grad, lr=0.1, out=w)
    assert_almost_equal(w, np.array([1.0, 2.0]) - 0.1 * old_grad)


def test_multi_head_disjoint_backward():
    """`for l in losses: l.backward()` (the DP pattern): disjoint heads
    recorded in one scope each get a full, correct sweep."""
    x1 = nd.array([1.0, 2.0])
    x2 = nd.array([3.0, 4.0])
    x1.attach_grad()
    x2.attach_grad()
    with autograd.record():
        l1 = (x1 * x1).sum()
        l2 = (x2 * 3.0).sum()
    l1.backward()
    l2.backward()
    assert np.allclose(x1.grad.asnumpy(), [2.0, 4.0])
    assert np.allclose(x2.grad.asnumpy(), [3.0, 3.0])


def test_second_backward_through_freed_subgraph_raises():
    """Two heads SHARING a subgraph: the first non-retain backward frees
    the shared nodes; the second must raise (reference
    Imperative::Backward on released AGInfo) — never silently return a
    partial gradient."""
    import pytest
    from mxnet_tpu.base import MXNetError
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2.0
        l1 = (y * 3.0).sum()
        l2 = (y * 5.0).sum()
    l1.backward()
    with pytest.raises(MXNetError, match="already freed"):
        l2.backward()


def test_shared_subgraph_retain_graph():
    """retain_graph=True keeps the shared subgraph usable for the
    second head."""
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2.0
        l1 = (y * 3.0).sum()
        l2 = (y * 5.0).sum()
    l1.backward(retain_graph=True)
    assert np.allclose(x.grad.asnumpy(), [6.0, 6.0])
    l2.backward()
    assert np.allclose(x.grad.asnumpy(), [10.0, 10.0])
