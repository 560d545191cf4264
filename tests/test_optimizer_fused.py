"""`Optimizer.update_multi`: one compiled program over the dense parameter
list for every optimizer that declares a ``dense_rule``, the per-key loop
for the rest, and the same results either way.

The compiled program runs the same op implementations
(ndarray/op_impl_optimizer.py) in the same order as the per-key loop's
eager dispatch, but as one XLA computation, whose CPU and TPU back ends may
contract a multiply and an add into one fused multiply-add. So the two
agree to rounding, not to the bit: the tolerances below were set from the
dtypes before the first comparison (float32: a few ulp over three steps,
with Ftrl's and centered RMSProp's cancelling subtractions in mind; a
bfloat16 array: one ulp, since a float32 master an ulp apart can round to
the neighbouring bfloat16).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, profiler
from mxnet_tpu import optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import sparse as sp
from mxnet_tpu.optimizer import optimizer as opt_mod

SHAPES = [(5, 7), (7,), (3, 2, 4)]
TOL = {"float32": dict(rtol=1e-4, atol=1e-6),
       "bfloat16": dict(rtol=2.0 ** -7, atol=2.0 ** -9)}

RULES = [                      # every optimizer that declares a rule
    ("sgd", {}),
    ("sgd", {"momentum": 0.9}),
    ("nag", {"momentum": 0.9}),
    ("adam", {}),
    ("adamw", {}),
    ("adagrad", {}),
    ("adadelta", {}),
    ("rmsprop", {}),
    ("rmsprop", {"centered": True, "clip_weights": 0.9}),
    ("ftrl", {}),
    ("signum", {"wd_lh": 0.01}),
    ("signum", {"momentum": 0.0}),     # signsgd_update
]
DTYPES = [("float32", False), ("bfloat16", True), ("bfloat16", False)]


def _rule_id(case):
    name, kw = case
    return name + "".join(f"-{k}" for k in kw)


def _make(name, kw, multi_precision=False, clip=None):
    """An optimizer with everything that moves between steps and between
    parameters: a schedule, an lr multiplier and a wd multiplier."""
    o = opt.create(name, multi_precision=multi_precision, clip_gradient=clip,
                   wd=0.01, learning_rate=0.05,
                   lr_scheduler=mx.lr_scheduler.FactorScheduler(
                       step=1, factor=0.7, base_lr=0.05), **kw)
    o.set_lr_mult({1: 0.5})
    o.set_wd_mult({2: 0.0})
    return o


def _flat(state):
    if state is None:
        return []
    if isinstance(state, (tuple, list)):
        return [a for s in state for a in _flat(s)]
    return [state]


def _three_steps(o, dtype, fused, grads=None):
    """Three steps over SHAPES with a batch size (``rescale_grad``, as
    `Trainer.step` sets it) that changes every step; ``fused``: through
    `Updater.update_multi`, else key by key through `Updater.__call__`.
    Returns the updater and every array it wrote, weights first."""
    rs = np.random.RandomState(7)
    up = opt.get_updater(o)
    ws = [nd.array(rs.randn(*s).astype(np.float32)).astype(dtype) for s in SHAPES]
    keys = list(range(len(SHAPES)))
    for step in range(3):
        gs = [nd.array(rs.randn(*s).astype(np.float32)).astype(dtype)
              for s in SHAPES]
        if grads is not None:
            gs = grads(gs)
        o.rescale_grad = 1.0 / (4 + step)
        if fused:
            up.update_multi(keys, gs, ws)
        else:
            for k in keys:
                up(k, gs[k], ws[k])
    return up, ws + [a for k in keys for a in _flat(up.states[k])]


@pytest.mark.parametrize("clip", [None, 0.3], ids=["noclip", "clip"])
@pytest.mark.parametrize("dtype, master", DTYPES,
                         ids=["f32", "bf16-master", "bf16"])
@pytest.mark.parametrize("case", RULES, ids=_rule_id)
def test_update_multi_equals_the_per_key_loop(case, dtype, master, clip):
    name, kw = case
    before = profiler.counters()
    _, got = _three_steps(_make(name, kw, master, clip), dtype, fused=True)
    after = profiler.counters()
    assert after["fused"] - before["fused"] == 3 * len(SHAPES)
    assert after["looped"] == before["looped"]
    _, want = _three_steps(_make(name, kw, master, clip), dtype, fused=False)
    assert len(got) == len(want) > len(SHAPES) - 1
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(g.asnumpy().astype(np.float32),
                                   w.asnumpy().astype(np.float32),
                                   **TOL[str(g.dtype)])


def _net_and_trainer(optimizer, params, dtype="float32"):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4), nn.Dense(2, in_units=8))
    net.initialize(init=mx.initializer.Xavier())
    if dtype != "float32":
        net.cast(dtype)
    return net, gluon.Trainer(net.collect_params(), optimizer, dict(params))


def _backward(net, seed, dtype="float32"):
    x = nd.array(np.random.RandomState(seed).randn(6, 4).astype(np.float32))
    with autograd.record():
        loss = (net(x.astype(dtype)) ** 2).sum()
    loss.backward()


TRAINERS = [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, "float32"),
    ("adamw", {"learning_rate": 0.01, "wd": 0.01, "multi_precision": True},
     "bfloat16"),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}, "float32"),
]
TRAINER_IDS = ["sgd-mom-f32", "adamw-master-bf16", "rmspropalex-f32"]


@pytest.mark.parametrize("optimizer, params, dtype", TRAINERS, ids=TRAINER_IDS)
def test_one_compile_and_one_invoke_a_step(optimizer, params, dtype):
    """Nothing that changes from step to step is baked in: over steps with
    a schedule and a new batch size each, `_fused_update` is compiled
    once, and each `Trainer.step` is one `invokes`."""
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.5, base_lr=0.1)
    net, trainer = _net_and_trainer(
        optimizer, dict(params, lr_scheduler=sched), dtype)
    built0 = opt_mod._fused_update._cache_size()
    for step in range(4):
        _backward(net, step, dtype)
        before = profiler.counters()
        trainer.step(4 + step)
        after = profiler.counters()
        assert after["invokes"] - before["invokes"] == 1
        assert after["fused"] - before["fused"] == 4
        assert after["looped"] == before["looped"]
    assert opt_mod._fused_update._cache_size() - built0 == 1


class MyAdam(opt.Adam):
    """A user's subclass that changes ``update`` and declares no rule of
    its own: Adam's rule is not what it computes any more."""

    def update(self, index, weight, grad, state):
        super().update(index, weight, grad * 0.5, state)


class RuleOnly(opt.Optimizer):
    """A user's optimizer that opts in: it declares the rule, no more."""

    def dense_rule(self, state):
        return "sgd_update", {}


def _row_sparse_second(gs):
    dense = gs[0].asnumpy()
    rows = np.array([0, 3], np.int64)
    return [sp.row_sparse_array((dense[rows], rows), shape=dense.shape)] + gs[1:]


FALLBACKS = [
    # name or class, constructor arguments, gradients, (fused, looped) a step
    ("sgd", {"momentum": 0.9}, _row_sparse_second, (2, 1)),
    ("adam", {}, _row_sparse_second, (2, 1)),
    ("lamb", {}, None, (0, 3)),
    ("lbsgd", {"momentum": 0.9}, None, (0, 3)),
    ("sgld", {}, None, (0, 3)),
    ("dcasgd", {"momentum": 0.9}, None, (0, 3)),
    ("test", {}, None, (0, 3)),
    (MyAdam, {}, None, (0, 3)),
    (RuleOnly, {}, None, (3, 0)),
]


@pytest.mark.parametrize(
    "which, kw, grads, counts", FALLBACKS,
    ids=["sgd-row_sparse", "adam-row_sparse", "lamb", "lbsgd", "sgld",
         "dcasgd", "test", "subclass-overrides-update", "declares-a-rule"])
def test_who_takes_the_loop_and_gets_todays_results(which, kw, grads, counts):
    """What the code can see decides, parameter by parameter: a row-sparse
    gradient, an optimizer with no rule and a subclass whose ``update`` is
    newer than the rule take the loop, which is the per-key path itself,
    so the results are the per-key path's to the bit."""
    def make():
        if isinstance(which, str):
            return opt.create(which, learning_rate=0.05, wd=0.01, **kw)
        return which(learning_rate=0.05, wd=0.01, **kw)

    mx.random.seed(11)                  # SGLD draws
    before = profiler.counters()
    _, got = _three_steps(make(), "float32", fused=True, grads=grads)
    after = profiler.counters()
    assert (after["fused"] - before["fused"],
            after["looped"] - before["looped"]) == tuple(3 * c for c in counts)
    mx.random.seed(11)
    _, want = _three_steps(make(), "float32", fused=False, grads=grads)
    exact = counts[0] == 0
    for g, w in zip(got, want):
        if exact:
            np.testing.assert_array_equal(g.asnumpy(), w.asnumpy())
        else:
            np.testing.assert_allclose(g.asnumpy(), w.asnumpy(),
                                       **TOL["float32"])


@pytest.mark.parametrize("optimizer, params, dtype", TRAINERS, ids=TRAINER_IDS)
def test_a_parameters_array_is_not_donated(optimizer, params, dtype):
    """The program donates what the optimizer made (moments, masters) and
    nothing else: an alias of a parameter's array, or of a gradient's,
    taken before three steps still reads its old value afterwards."""
    net, trainer = _net_and_trainer(optimizer, params, dtype)
    plist = list(net.collect_params().values())
    held = [p.data()._data for p in plist]
    old = [np.asarray(a.astype("float32")) for a in held]
    states = []
    for step in range(3):
        _backward(net, step, dtype)
        grads = [p.grad()._data for p in plist]
        trainer.step(6)
        # the moments and masters the step read are gone: donated
        assert all(s.is_deleted() for s in states)
        states = [a._data for st in trainer._updaters[0].states.values()
                  for a in _flat(st)]
        assert not any(g.is_deleted() for g in grads)
    for a, before, p in zip(held, old, plist):
        assert not a.is_deleted()
        np.testing.assert_array_equal(np.asarray(a.astype("float32")), before)
        assert not np.array_equal(p.data().asnumpy().astype(np.float32), before)
    # and the states it reads back next step are live (the donated ones
    # were replaced in the same NDArrays)
    assert states and not any(s.is_deleted() for s in states)


@pytest.mark.parametrize("optimizer, params, dtype", TRAINERS, ids=TRAINER_IDS)
def test_save_and_load_states_continue_bit_equal(optimizer, params, dtype, tmp_path):
    """`save_states` (which pickles the optimizer: ``dump_optimizer=True``)
    after a compiled step, `load_states` into a new Trainer: the next
    steps are those of the run that was never interrupted."""
    sched = lambda: mx.lr_scheduler.FactorScheduler(step=2, factor=0.5, base_lr=0.1)
    net_a, tr_a = _net_and_trainer(optimizer, dict(params, lr_scheduler=sched()), dtype)
    for step in range(2):
        _backward(net_a, step, dtype)
        tr_a.step(6)
    fname = str(tmp_path / "trainer.states")
    tr_a.save_states(fname)
    net_a.save_parameters(str(tmp_path / "net.params"))

    net_b, tr_b = _net_and_trainer(optimizer, dict(params, lr_scheduler=sched()), dtype)
    net_b.load_parameters(str(tmp_path / "net.params"))
    tr_b.load_states(fname)
    for step in range(2, 5):
        for net, tr in ((net_a, tr_a), (net_b, tr_b)):
            _backward(net, step, dtype)
            tr.step(6)
    assert tr_b.optimizer.num_update == tr_a.optimizer.num_update == 5
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        np.testing.assert_array_equal(pa.data().asnumpy(), pb.data().asnumpy())
    for k, st in tr_a._updaters[0].states.items():
        for a, b in zip(_flat(st), _flat(tr_b._updaters[0].states[k])):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())


def test_the_compiled_function_is_not_the_optimizers():
    """It lives in the module, so pickling an optimizer (the kvstore
    server's copy, ``get_states(dump_optimizer=True)``) never meets it."""
    import pickle

    o = _make("adam", {})
    _three_steps(o, "float32", fused=True)
    assert not any("jit" in type(v).__name__.lower() or callable(v)
                   for k, v in vars(o).items() if k != "lr_scheduler")
    clone = pickle.loads(pickle.dumps(o))
    assert clone.num_update == o.num_update == 3


def test_the_benchmarks_reader_sees_two_invokes_a_step(tmp_path):
    """The benchmark's own loop at rehearsal width, traced on the CPU and
    read by the benchmark's own reader: a step is two `register.invoke`s
    (the CachedOp and the compiled update), the counter and the
    ``mxtpu/op/*`` events agree (so `invokes_per_step.train` is reported),
    and all 37 parameters go through the one program."""
    import importlib
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    chip = os.path.join(root, "benchmark", "chip")
    sys.path[:0] = [chip, root]
    try:
        importlib.import_module("record_program_trace").main(
            out=str(tmp_path), cpu=True)
        program_spans = importlib.import_module("program_spans")
        reader = importlib.import_module("readers.invokes_per_step")
        pt = program_spans.load(str(tmp_path / "program.xplane.pb"), chips=1)
        assert reader.read({"program_trace": pt}) == 2.0
    finally:
        sys.path.remove(chip)
        sys.path.remove(root)
    updates = [s for s in pt.spans if s.name == "mxtpu/trainer/update"]
    assert len(updates) == 2
    for u in updates:
        assert (u.stats["params"], u.stats["fused"], u.stats["looped"],
                u.stats["invokes"]) == (37, 37, 0, 1)
    ops = [s.name for s in pt.spans if s.name.startswith("mxtpu/op/")]
    assert ops.count("mxtpu/op/fused_adamw_update") == 2 and len(ops) == 4
