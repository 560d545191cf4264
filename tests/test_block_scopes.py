"""The program names its device work (`gluon/block.py` `_block_scope`).

Inside a compiled program every Gluon block's call runs under
``jax.named_scope("mx.<BlockClass>")``; XLA keeps the name stack as each
instruction's ``op_name`` and the TPU profiler writes it into the trace,
where ``benchmark/chip/readers/device_scope_ms.py`` reads it. These tests
read the COMPILED programs of a recorded step (every program the step
compiles, caught at jax's compile call) on the CPU: names, not times.

What the HLO of this tree's path says, pinned here: on the Gluon path
(``jax.vjp`` over a jitted function called eagerly) the backward is a
second program with the forward's module name and the forward's paths, and
no ``transpose(`` inside (jax puts that on the call's equation); a step
compiled as ONE program (``functionalize`` + ``jax.grad`` under ``jit``)
carries ``transpose(jvp(mx.<Root>))``. Remat's rebuild carries
``rematted_computation`` on both.
"""
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


class Cell(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.fc = nn.Dense(16, flatten=False, in_units=16)

    def hybrid_forward(self, F, x):
        return F.tanh(self.fc(x))


class Net(gluon.HybridBlock):
    """A remat'd child, a plain child, and an operation of the root's own."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.cell = Cell()
            self.out = nn.Dense(4, flatten=False, in_units=16)

    def hybrid_forward(self, F, x):
        return self.out(self.cell(x)) * 2.0


class Experts(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.moe = nn.HeldExperts(16, 8, 4, 2, num_shared_experts=1,
                                      prefix="moe_")

    def hybrid_forward(self, F, x):
        return self.moe(x)


class Sparse(gluon.HybridBlock):
    def hybrid_forward(self, F, q):
        out, p_bar = F.dsa_attention(q, q, q, F.ones((1, 8, 8)))
        return F.sum(out) + F.sum(p_bar)


KINDS = {
    # kind: (block, input shape, components its forward program must carry
    # one after the other)
    "dense": (Net, (8, 16), ("mx.Net", "mx.Cell", "mx.Dense", "dot_general")),
    "experts": (Experts, (2, 8, 16),
                ("mx.Experts", "mx.HeldExperts", "mxtpu_moe", "mxtpu_moe_dense")),
    "shared_expert": (Experts, (2, 8, 16),
                      ("mx.Experts", "mx.HeldExperts", "mx.GatedMLP")),
    "sparse_attention": (Sparse, (1, 2, 8, 8),
                         ("mx.Sparse", "mxtpu_dsa_attn", "mxtpu_dsa_pbar")),
}


@pytest.fixture
def compiled(monkeypatch):
    """[(module name, compiled HLO text)] of every program compiled while
    the fixture lives."""
    from jax._src import compiler

    programs = []
    real = compiler.compile_or_get_cached

    def spy(backend, computation, devices, compile_options, *args, **kwargs):
        exe = real(backend, computation, devices, compile_options, *args, **kwargs)
        programs.append((computation.operation.attributes["sym_name"].value,
                         exe.hlo_modules()[0].to_string()))
        return exe

    monkeypatch.setattr(compiler, "compile_or_get_cached", spy)
    return programs


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _carries(names, components):
    """Whether one op_name has ``components`` in this order (whole
    components, others allowed between)."""
    for name in names:
        parts = iter(name.split("/"))
        if all(c in parts for c in components):
            return True
    return False


def _step(block_cls, shape, remat_child=True, steps=1):
    net = block_cls()
    net.initialize(init=mx.initializer.Normal(0.2))
    if remat_child and hasattr(net, "cell"):
        net.cell.hybridize(active=False, remat=True)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    x = nd.array(np.random.default_rng(0).normal(size=shape).astype("float32"))
    for _ in range(steps):
        with autograd.record():
            y = net(x).sum()
        y.backward()
        trainer.step(shape[0])
    return net, x


def _programs_named(compiled, name):
    return [text for n, text in compiled if n == name]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_forward_program_carries_the_block_path(compiled, kind):
    block_cls, shape, components = KINDS[kind]
    _step(block_cls, shape)
    programs = _programs_named(compiled, "jit_mxtpu_fwd_" + block_cls.__name__)
    # the forward, and the backward under the same module name
    assert len(programs) == 2, [n for n, _ in compiled]
    names = _op_names(programs[0])
    assert _carries(names, components), sorted(names)
    assert all(n.startswith(f"jit(mxtpu_fwd_{block_cls.__name__})/")
               for n in names if "mx." in n)


@pytest.mark.parametrize("marker", ["backward", "rebuild", "root"])
def test_backward_program_carries_the_paths_and_remats_marker(compiled, marker):
    _step(Net, (8, 16))
    forward, backward = map(_op_names, _programs_named(compiled, "jit_mxtpu_fwd_Net"))
    if marker == "backward":
        # the plain child's gradient products, under the forward's path; the
        # tree's vjp path puts no transpose( inside the program
        assert _carries(backward, ("mx.Net", "mx.Dense", "dot_general"))
        assert not any("transpose(" in n for n in forward | backward)
    elif marker == "rebuild":
        assert _carries(backward, ("mx.Net", "mx.Cell", "rematted_computation",
                                   "mx.Dense", "dot_general"))
        assert not any("rematted_computation" in n for n in forward)
    else:
        # what the root computes outside any child is named too
        assert "jit(mxtpu_fwd_Net)/mx.Net/mul" in forward


def test_fused_update_runs_under_its_scope_and_name(compiled):
    from mxnet_tpu.optimizer import optimizer

    optimizer._fused_update.clear_cache()   # an earlier test's same program
    _step(Net, (8, 16))
    (text,) = _programs_named(compiled, "jit_mxtpu_update")
    names = {n for n in _op_names(text) if "/" in n}
    assert names and all(n.startswith("jit(mxtpu_update)/mxtpu_update/")
                         for n in names), sorted(names)


def test_one_program_step_carries_jaxs_transpose_marker(compiled):
    net = Net()
    net.initialize(init=mx.initializer.Normal(0.2))
    net.cell.hybridize(active=False, remat=True)
    fn, params = gluon.block.functionalize(net, training=True)
    x = jnp.ones((8, 16), jnp.float32)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def step(p):
        return jax.grad(lambda p: fn(p, key, x).sum())(p)

    names = _op_names(step.lower(params).compile().as_text())
    assert _carries(names, ("jvp(mx.Net)", "mx.Dense", "dot_general")), names
    assert _carries(names, ("transpose(jvp(mx.Net))", "mx.Dense", "dot_general"))
    assert _carries(names, ("rematted_computation", "mx.Dense", "dot_general"))


@pytest.mark.parametrize("hybridized", [False, True])
def test_named_scope_is_entered_only_inside_a_trace(monkeypatch, hybridized):
    net = Net()
    net.initialize(init=mx.initializer.Normal(0.2))
    x = nd.ones((8, 16))
    if hybridized:
        net.hybridize()
        with autograd.record():
            net(x)      # the trace
    entered = []
    real = jax.named_scope

    def counting(name):
        entered.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", counting)
    with autograd.record():
        y = net(x).sum()
    y.backward()
    # an eager call names nothing; a compiled one is already traced
    assert entered == []


def _cache_key(lowered):
    """jax's persistent-cache key of a lowered program on this backend."""
    from jax._src import cache_key, compiler

    return cache_key.get(
        lowered.compiler_ir(), np.array(jax.devices()[:1]),
        compiler.get_compile_options(num_replicas=1, num_partitions=1),
        jax.devices()[0].client)


def test_the_programs_name_is_in_its_cache_key_and_a_scope_is_not():
    """Why a new scope must come with a new program name (or arithmetic):
    jax strips the op names before it hashes a module for the persistent
    cache, so a warm cache would serve the executable compiled before the
    scope existed, with the old names in every trace."""
    def body(x):
        return jnp.tanh(x) * 2.0

    def scoped_body(x):
        with jax.named_scope("mx.Dense"):
            return jnp.tanh(x) * 2.0

    x = jnp.ones((8, 16), jnp.float32)
    plain = jax.jit(body).lower(x)
    scoped = jax.jit(types.FunctionType(scoped_body.__code__, globals(), "body")
                     ).lower(x)
    assert "mx.Dense" in scoped.as_text(debug_info=True)
    assert _cache_key(plain) == _cache_key(scoped)


def test_a_blocks_program_does_not_share_the_old_names_cache_key():
    net = Net()
    net.initialize(init=mx.initializer.Normal(0.2))
    net.hybridize()
    x = nd.ones((8, 16))
    net(x)
    (entry,) = net._cached_graph.values()
    jitted = entry[0].fn
    new = jitted.__wrapped__
    assert new.__name__ == "mxtpu_fwd_Net"
    old = types.FunctionType(new.__code__, new.__globals__, "traced",
                             new.__defaults__, new.__closure__)
    old.__qualname__ = "traced"
    args = [jax.random.PRNGKey(0), x._data] + \
        [p.data()._data for p in net.collect_params().values()]
    lowered_new, lowered_old = jitted.lower(*args), jax.jit(old).lower(*args)
    assert "jit_mxtpu_fwd_Net" in lowered_new.as_text()
    assert "jit_traced" in lowered_old.as_text()
    assert _cache_key(lowered_new) != _cache_key(lowered_old)
