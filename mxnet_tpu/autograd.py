"""Define-by-run autograd.

Analog of the reference's ``python/mxnet/autograd.py`` frontend and the
C++ ``Imperative`` tape (src/imperative/imperative.cc:
``Imperative::RecordOp`` / ``Imperative::Backward``). The reference
records invoked ops as nnvm nodes and, at ``backward()``, runs the nnvm
``Gradient`` pass then executes the backward graph through the engine.

TPU-native design: each recorded op is executed through ``jax.vjp`` at
dispatch time (ndarray/register.py), so the tape stores ready-made
pullback closures whose residuals are device-resident jax.Arrays —
forward runs once, backward is a reverse sweep calling pullbacks and
accumulating cotangents. This replaces the Gradient-pass-over-nnvm-graph
machinery with JAX's native VJP while keeping MXNet's user contract:

    with autograd.record():
        out = net(x)
        loss = loss_fn(out, y)
    loss.backward()          # leaf.grad populated per grad_req

Versioned values: in-place NDArray mutation rebinds ``_data`` and bumps
``_version`` (the engine-variable version analog), so tape values are
keyed ``(id(ndarray), version)`` — a mutation after recording creates a
distinct value node and cannot corrupt earlier gradients.
"""
from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Optional

import jax
import jax.numpy as jnp

from . import profiler as _profiler
from .base import MXNetError

__all__ = [
    "record",
    "pause",
    "train_mode",
    "predict_mode",
    "is_recording",
    "is_training",
    "mark_variables",
    "backward",
    "grad",
    "set_recording",
    "set_training",
    "Function",
]


class _TapeNode:
    __slots__ = ("op_name", "in_keys", "in_arrays", "out_keys", "out_refs",
                 "vjp_fn", "raw_multi", "n_raw_out", "out_shapes")

    def __init__(self, op_name, in_keys, in_arrays, out_keys, out_refs,
                 vjp_fn, raw_multi, n_raw_out, out_shapes):
        self.op_name = op_name
        self.in_keys = in_keys        # [(key, ndarray-or-None), ...] aligned w/ vjp positionals
        self.in_arrays = in_arrays    # NDArray refs (leaves need .grad writes)
        self.out_keys = out_keys
        self.out_refs = out_refs      # weakrefs to output NDArrays: a node
        # whose outputs were ALL collected is unreachable (consumers hold
        # strong input refs) — pruned at the next backward; this also
        # prevents a recycled id() from colliding with a stale out_key.
        self.vjp_fn = vjp_fn
        self.raw_multi = raw_multi
        self.n_raw_out = n_raw_out
        self.out_shapes = out_shapes  # [(shape, dtype)] of raw outputs


class _AutogradState(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False
        self.tape: list[_TapeNode] = []


_STATE = _AutogradState()


def is_recording() -> bool:
    return _STATE.recording


def is_training() -> bool:
    return _STATE.training


def set_recording(flag: bool) -> bool:
    prev, _STATE.recording = _STATE.recording, flag
    return prev


def set_training(flag: bool) -> bool:
    prev, _STATE.training = _STATE.training, flag
    return prev


class _Scope:
    def __init__(self, recording: Optional[bool], training: Optional[bool]):
        self._rec, self._train = recording, training

    def __enter__(self):
        if self._rec is not None:
            self._prev_rec = set_recording(self._rec)
        if self._train is not None:
            self._prev_train = set_training(self._train)
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            set_recording(self._prev_rec)
        if self._train is not None:
            set_training(self._prev_train)
        return False


def record(train_mode: bool = True):
    """Scope: record ops for autograd (and set train mode)."""
    return _Scope(True, train_mode)


def pause(train_mode: bool = False):
    """Scope: stop recording (e.g. for parameter updates)."""
    return _Scope(False, train_mode)


def train_mode():
    return _Scope(None, True)


def predict_mode():
    return _Scope(None, False)


def _key(nd):
    return (id(nd), nd._version)


def _record_op(op, inputs, outputs, vjp_fn, raw_multi, n_raw_out,
               raw_avals=None, in_keys=None):
    """Called by register.invoke for every differentiable op under record().

    ``in_keys`` are the (id, version) pairs snapshotted BEFORE any
    in-place write-back of the same dispatch (out=/mutates), so the tape
    references the values the op actually read."""
    from .ndarray.ndarray import NDArray

    if in_keys is None:
        in_keys = [_key(x) if isinstance(x, NDArray) else None for x in inputs]
    in_arrays = []
    for x in inputs:
        if isinstance(x, NDArray):
            in_arrays.append(x)
            x._in_graph = True
        else:
            in_arrays.append(None)
    out_keys = []
    out_refs = []
    for o in outputs:
        o._in_graph = True
        out_keys.append(_key(o))
        out_refs.append(weakref.ref(o))
    # raw outputs may exceed visible outputs (e.g. BatchNorm aux); vjp
    # needs cotangents for all of them — remember avals for zero-fill.
    _STATE.tape.append(
        _TapeNode(op.name, in_keys, in_arrays, out_keys, out_refs, vjp_fn,
                  raw_multi, n_raw_out, raw_avals)
    )


def mark_variables(variables, gradients, grad_reqs="write"):
    """Attach gradient buffers to arrays (MXAutogradMarkVariables)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = req
        v._is_leaf = True


def _ones_like(a):
    with _on_device_of([a]):
        return jnp.ones(a.shape, a.dtype)


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Run the reverse sweep from `heads`; leaf ``.grad`` is populated.

    Mirrors MXAutogradBackwardEx semantics: default head gradient is
    ones; grad_req 'write' overwrites, 'add' accumulates, 'null' skips.
    """
    with _profiler.span("mxtpu/autograd/backward") as sp:
        sp.set(nodes=_sweep(heads, head_grads, retain_graph))


def _sweep(heads, head_grads, retain_graph):
    """`backward`'s body; returns the number of tape nodes whose
    pullback ran."""
    from .ndarray.ndarray import NDArray

    if isinstance(heads, NDArray):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, NDArray):
        head_grads = [head_grads]

    # cotangent accumulator keyed by (id, version)
    cot: dict = {}
    for h, hg in zip(heads, head_grads):
        g = _ones_like(h._data) if hg is None else hg._data
        k = _key(h)
        cot[k] = cot[k] + g if k in cot else g

    tape = _STATE.tape
    # a head no tape node produced yields all-zero gradients — the
    # reference's documented no-op for unrecorded graphs, but ALSO the
    # classic silent footgun (loss.sum() OUTSIDE record() drops the
    # reduction off the tape). Keep the no-op semantics, but say so.
    taped_keys = {k for node in tape for k in node.out_keys}
    for h in heads:
        if _key(h) not in taped_keys:
            import warnings
            warnings.warn(
                "backward() head was not computed inside autograd."
                "record() (or was mutated since); gradients will not "
                "flow through it — did you call .sum() on the loss "
                "AFTER the record block?", stacklevel=3)
    touched_leaves = []
    leaf_slots: dict = {}  # id(leaf) → set of tape value-keys it fed
    used_nodes: set = set()  # nodes this sweep consumed (freed below)
    swept = 0
    for node in reversed(tape):
        if all(r() is None for r in node.out_refs):
            # every output collected → no live head/consumer can reach
            # this node; prune it (and never match a recycled id)
            used_nodes.add(id(node))
            continue
        outs_cot = [cot.get(k) if r() is not None else None
                    for k, r in zip(node.out_keys, node.out_refs)]
        if all(c is None for c in outs_cot):
            continue
        used_nodes.add(id(node))
        swept += 1
        # assemble cotangent structure matching the vjp output structure
        if node.raw_multi:
            # visible outputs lead; hidden raw outputs get zeros. We can
            # recover hidden shapes from the vjp function's expected
            # structure only by probing — instead keep zeros via the
            # visible outputs count; hidden outputs' cotangents are not
            # derivable from the tape, pass zeros of matching shape using
            # jax's None-aware api: jax.vjp requires exact pytree, so we
            # reconstruct with stored ShapeDtypeStructs on first use.
            cots = []
            for i in range(node.n_raw_out):
                if i < len(outs_cot) and outs_cot[i] is not None:
                    cots.append(outs_cot[i])
                else:
                    cots.append(None)
            with _on_device_of(outs_cot):
                cots = _fill_zeros(node, cots)
                in_cots = node.vjp_fn(tuple(cots))
        else:
            with _on_device_of(outs_cot):
                in_cots = node.vjp_fn(outs_cot[0])
        for slot, g, x in zip(node.in_keys, in_cots, node.in_arrays):
            if slot is None or g is None:
                continue
            if getattr(g, "dtype", None) == jax.dtypes.float0:
                continue  # integer-typed input (indices): no gradient
            if x is not None and getattr(x, "_released", False):
                # the producer subgraph of this value was freed by an
                # earlier backward — continuing would yield silently
                # partial gradients. Mirror the reference's error
                # (Imperative::Backward on released AGInfo).
                raise MXNetError(
                    f"cannot differentiate through input of op "
                    f"'{node.op_name}': its computation history was "
                    "already freed by a previous backward; pass "
                    "retain_graph=True to keep it")
            cot[slot] = cot[slot] + g if slot in cot else g
        for slot, x in zip(node.in_keys, node.in_arrays):
            if x is not None and getattr(x, "_is_leaf", False):
                touched_leaves.append(x)
                leaf_slots.setdefault(id(x), set()).add(slot)

    # write leaf gradients — read cotangents at the RECORDED value-keys
    # (a leaf mutated in place after recording has a newer version; its
    # gradient belongs to the version(s) the tape actually read)
    seen = set()
    for x in touched_leaves:
        if id(x) in seen:
            continue
        seen.add(id(x))
        req = getattr(x, "_grad_req", "null")
        if req == "null" or x._grad is None:
            continue
        g = None
        for slot in leaf_slots.get(id(x), ()):
            c = cot.get(slot)
            if c is not None:
                g = c if g is None else g + c
        if g is None:
            continue
        if req == "add":
            x._grad._set_data(x._grad._data + g)
        else:  # write
            x._grad._set_data(jnp.asarray(g, x._grad.dtype))

    if not retain_graph:
        # free only the subgraph this sweep consumed: other heads recorded
        # in the same scope (the per-device losses of a DP step — the
        # reference's `for l in losses: l.backward()` pattern) keep their
        # nodes until their own backward runs. Outputs of freed nodes are
        # marked released so a later backward that reaches one raises
        # instead of silently dropping the upstream gradient. Arrays a
        # user keeps alive without ever calling backward keep their nodes
        # (same retention as the reference's per-array AGInfo); dropped
        # arrays are pruned at the next sweep via the weakrefs.
        for n in tape:
            if id(n) in used_nodes:
                for r in n.out_refs:
                    o = r()
                    if o is not None:
                        o._released = True
        _STATE.tape = [n for n in _STATE.tape if id(n) not in used_nodes]
    return swept


def _on_device_of(cots):
    """Default-device scope for the reverse sweep, as invoke() sets for
    the forward: where the (first) given array lives. What the sweep
    creates rather than computes — the head's ones, the zero gradient
    of an input the loss never used, a hidden output's zero cotangent —
    is uncommitted and would otherwise land on the PROCESS default
    device: replica i's gradient on device 0, which made the kvstore
    decline its fused all-reduce for the whole step."""
    devs = next(c for c in cots if c is not None).devices()
    if len(devs) != 1:
        return contextlib.nullcontext()
    return jax.default_device(next(iter(devs)))


def _fill_zeros(node, cots):
    """Replace None cotangents with zeros matching the vjp's expectation
    (jax.vjp pytree-checks its argument, so every raw output needs a
    cotangent; non-visible aux outputs get zeros)."""
    shapes = node.out_shapes
    if shapes is None:
        raise MXNetError(
            f"op {node.op_name}: multi-output op missing raw output avals"
        )
    return [
        c if c is not None else jnp.zeros(s.shape, s.dtype)
        for c, s in zip(cots, shapes)
    ]


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False, train_mode=True):
    """Compute gradients of heads w.r.t variables and return them
    (MXAutogradBackwardEx with variables set)."""
    from .ndarray.ndarray import NDArray
    from .ndarray import zeros_like

    if create_graph:
        raise MXNetError("create_graph=True (higher-order grad) is not supported yet")
    if isinstance(variables, NDArray):
        variables = [variables]
    saved = [(getattr(v, "_grad", None), getattr(v, "_grad_req", "null"), getattr(v, "_is_leaf", False)) for v in variables]
    gradients = [zeros_like(v) for v in variables]
    mark_variables(variables, gradients, "write")
    try:
        backward(heads, head_grads, retain_graph=bool(retain_graph), train_mode=train_mode)
    finally:
        for v, (g, req, leaf) in zip(variables, saved):
            v._grad, v._grad_req, v._is_leaf = g, req, leaf
    return gradients


class Function:
    """Custom differentiable function (mx.autograd.Function analog).

    Subclass and implement ``forward(self, *inputs)`` and
    ``backward(self, *output_grads)`` operating on NDArrays.
    """

    def __init__(self):
        self._saved = ()

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray

        with pause():
            outputs = self.forward(*inputs)
        single = isinstance(outputs, NDArray)
        outs = [outputs] if single else list(outputs)
        if is_recording() and any(
            isinstance(x, NDArray) and x._requires_grad_somewhere() for x in inputs
        ):
            func = self

            def vjp_fn(cotangents):
                cts = cotangents if isinstance(cotangents, tuple) else (cotangents,)
                from .ndarray.ndarray import _wrap
                ct_nd = [_wrap(c, outs[0].ctx) for c in cts]
                with pause():
                    in_grads = func.backward(*ct_nd)
                if isinstance(in_grads, NDArray):
                    in_grads = [in_grads]
                return tuple(
                    (g._data if isinstance(g, NDArray) else g) for g in in_grads
                )

            raw_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs]

            class _FakeOp:
                name = type(self).__name__

            _record_op(_FakeOp, list(inputs), outs, vjp_fn,
                       raw_multi=not single, n_raw_out=len(outs),
                       raw_avals=raw_avals)
        return outputs if single else outs


def get_symbol(*a, **k):  # legacy API stub (symbol extraction from tape)
    raise MXNetError("autograd.get_symbol is not supported on the TPU backend")
