"""Gluon Block / HybridBlock / SymbolBlock
(python/mxnet/gluon/block.py analog).

``Block`` is the eager container (children registry, name scopes,
collect_params, save/load_parameters, hooks). ``HybridBlock`` adds
``hybridize()`` — the CachedOp analog (reference
src/imperative/cached_op.cc): the first hybridized call *traces*
``hybrid_forward`` into one jit-compiled XLA computation whose
arguments are (rng-key, inputs…, parameters…); subsequent calls with
the same input signature replay the compiled computation. The whole
compiled graph enters the autograd tape as ONE node via jax.vjp —
exactly CachedOp's role of "one engine op for the whole subgraph", with
XLA doing what nnvm PlanMemory/bulking did (`static_alloc`/
`static_shape` become XLA buffer planning, for free).

BatchNorm-style running statistics inside a trace are handled
functionally: layers register deferred aux updates which the tracer
returns as extra outputs and the caller writes back after execution
(the reference mutates aux NDArrays from inside the op; immutability
forces — and rewards — the functional form).
"""
from __future__ import annotations

import copy
import re
import threading
from collections import OrderedDict

import jax
import numpy as np

from ..base import MXNetError
from ..context import Context, current_context, cpu
from ..name import NameManager, Prefix
from ..ndarray import NDArray
from ..ndarray.ndarray import _wrap
from ..ndarray.register import Op, invoke
from .. import autograd as _autograd
from .. import profiler as _profiler
from .. import random as _random
# the names of what a remat'd block keeps by default (`_remat_policy`)
from ..ops.pallas.flash_attention import REMAT_KEEP as _REMAT_KEEP
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    """Name scope for parameter/prefix management."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = NameManager.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return False
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope
        return False


class Block:
    """Base class for all neural network layers and models."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(f"  ({key}): {_indent(str(block), 2)}"
                           for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(value, type(existing)):
                raise TypeError(f"Changing attribute type for {name} from "
                                f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def __getattr__(self, name):
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        from .. import ndarray as nd
        arg_dict = {key: val._reduce() for key, val in params.items()}
        nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from .. import ndarray as nd
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not isinstance(loaded, dict):
            raise MXNetError(f"{filename} has no parameter names")
        if not loaded and not params:
            return
        # legacy full-name format fallback
        if not any("." in k for k in loaded.keys()) and \
                any(k.startswith(self.prefix) for k in loaded.keys()):
            del loaded
            self.collect_params().load(filename, ctx, allow_missing,
                                       ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    f"Parameter '{name}' is missing in file '{filename}'"
        for name in loaded:
            if not ignore_extra and name not in params:
                raise MXNetError(
                    f"Parameter '{name}' loaded from file '{filename}' is not "
                    "present in this Block")
            if name in params:
                param = params[name]
                arr = loaded[name]
                if param._data is None and param._deferred_init:
                    param.shape = arr.shape
                    param._finish_deferred_init()
                elif param._data is None:
                    param._shape = arr.shape
                    param.initialize(ctx=ctx or [current_context()])
                if cast_dtype:
                    arr = arr.astype(param.dtype)
                param.set_data(arr)

    # legacy names
    save_params = save_parameters

    def load_params(self, filename, ctx=None, allow_missing=False, ignore_extra=False):
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        raise NotImplementedError

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        if _in_cached_call():
            # inside a compiled program the block's kind is the name of its
            # operations in the device trace (`_block_scope`)
            with _block_scope(self):
                out = self.forward(*args)
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError


class _HookHandle:
    _id = 0

    def __init__(self, hooks):
        self._hooks = hooks
        _HookHandle._id += 1
        self.id = _HookHandle._id

    def detach(self):
        self._hooks.pop(self.id, None)


def _indent(s, num):
    lines = s.split("\n")
    return ("\n" + " " * num).join(lines)


# ----------------------------------------------------------------------
# trace guard: inside a CachedOp trace (or its shape dry-run) all blocks
# run pure-eager so a parent's compiled graph inlines its children
# (reference CachedOp also flattens the whole subgraph into one graph —
# nested CachedOps would mean nested jit with per-child rng draws)
# ----------------------------------------------------------------------
_TRACE_GUARD = threading.local()


def _in_cached_call() -> bool:
    return getattr(_TRACE_GUARD, "depth", 0) > 0


def _block_scope(block):
    """The name a block's operations carry in a compiled program, and from
    there in the device trace (each event's ``tf_op``): ``mx.<class>``, one
    component of jax's name stack a block call, so nesting gives the path
    (``mx.BERTModel/mx.TransformerEncoderCell/mx.Dense/dot_general``). The
    class and not the instance: six equal layers are one row of a table.
    Entered only under `_in_cached_call`; it is metadata of the program and
    costs nothing at step time. Of a step's phases jax marks remat's rebuild
    (``rematted_computation``); the backward of a recorded CachedOp is a
    second program with the forward's paths (`_build_cached_op` names both
    ``mxtpu_fwd_<class>``; ``transpose(`` shows only where a whole step is
    one program), and `optimizer._fused_update` names the update."""
    return jax.named_scope("mx." + type(block).__name__)


class _trace_guard:
    def __enter__(self):
        _TRACE_GUARD.depth = getattr(_TRACE_GUARD, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _TRACE_GUARD.depth -= 1
        return False


# outermost-wins guard for trace-time remat: hybridize(remat=True)
# propagates to children, but nesting jax.checkpoint inside an already
# checkpointed region just re-wraps recompute in recompute — the
# outermost flagged block claims the wrap and descendants run plain
_REMAT_GUARD = threading.local()


def _remat_policy(flags):
    """(``jax.checkpoint`` policy, the names it keeps) of a block's flags,
    for both remat sites (`_remat_trace`, `_build_cached_op`):
    ``remat_policy`` where the user gave one (a ``jax.checkpoint_policies``
    name, "names:a,b" or a callable), else `_REMAT_KEEP`, the values a
    kernel named as dear to rebuild and small to keep (a flash attention
    call's output and log-sum-exp: rebuilding them is a second forward call
    of the kernel in the backward). A block in which nothing carries one of
    those names keeps nothing but its inputs."""
    policy = flags.get("remat_policy")
    names = ()
    if policy is None:
        names = _REMAT_KEEP
    elif isinstance(policy, str):
        if policy.startswith("names:"):
            # "names:conv_out[,other]" — keep only values tagged with
            # jax.ad_checkpoint.checkpoint_name (Convolution tags its
            # output 'conv_out'): backward recomputes just the cheap
            # elementwise chain between kept anchors
            names = tuple(policy[len("names:"):].split(","))
        else:
            policy = getattr(jax.checkpoint_policies, policy)
    if names:
        policy = jax.checkpoint_policies.save_only_these_names(*names)
    return policy, names


def _tally_kept(heard, names, times=1):
    """Add what a remat site keeps (``heard``: `profiler.named_values` of
    its trace; ``times``: groups of rows) to the open CachedOp build's
    tally, if one is open."""
    kept = getattr(_REMAT_GUARD, "kept", None)
    if kept is not None:
        sizes = [size for name, size in heard if name in names]
        kept[0] += times * len(sizes)
        kept[1] += times * sum(sizes)


# ----------------------------------------------------------------------
# deferred aux updates (BatchNorm running stats inside a trace)
# ----------------------------------------------------------------------
_AUX_COLLECT = threading.local()


def _collecting_aux():
    return getattr(_AUX_COLLECT, "sink", None)


def defer_aux_update(param: Parameter, new_value, increment=False):
    """Called by layers with running state. Inside a hybridize trace the
    new (traced) value is collected as an extra output; eagerly it is
    written immediately. ``increment``: ``new_value`` is what to ADD to the
    state (a tally), so that several calls in one trace — a block called
    more than once, or row by row under ``remat_rows`` — add up instead of
    the last one winning."""
    sink = _collecting_aux()
    if sink is not None:
        sink.append((param, new_value, increment))
    else:
        with _autograd.pause():
            arr = param.data()
            raw = new_value._data if isinstance(new_value, NDArray) else new_value
            arr._set_data(arr._data + raw.astype(arr._data.dtype) if increment
                          else raw)


def _raw(value):
    return value._data if isinstance(value, NDArray) else value


def _resolve_aux(sink, current):
    """The sink's entries as one replacement value per parameter, in first-seen
    order: an increment adds to the parameter's pending value, else to its
    value in this trace (``current``: {parameter: traced array})."""
    pending = {}
    for param, value, increment in sink:
        value = _raw(value)
        if increment:
            base = pending[param][1] if param in pending else current[param]
            value = base + value.astype(base.dtype)
        pending[param] = (param, value)
    return list(pending.values())


class HybridBlock(Block):
    """Block that can be traced into one compiled XLA computation."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._cached_graph = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  remat=None, remat_policy=None, remat_rows=None, **kwargs):
        """Activate compiled execution. static_alloc/static_shape are
        accepted for API parity — XLA always plans memory statically.

        ``remat=True`` (TPU-first extension, no reference analog) wraps
        the compiled subgraph in ``jax.checkpoint``: the backward pass
        recomputes this block's activations instead of storing them —
        the HBM-for-FLOPs trade for long sequences / deep nets.
        Hybridize the root for whole-net remat, or mark children with
        ``child.hybridize(active=False, remat=True)`` for selective
        per-block checkpointing — a marked child is wrapped when any
        ancestor traces it (CachedOp or functionalize;
        :meth:`_remat_trace`). ``remat``/``remat_policy`` default to
        None = KEEP the block's existing setting, so a later parent
        ``net.hybridize()`` does not erase per-child marks; pass
        ``remat=False`` to clear explicitly. What the forward keeps,
        beside the block's inputs: by default only the values a kernel
        named dear to rebuild and small to keep — a flash attention
        call's output and log-sum-exp, so the kernel's forward runs once
        a step and not again in the backward (an attention layer keeps
        one more activation the size of its input; a block with no such
        call keeps nothing; `profiler.counters()` ``remat_kept`` /
        ``remat_kept_bytes`` say what was kept). ``remat_policy``
        overrides that (a ``jax.checkpoint_policies`` name:
        "nothing_saveable" keeps nothing at all; or "names:conv_out" to
        keep conv outputs and recompute only the elementwise chain), for
        whole-net and per-block remat alike. ``remat_rows=N`` (with
        ``remat`` on a marked child) takes the batch N rows at a time
        through the checkpointed block, one group after the other (a
        ``lax.map``), so the backward
        rebuilds N rows' activations at once, not the batch's; a
        tally kept with ``defer_aux_update(..., increment=True)`` adds
        up over the groups. A block may take SEVERAL arrays and return
        several (a decoder layer that reads another layer's keys and
        values, or hands its own on: each is an input or an output of
        the checkpoint, kept, and a value read by two blocks gets both
        cotangents). Under ``remat_rows`` EVERY array input and output
        must lead with the batch axis (so (B, heads, S, d), not (heads,
        B, S, d)), but for an output WITHOUT axes: a scalar second output
        (a layer's own loss term, summed over its rows) is summed over
        the groups, so the block returns the batch's sum whether or not
        the rows were split. Where one input does not lead with the batch
        axis, or N does not divide the batch, the rows are not split: the
        block runs once over the whole batch, correct and with the whole
        batch's rebuilt activations live."""
        prev = self._flags
        if remat is None:
            remat = prev.get("remat", False)
        if remat_policy is None:
            remat_policy = prev.get("remat_policy")
        if remat_rows is None:
            remat_rows = prev.get("remat_rows")
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           remat=remat, remat_policy=remat_policy,
                           remat_rows=remat_rows, **kwargs)
        self._cached_graph = {}
        super().hybridize(active, **kwargs)

    def infer_shape(self, *args):
        """Finalize deferred parameter shapes from the input shapes.

        Parametrized layers override this (the reference runs symbolic
        shape inference over the traced graph; here each layer's shape
        rule is local and explicit — Dense/Conv/BatchNorm/... set their
        weight shapes from the first input)."""
        raise MXNetError(
            f"{type(self).__name__} has deferred-initialized parameters but "
            "does not implement infer_shape")

    def cast(self, dtype):
        super().cast(dtype)
        self._cached_graph = {}

    def __call__(self, *args):
        return super().__call__(*args)

    def forward(self, x, *args):
        """Route to hybrid_forward, eagerly or through the cached op."""
        if isinstance(x, NDArray):
            if self._active and not _in_cached_call():
                return self._call_cached_op(x, *args)
            if self._flags.get("remat") and _in_cached_call() \
                    and not getattr(_REMAT_GUARD, "active", False):
                return self._remat_trace(x, *args)
            return self._forward_eager(x, *args)
        # symbolic path (Symbol inputs → graph building)
        from .. import symbol as symmod
        from ..symbol import Symbol
        if isinstance(x, Symbol):
            params = {k: v.var() for k, v in self._reg_params.items()}
            with self.name_scope():
                return self.hybrid_forward(symmod, x, *args, **params)
        raise MXNetError(f"unsupported input type {type(x)}")

    def _forward_eager(self, x, *args):
        with x.ctx:
            try:
                params = {k: v.data(x.ctx) for k, v in self._reg_params.items()}
            except DeferredInitializationError:
                self._infer_param_shapes(x, *args)
                params = {k: v.data(x.ctx) for k, v in self._reg_params.items()}
            from .. import ndarray as ndmod
            # np-style hybrid blocks reach the numpy namespaces through
            # F.np / F.npx (the deep-numpy convention; attributes are
            # installed on the nd package by mxnet_tpu/__init__) while
            # classic F.<op> names stay exactly as before
            return self.hybrid_forward(ndmod, x, *args, **params)

    def _remat_trace(self, x, *args):
        """Inside a parent trace, run this block under ``jax.checkpoint``:
        the backward pass recomputes the block's activations instead of
        reading them back from HBM (selective activation checkpointing —
        the TPU-native lever for bandwidth-bound backward passes; the
        reference has a coarse graph-level analog in mirror mode,
        docs/faq/env_var.md MXNET_BACKWARD_DO_MIRROR).

        The wrapped function is pure: (rng-key, inputs, params) →
        (outputs, aux updates). Running-stat updates surface as extra
        checkpoint outputs and re-enter the outer trace's aux sink; a
        subkey of the active trace key is passed in explicitly so the
        backward recompute replays identical randomness (dropout masks
        match between forward and rebuild). What the forward keeps is
        `_remat_policy`'s: the user's ``remat_policy``, else the inputs
        and whatever a kernel inside named with one of `_REMAT_KEEP`
        (tallied, where a CachedOp build is listening, for
        `profiler.counters()` ``remat_kept``)."""
        ctx = x.ctx
        try:
            params = list(self.collect_params().values())
            p_datas = [p.data(ctx)._data for p in params]
        except DeferredInitializationError:
            # shapes not concrete yet (dry-run trace) — plain eager pass;
            # the real trace after init takes the checkpointed path
            return self._forward_eager(x, *args)
        arg_template = [x] + list(args)
        in_datas = [a._data for a in arg_template if isinstance(a, NDArray)]
        box = {}
        block = self

        def pure(rng_key, in_datas, p_datas):
            it = iter(in_datas)
            call_args = [_wrap(next(it), ctx) if isinstance(a, NDArray) else a
                         for a in arg_template]
            saved = [(p, p._data) for p in params]
            outer_sink = getattr(_AUX_COLLECT, "sink", None)
            sink: list = []
            _AUX_COLLECT.sink = sink
            _random.push_trace_key(rng_key)
            prev_remat = getattr(_REMAT_GUARD, "active", False)
            _REMAT_GUARD.active = True
            try:
                for p, d in zip(params, p_datas):
                    p._data = {c: _wrap(d, c) for c in p._data}
                with _profiler.named_values() as box["named"]:
                    out = block._forward_eager(*call_args)
            finally:
                _REMAT_GUARD.active = prev_remat
                for p, d in saved:
                    p._data = d
                _AUX_COLLECT.sink = outer_sink
                _random.pop_trace_key()
            flat, structure = _flatten(out)
            box["structure"] = structure
            box["aux_params"] = [(p, inc) for p, _, inc in sink]
            aux = tuple(_raw(n) for _, n, _ in sink)
            return tuple(f._data for f in flat), aux

        policy, keep = _remat_policy(self._flags)
        ckpt = jax.checkpoint(pure, policy=policy)
        key = _random._next_key()
        rows = self._flags.get("remat_rows")
        batch = in_datas[0].shape[0] if in_datas and in_datas[0].ndim else 0
        n = 1
        # the rows are split only where every array input leads with the
        # batch axis and ``rows`` divides it; else (the fallback the
        # docstring of `hybridize` names) the whole batch at once
        if rows and batch > rows and batch % rows == 0 \
                and all(d.ndim and d.shape[0] == batch for d in in_datas):
            # ``rows`` rows at a time, one after the other (a lax.map: the
            # backward holds one group's rebuilt activations, not the batch's)
            n = batch // rows
            groups = [d.reshape((n, rows) + d.shape[1:]) for d in in_datas]
            outs, auxs = jax.lax.map(
                lambda kx: ckpt(kx[0], list(kx[1]), p_datas),
                (jax.random.split(key, n), groups))
            # an output without axes (a layer's scalar loss term) is the sum
            # over the groups; the others lie side by side
            out_datas = tuple(o.sum(0).astype(o.dtype) if o.ndim == 1
                              else o.reshape((batch,) + o.shape[2:])
                              for o in outs)
            aux_datas = tuple(a.sum(0).astype(a.dtype) if inc else a[-1]
                              for a, (_, inc) in zip(auxs, box["aux_params"]))
        else:
            out_datas, aux_datas = ckpt(key, in_datas, p_datas)
        _tally_kept(box["named"], keep, times=n)
        for (p, inc), new in zip(box["aux_params"], aux_datas):
            defer_aux_update(p, _wrap(new, ctx), increment=inc)
        flat = [_wrap(d, ctx) for d in out_datas]
        return _unflatten(flat, box["structure"])

    def _infer_param_shapes(self, *args):
        """Finalize deferred init using the layer's shape rule, then retry.
        (Children finalize on their own first calls.)"""
        self.infer_shape(*args)
        for _, v in self._reg_params.items():
            v._finish_deferred_init()

    # -- the CachedOp analog ----------------------------------------------
    def _call_cached_op(self, *args):
        with _profiler.span("mxtpu/cachedop/call", block=self.name) as sp:
            inputs = [a for a in args if isinstance(a, NDArray)]
            ctx = inputs[0].ctx if inputs else current_context()
            # make sure all params are concrete (deferred init finalized
            # by an eager dry-run if needed)
            try:
                params = list(self.collect_params().values())
                param_arrays = [p.data(ctx) for p in params]
            except DeferredInitializationError:
                with _autograd.pause(), _trace_guard():
                    self.forward(*args)
                params = list(self.collect_params().values())
                param_arrays = [p.data(ctx) for p in params]

            training = _autograd.is_training()
            from ..ndarray.register import dispatch_cast_generation
            key = (tuple((tuple(a.shape), str(a.dtype)) for a in inputs),
                   training,
                   dispatch_cast_generation())  # AMP on/off → fresh trace
            entry = self._cached_graph.get(key)
            sp.set(built=int(entry is None))
            if entry is None:
                # the trace; the compile is this call's `invoke` below
                _profiler.count("cachedop_builds")
                with _profiler.span("mxtpu/cachedop/build",
                                    block=self.name) as build:
                    # what this trace's remat sites keep for a backward
                    # (`_tally_kept`): nothing where nobody records one
                    kept = [0, 0]
                    _REMAT_GUARD.kept = \
                        kept if _autograd.is_recording() else None
                    tiles = _profiler.counters(device=False)
                    try:
                        entry = self._build_cached_op(args, inputs, params,
                                                      ctx, training)
                    finally:
                        _REMAT_GUARD.kept = None
                    build.set(remat_kept=kept[0], remat_kept_bytes=kept[1])
                    # the kernels' tallies of this trace (`_BUILD_TALLIES`, at
                    # this file's end: a line added here would move `traced`
                    # below, whose line number compiled programs carry)
                    now = _profiler.counters(device=False)
                    build.set(**{k: now[k] - tiles[k]
                                 for k in _BUILD_TALLIES})
                _profiler.count("remat_kept", kept[0])
                _profiler.count("remat_kept_bytes", kept[1])
                self._cached_graph[key] = entry
            op, structure, aux_params, n_flat_out = entry

            rng = _wrap(_random._next_key(), ctx)
            results = invoke(op, [rng] + inputs + param_arrays, {}, ctx=ctx)
            if not isinstance(results, list):
                results = [results]
            flat_out, aux_out = results[:n_flat_out], results[n_flat_out:]
            # write back running stats
            with _autograd.pause():
                for p, new in zip(aux_params, aux_out):
                    p.data(ctx)._set_data(new._data)
            return _unflatten(flat_out, structure)

    def _build_cached_op(self, args, inputs, params, ctx, training):
        """Trace hybrid_forward into a jitted function (CachedOp ctor)."""
        # trace time is compile time: make sure the persistent
        # compilation cache is pointed at disk BEFORE the first jit,
        # so this executable outlives the process (warm restarts)
        from .. import compile_cache
        compile_cache.ensure()
        block = self
        n_in = len(inputs)
        arg_template = list(args)

        aux_params_order: list = []
        remat = self._flags.get("remat")
        policy, keep = _remat_policy(self._flags)

        def traced(rng_key, *arrays):
            in_arrays = arrays[:n_in]
            p_arrays = arrays[n_in:]
            wrapped_inputs = [_wrap(a, ctx) for a in in_arrays]
            # rebuild the positional args with traced NDArrays
            it = iter(wrapped_inputs)
            call_args = [next(it) if isinstance(a, NDArray) else a
                         for a in arg_template]
            _random.push_trace_key(rng_key)
            sink: list = []
            _AUX_COLLECT.sink = sink
            saved_data = [(p, p._data) for p in params]
            prev_train = _autograd.set_training(training)
            prev_rec = _autograd.set_recording(False)
            prev_remat = getattr(_REMAT_GUARD, "active", False)
            if remat:
                # whole-block remat is applied at the jit level below —
                # keep forward() from re-wrapping this same block (and
                # any descendant) in a nested trace-time checkpoint
                _REMAT_GUARD.active = True
            try:
                with _trace_guard(), _profiler.named_values() as named:
                    for p, arr in zip(params, p_arrays):
                        wrappers = {c: _wrap(arr, c) for c in p._data}
                        p._data = wrappers
                    # the root's call does not pass `__call__`
                    with _block_scope(block):
                        out = block.forward(*call_args)
            finally:
                _REMAT_GUARD.active = prev_remat
                for p, d in saved_data:
                    p._data = d
                _autograd.set_recording(prev_rec)
                _autograd.set_training(prev_train)
                _AUX_COLLECT.sink = None
                _random.pop_trace_key()
            flat, structure = _flatten(out)
            aux_arrays = []
            aux_params_order.clear()
            for p, new in _resolve_aux(sink, dict(zip(params, p_arrays))):
                aux_params_order.append(p)
                aux_arrays.append(new)
            traced._structure = structure
            if remat:
                _tally_kept(named, keep)
            return tuple(x._data if isinstance(x, NDArray) else x
                         for x in flat) + tuple(aux_arrays)

        # the program's name in the trace's ``XLA Modules`` line, and part of
        # its key in the persistent compile cache
        traced.__name__ = traced.__qualname__ = \
            "mxtpu_fwd_" + type(self).__name__
        fn = jax.checkpoint(traced, policy=policy) if remat else traced
        jitted = jax.jit(fn)
        # learn the output structure abstractly — no device execution
        # (jax.eval_shape runs the python once with avals; the real
        # compile+run happens on the first invoke below)
        rng = _random._next_key()
        sample = jax.eval_shape(traced, rng, *[a._data for a in inputs],
                                *[p.data(ctx)._data for p in params])
        structure = traced._structure
        n_flat_out = len(sample) - len(aux_params_order)
        op = Op(f"CachedOp_{self.name}", jitted, differentiable=True)
        return op, structure, list(aux_params_order), n_flat_out

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Export model-symbol.json + params (reference HybridBlock.export)."""
        from .. import symbol as symmod
        from .. import ndarray as nd
        data = symmod.var("data")
        with _autograd.pause():
            try:
                sym = self(data)
            except Exception as e:
                raise MXNetError(
                    "export requires the block to support symbolic forward; "
                    f"tracing failed: {e}") from e
        if isinstance(sym, (list, tuple)):
            sym = symmod.Group(list(sym))
        sym.save(f"{path}-symbol.json")
        arg_dict = {}
        for name, param in self.collect_params().items():
            arg_dict[f"arg:{name}"] = param._reduce()
        nd.save(f"{path}-{epoch:04d}.params", arg_dict)
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"

    def forward_symbolic(self, x, *args):
        return self.forward(x, *args)


def _flatten(out):
    """Flatten nested (list/tuple of) NDArrays → flat list + structure."""
    if isinstance(out, NDArray):
        return [out], "single"
    if isinstance(out, (list, tuple)):
        flat = []
        struct = []
        for o in out:
            f, s = _flatten(o)
            flat.extend(f)
            struct.append((s, len(f)))
        return flat, struct
    raise MXNetError(f"unsupported output type {type(out)}")


def _unflatten(flat, structure):
    if structure == "single":
        return flat[0]
    out = []
    i = 0
    for s, n in structure:
        if s == "single":
            out.append(flat[i])
        else:
            out.append(_unflatten(flat[i:i + n], s))
        i += n
    return out


def functionalize(block: Block, training: bool = False, ctx=None):
    """Pure-functional view of a block: returns ``(fn, params)`` where
    ``fn(param_arrays: dict, rng_key, *input_arrays) -> jax array(s)`` is
    jit-traceable and ``params`` maps parameter name → jax array.

    This is the bridge from the MXNet-shaped object API to the
    jit/pjit/shard_map world (SURVEY §7: the sharded Trainer fast path,
    __graft_entry__, and the benchmarks use it). The block must already
    be initialized (shapes concrete). BatchNorm running-stat updates are
    dropped inside the functional view (they are aux side effects; use
    the CachedOp path when you need them written back).
    """
    params = list(block.collect_params().values())
    if ctx is None:
        ctx = current_context()

    def fn(param_arrays, rng_key, *in_arrays):
        saved = [(p, p._data) for p in params]
        _random.push_trace_key(rng_key)
        prev_train = _autograd.set_training(training)
        prev_rec = _autograd.set_recording(False)
        prev_sink = getattr(_AUX_COLLECT, "sink", None)
        _AUX_COLLECT.sink = []
        try:
            with _trace_guard():
                for p in params:
                    arr = param_arrays[p.name]
                    p._data = {c: _wrap(arr, c) for c in p._data}
                # None inputs pass through untouched: optional
                # positional slots (e.g. BERTModel's mask between
                # valid_length and segment_ids) stay skippable from the
                # functional caller
                out = block(*[_wrap(a, ctx) if a is not None else None
                              for a in in_arrays])
        finally:
            for p, d in saved:
                p._data = d
            _autograd.set_recording(prev_rec)
            _autograd.set_training(prev_train)
            _AUX_COLLECT.sink = prev_sink
            _random.pop_trace_key()
        flat, structure = _flatten(out)
        arrays = tuple(x._data for x in flat)
        return arrays[0] if structure == "single" else arrays

    init_params = {p.name: p.data(ctx)._data for p in params}
    return fn, init_params


class SymbolBlock(HybridBlock):
    """Wrap an exported Symbol graph as a Block (reference SymbolBlock).
    Loads model-symbol.json + .params (the deployment path)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=None)
        from ..symbol import Symbol, Group
        if isinstance(outputs, (list, tuple)):
            outputs = Group(list(outputs))
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._symbol = outputs
        self._input_names = [i.name for i in inputs]
        arg_names = outputs.list_arguments()
        for name in arg_names:
            if name not in self._input_names:
                self.params.get(name, allow_deferred_init=True)
        if params is not None:
            for name, arr in params.items():
                clean = name
                for pfx in ("arg:", "aux:"):
                    if clean.startswith(pfx):
                        clean = clean[len(pfx):]
                p = self.params.get(clean, allow_deferred_init=True)
                p._shape = arr.shape
                p.initialize(ctx=[arr.ctx])
                p.set_data(arr)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as symmod
        from .. import ndarray as nd
        sym = symmod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [symmod.var(n) for n in input_names]
        params = nd.load(param_file) if param_file else None
        ret = SymbolBlock(sym, inputs, params)
        if ctx is not None and params is not None:
            ret.collect_params().reset_ctx(ctx)
        return ret

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            bindings = dict(zip(self._input_names, [x] + list(args)))
            for name, p in self.params.items():
                bindings[name] = p.data(x.ctx)
            outs = self._symbol._eval(bindings)
            return outs[0] if len(outs) == 1 else outs
        raise MXNetError("SymbolBlock only supports NDArray inputs")

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


# what a build span reports of its trace's kernels (`_call_cached_op`):
# differences of these `profiler.counters()` tallies over the trace
_BUILD_TALLIES = ("flash_tiles", "flash_tiles_live", "dsa_layers",
                  "dsa_topk_chunks", "dsa_topk_chunks_live",
                  "conv1d_calls", "conv1d_kernel_calls",
                  "mla_rope_calls", "mtp_modules",
                  "flash_calls", "flash_two_width_calls",
                  "bdiff_calls", "bdiff_tiles", "bdiff_tiles_live",
                  "flash_chunks", "flash_chunks_run", "flash_grid_steps")
