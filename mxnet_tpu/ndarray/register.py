"""Operator registry + imperative dispatch.

This is the TPU-native analog of three reference layers at once:

- the nnvm op registry (``NNVM_REGISTER_OP`` + attr dicts,
  include/mxnet/op_attr_types.h): here an :class:`Op` record holding the
  JAX implementation (the ``FCompute<tpu>`` of the north star) plus
  metadata (differentiability, number of outputs, aliases);
- ``Imperative::Invoke`` (src/imperative/imperative.cc): eager dispatch —
  resolve the target context, unwrap NDArray→jax.Array, run the impl
  (shape/dtype inference is implicit: XLA infers during tracing, the
  ``SetShapeType`` analog), wrap outputs, honour ``out=``;
- ``Imperative::RecordOp``: when autograd is recording and any input
  requires grad, the op is executed through ``jax.vjp`` and the pullback
  closure is appended to the tape (the nnvm-tape analog; residuals live
  on device).

Import-time namespace codegen (``_init_op_module`` in the reference's
python/mxnet/base.py) is :func:`populate_namespace`, which turns every
registered op into a module-level function ``mx.nd.<op>``.

Async contract: dispatch returns immediately — jax.Array is a future —
and ``engine.on_dispatch`` tracks outputs for WaitForAll (see engine.py).
"""
from __future__ import annotations

import ast
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, _Registry, dtype_np
from ..context import Context, current_context
from ..engine import engine
from ..profiler import count as _count, op_span as _op_span

__all__ = ["Op", "register_op", "invoke", "populate_namespace", "OP_REGISTRY"]

OP_REGISTRY = _Registry("operator")
# case-sensitive primary index (MXNet op names are case-sensitive:
# FullyConnected vs broadcast_add)
_OPS: dict[str, "Op"] = {}


class Op:
    """A registered operator.

    Attributes
    ----------
    name : canonical op name (e.g. 'FullyConnected')
    fn : callable(*arrays, **params) -> array | tuple(arrays)
        Pure JAX implementation; must be jit-traceable.
    differentiable : bool
        If False the op is never recorded on the autograd tape
        (integer/ordering ops). Analog of having no FGradient attr.
    num_visible_outputs : int | None
        When the impl returns a tuple but user-facing output count is
        smaller (e.g. BatchNorm returns (out, mean, var)), how many lead
        outputs the eager API returns. None = all.
    """

    __slots__ = ("name", "fn", "differentiable", "aliases",
                 "num_visible_outputs", "mutates", "dynamic_arity",
                 "infer_num_outputs", "infer_input_names")

    def __init__(self, name, fn, differentiable=True, aliases=(),
                 num_visible_outputs=None, mutates=(), dynamic_arity=False,
                 infer_num_outputs=None, infer_input_names=None):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.aliases = tuple(aliases)
        self.num_visible_outputs = num_visible_outputs
        # (raw_output_index, input_index) pairs written back in place —
        # the reference's kWriteInplace/aux-state mutation (optimizer ops
        # update mom/mean/var inputs; see op_impl_optimizer.py)
        self.mutates = tuple(mutates)
        # True only for ops whose ``num_outputs`` kwarg IS the output
        # count (split/SliceChannel, amp_multicast); gates the symbolic
        # arity override so an unrelated param named num_outputs on a
        # future op can't silently mis-route sym[i] indexing
        self.dynamic_arity = bool(dynamic_arity)
        # param-dependent metadata hooks (mx.operator Custom: output
        # count and input names come from the user's CustomOpProp, keyed
        # by the op_type param) — callable(params_dict) -> int / [str]
        self.infer_num_outputs = infer_num_outputs
        self.infer_input_names = infer_input_names

    def __repr__(self):
        return f"<Op {self.name}>"


def register_op(name=None, *, differentiable=True, aliases=(),
                num_visible_outputs=None, mutates=(), wrap=True,
                dynamic_arity=False, infer_num_outputs=None,
                infer_input_names=None):
    """Decorator: register a JAX function as an operator.

    ``wrap=False`` registers the op but does not expose a generated
    namespace function (for internal helpers).
    """

    def deco(fn):
        op_name = name or fn.__name__
        op = Op(op_name, fn, differentiable=differentiable, aliases=aliases,
                num_visible_outputs=num_visible_outputs, mutates=mutates,
                dynamic_arity=dynamic_arity,
                infer_num_outputs=infer_num_outputs,
                infer_input_names=infer_input_names)
        _OPS[op_name] = op
        # re-registration may change the impl signature — drop the
        # cached positional-name tuple call_op_fn binds with
        _POS_PARAM_NAMES.pop(op_name, None)
        for a in aliases:
            _OPS[a] = op
            _POS_PARAM_NAMES.pop(a, None)
        OP_REGISTRY.register(op_name)(op)
        fn._op = op
        fn._expose = wrap
        return fn

    return deco


def get_op(name: str) -> Op:
    try:
        return _OPS[name]
    except KeyError:
        raise MXNetError(f"operator {name!r} is not registered") from None


def list_ops():
    """Analog of MXListAllOpNames."""
    return sorted(_OPS)


def _parse_param(v):
    """Accept MXNet-style stringified params ("(3, 3)", "True", "float32")."""
    if isinstance(v, str):
        try:
            return ast.literal_eval(v)
        except (ValueError, SyntaxError):
            return v
    return v


def _as_jax(x, ctx: Context | None):
    """Unwrap NDArray / coerce python scalars & numpy to jax arrays."""
    from .ndarray import NDArray

    if isinstance(x, NDArray):
        return x._data
    if isinstance(x, (jnp.ndarray, jax.Array)):
        return x
    if isinstance(x, (int, float, bool, np.generic)):
        return x  # let jnp broadcast python scalars (keeps weak typing)
    if isinstance(x, np.ndarray):
        return jnp.asarray(x)
    raise MXNetError(f"cannot convert {type(x)} to tensor input")


# AMP dispatch-cast hook (contrib/amp): when installed, every op's
# tensor inputs pass through it before execution — the TPU-native form
# of the reference's amp_cast/amp_multicast graph rewrite. It applies
# during BOTH eager dispatch and hybridize/CachedOp tracing (traces run
# through invoke), so compiled graphs carry the casts.
_DISPATCH_CAST_HOOK = None
# bumped on every hook change: compiled-graph caches (CachedOp, the
# symbolic executor) key on this so traces built before amp.init() are
# not served after it (and vice versa)
_DISPATCH_CAST_GENERATION = 0


def set_dispatch_cast_hook(fn):
    """Install (or clear with None) the AMP cast hook:
    fn(op, [jax arrays]) -> [jax arrays]."""
    global _DISPATCH_CAST_HOOK, _DISPATCH_CAST_GENERATION
    _DISPATCH_CAST_HOOK = fn
    _DISPATCH_CAST_GENERATION += 1


def dispatch_cast_generation():
    return _DISPATCH_CAST_GENERATION


# lazy one-time bind of the np ndarray class holder (the ndarray
# PACKAGE self-aliases its `ndarray` attr, so the defining module is
# fetched through sys.modules once, not per dispatch)
_ND_NDARRAY_MOD = None


def _np_cls():
    global _ND_NDARRAY_MOD
    if _ND_NDARRAY_MOD is None:
        _ND_NDARRAY_MOD = sys.modules["mxnet_tpu.ndarray.ndarray"]
    return _ND_NDARRAY_MOD._NP_CLS


# -- op-invocation recording ------------------------------------------
# The test suite's coverage gate used to trust a hand-maintained list;
# now conftest.py turns recording on and gates on the ops ACTUALLY
# dispatched during the run (eager invoke + symbolic executor).
_INVOCATION_RECORD = None


def record_invocations(target):
    """Route every subsequent op dispatch's canonical name into
    ``target`` (a set); pass None to stop recording."""
    global _INVOCATION_RECORD
    _INVOCATION_RECORD = target


def _note_invocation(op):
    if _INVOCATION_RECORD is not None:
        _INVOCATION_RECORD.add(op.name)


def invoke(op: Op, inputs, params=None, out=None, ctx: Context | None = None,
           name=None, wrap_cls=None):
    """Eager dispatch of one op — `Imperative::Invoke` analog.

    Parameters
    ----------
    inputs : sequence of NDArray / array-like tensor inputs
    params : dict of non-tensor attributes (the DMLC parameter struct)
    out : optional NDArray (or list) to write results into (in-place API)
    ctx : target context; defaults to first input's context else current
    """
    from .ndarray import NDArray, _wrap

    _note_invocation(op)
    params = {k: _parse_param(v) for k, v in (params or {}).items() if v is not None}
    # trailing None tensor inputs (e.g. bias with no_bias=True) are dropped
    # so the impl's defaults apply — mirrors optional op inputs upstream
    while inputs and inputs[-1] is None:
        inputs = list(inputs)[:-1]

    if ctx is None:
        for x in inputs:
            if isinstance(x, NDArray):
                ctx = x.ctx
                break
        else:
            ctx = current_context()

    arrays = [_as_jax(x, ctx) for x in inputs]

    from .. import autograd  # late import (cycle)

    # The reference tapes every op invoked under record() (RecordOp),
    # which is what makes post-hoc autograd.grad(heads, variables) work;
    # backward only walks the needed subgraph.
    record = (
        autograd.is_recording()
        and op.differentiable
        and any(isinstance(x, NDArray) for x in inputs)
    )

    _count("invokes")
    device = ctx.jax_device
    # `mxtpu/op/<name>` while a profiler is active (the shared no-op
    # otherwise): the dispatch-side op event (ThreadedEngine
    # ProfileOperator analog; the device timeline is the jax profiler's
    # — execution is async under PJRT, so this measures trace+dispatch,
    # which equals execution under MXNET_ENGINE_TYPE=NaiveEngine)
    with _op_span(op.name), jax.default_device(device):
        if record:
            fn = functools.partial(_call_positional, op, params, len(arrays))
            raw_out, vjp_fn = jax.vjp(fn, *arrays)
        else:
            raw_out = _call_positional(op, params, len(arrays), *arrays)
            vjp_fn = None

    multi = isinstance(raw_out, (tuple, list))
    out_arrays = list(raw_out) if multi else [raw_out]
    engine.on_dispatch(out_arrays)

    # snapshot input value-keys BEFORE any out=/mutates write-back bumps
    # versions — the tape must reference the values the op actually read
    if record:
        in_keys = [(id(x), x._version) if isinstance(x, NDArray) else None
                   for x in inputs]

    # in-place state mutation (optimizer mom/mean/var — kWriteInplace)
    for out_idx, in_idx in op.mutates:
        tgt = inputs[in_idx]
        if isinstance(tgt, NDArray):
            tgt._set_data(out_arrays[out_idx])

    # wrap / write into `out`
    visible = op.num_visible_outputs
    if out is not None:
        outs = out if isinstance(out, (tuple, list)) else [out]
        vis = out_arrays if visible is None else out_arrays[:visible]
        if len(outs) != len(vis):
            raise MXNetError(f"{op.name}: expected {len(vis)} out= arrays, got {len(outs)}")
        for o, a in zip(outs, vis):
            o._set_data(a)
        results = list(outs)
    else:
        n = len(out_arrays) if visible is None else visible
        if wrap_cls is None:
            # np-mode class preservation: outputs are mx.np.ndarray when
            # any input already is one (mixing np activations with
            # classic params inside Gluon blocks keeps the np-ness of
            # the dataflow). The set_np global mode is handled inside
            # _wrap itself, so only the input rule lives here.
            np_cls = _np_cls()
            if np_cls is not None and any(isinstance(x, np_cls) for x in inputs):
                wrap_cls = np_cls
        results = [_wrap(a, ctx, cls=wrap_cls) for a in out_arrays[:n]]

    if record:
        raw_avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in out_arrays]
        autograd._record_op(op, [x for x in inputs], results, vjp_fn,
                            raw_multi=multi, n_raw_out=len(out_arrays),
                            raw_avals=raw_avals, in_keys=in_keys)

    if len(results) == 1:
        return results[0]
    return results


# op name -> leading positional parameter names of its impl (cached;
# stops at *args / keyword-only, same rule as the symbol builder's
# scalar folding)
_POS_PARAM_NAMES: dict[str, tuple] = {}


def _positional_names(op):
    names = _POS_PARAM_NAMES.get(op.name)
    if names is None:
        import inspect
        try:
            names = []
            for p in inspect.signature(op.fn).parameters.values():
                if p.kind not in (p.POSITIONAL_ONLY,
                                  p.POSITIONAL_OR_KEYWORD):
                    break
                names.append(p.name)
            names = tuple(names)
        except (TypeError, ValueError):
            names = ()
        _POS_PARAM_NAMES[op.name] = names
    return names


def call_op_fn(op, arrays, params):
    """``op.fn(*arrays, **params)`` with signature-aware rebinding.

    The symbol builder folds scalar positionals into attrs by their
    ORIGINAL argument index (sym.op(x, 2.0, y) -> inputs [x, y], attr
    {<param1>: 2.0}). Calling the impl with the tensors positional
    would then bind y into the scalar's slot and collide ("multiple
    values for <param1>"). When an attr names one of the leading slots
    the tensors would occupy, walk the signature's positional names and
    the tensors together, skipping names the attrs own — reproducing
    the user's original argument order."""
    if params:
        names = _positional_names(op)
        if names and any(n in params for n in names[:len(arrays)]):
            free = [n for n in names if n not in params]
            if len(arrays) <= len(free):  # every tensor has a named slot
                return op.fn(**dict(zip(free, arrays)), **params)
    return op.fn(*arrays, **params)


def _call_positional(op, params, nargs, *arrays):
    """Closure helper so jax.vjp sees only tensor positionals. The AMP
    cast hook applies HERE — inside the differentiated function — so
    vjp transposes the casts and cotangent dtypes line up with each
    producer's output dtype."""
    if _DISPATCH_CAST_HOOK is not None:
        arrays = _DISPATCH_CAST_HOOK(op, arrays)
    return call_op_fn(op, arrays, params)


def _make_ns_function(op: Op, fname: str):
    def op_func(*args, **kwargs):
        from .ndarray import NDArray

        out = kwargs.pop("out", None)
        ctx = kwargs.pop("ctx", None)
        name = kwargs.pop("name", None)  # symbol-compat, ignored eagerly
        # split positional tensor inputs from keyword params: MXNet ops
        # take tensors positionally (or as leading kwargs like data=)
        inputs = list(args)
        # common tensor kwarg spellings (data=, lhs=, rhs=...) — pull any
        # NDArray-valued kwarg into inputs in declaration order when the
        # impl names them; simplest robust rule: NDArray kwargs are bound
        # through the impl signature directly.
        tensor_kwargs = {k: v for k, v in kwargs.items() if isinstance(v, NDArray)}
        if tensor_kwargs and not inputs:
            # rely on python binding: call impl-style fn(data=..) via invoke
            # by reordering using fn signature
            import inspect

            sig = inspect.signature(op.fn)
            bound = []
            for pname in sig.parameters:
                if pname in tensor_kwargs:
                    bound.append(kwargs.pop(pname))
                else:
                    break
            inputs = bound
        return invoke(op, inputs, kwargs, out=out, ctx=ctx, name=name)

    op_func.__name__ = fname
    op_func.__qualname__ = fname
    op_func.__doc__ = op.fn.__doc__
    op_func._op = op
    return op_func


def populate_namespace(module_name: str, names=None):
    """Generate `mx.nd.<op>` functions into a module — `_init_op_module`.

    Called at import time by mxnet_tpu.ndarray.
    """
    mod = sys.modules[module_name]
    seen = set()
    for nm, op in list(_OPS.items()):
        if names is not None and nm not in names:
            continue
        if nm in seen:
            continue
        seen.add(nm)
        setattr(mod, nm, _make_ns_function(op, nm))
    return sorted(seen)
