"""Optimizers (python/mxnet/optimizer/optimizer.py analog).

Same surface as the reference: an ``Optimizer`` registry, per-parameter
state creation (``create_state``), index-keyed ``update``, lr/wd
multipliers, gradient rescale/clipping, multi-precision (fp32 master
weights for bf16/fp16 params — the mp_sgd path), and an ``Updater``
wrapper that KVStore server-side updates use.

The update math is the optimizer ops' (ndarray/op_impl_optimizer.py). An
optimizer whose dense update is one such op says so once, in
``dense_rule``. ``update`` (per key: kvstore, Module, sparse gradients)
invokes that op eagerly and writes back through ``out=``;
``update_multi`` (Gluon ``Trainer.step``) applies the same op to the
whole dense parameter list inside ONE compiled program, `_fused_update`,
with the multi-precision casts and the state writes in it.
"""
from __future__ import annotations

import math
import pickle

import jax
import numpy as np

from ..base import _Registry, MXNetError
from ..engine import engine as _engine
from ..ndarray import NDArray, sparse as _sparse, zeros
from ..ndarray.register import invoke as _invoke, get_op as _get_op
from ..profiler import count as _count, op_span as _op_span

__all__ = ["Optimizer", "Updater", "get_updater", "create", "register"]

_REG = _Registry("optimizer")


def register(klass):
    _REG.register(klass.__name__.lower())(klass)
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return _REG.get(name)(**kwargs)


# dense op -> the op that applies the same rule to a row-sparse gradient
_ROW_SPARSE_TWIN = {"sgd_update": "sgd_update_rsp",
                    "sgd_mom_update": "sgd_mom_update_rsp",
                    "adam_update": "adam_update_rsp",
                    "adagrad_update": "adagrad_update_rsp"}


def _leaves(state):
    """A parameter's state as the op's tensor inputs after (weight, grad)."""
    if state is None:
        return ()
    return tuple(state) if isinstance(state, (tuple, list)) else (state,)


def _pinned(x):
    """``x``'s array, committed to its device: `zeros` makes a fresh state
    uncommitted and a program's output is committed, and jit compiles
    once for each; this way the first step's program is every step's."""
    a = x._data
    return a if a.committed else jax.device_put(a, x.ctx.jax_device)


def _definer(cls, attr):
    return next(c for c in cls.__mro__ if attr in vars(c))


@jax.named_scope("mxtpu_update")
def _fused_update(rules, weights, grads, states, masters, hyper, rescale_grad):
    """Every parameter's rule in one program: ``rules[i]`` is parameter
    i's (op name, constant hyperparameters), static; ``hyper`` maps the
    op's per-step keywords (lr, wd) to one float32 vector over the
    parameters and ``rescale_grad`` is a float32 scalar, all traced, so a
    schedule, Adam's bias correction or a new batch size compile nothing.
    A traced scalar is strongly typed where the eager op's Python float is
    not: each is cast to the dtype of the array the eager op would
    multiply it into, and every output to the array it replaces. The
    optimizer's own arrays (``states``, ``masters``) are donated; weights
    and gradients are the caller's and are not. The name scope
    ``mxtpu_update`` marks the one phase of a step that jax's name stack
    does not mark itself (``transpose(``, ``rematted_computation``)."""
    new_weights, new_states, new_masters = [], [], []
    for i, (name, consts) in enumerate(rules):
        op, weight, grad, master = _get_op(name), weights[i], grads[i], masters[i]
        if master is not None:
            weight, grad = master, grad.astype(master.dtype)
        kw = {k: v[i].astype(weight.dtype) for k, v in hyper.items()}
        out = op.fn(weight, grad, *states[i],
                    rescale_grad=rescale_grad.astype(grad.dtype), **kw,
                    **dict(consts))
        out = out if isinstance(out, tuple) else (out,)
        written = {in_idx: out[out_idx] for out_idx, in_idx in op.mutates}
        new_states.append(tuple(written[2 + j].astype(s.dtype)
                                for j, s in enumerate(states[i])))
        new = out[0] if master is None else out[0].astype(master.dtype)
        new_masters.append(None if master is None else new)
        new_weights.append(new.astype(weights[i].dtype))
    return new_weights, new_states, new_masters


# the program's name: `jit_mxtpu_update` on a trace's ``XLA Modules`` line,
# and part of its key in the persistent compile cache
_fused_update.__name__ = _fused_update.__qualname__ = "mxtpu_update"
_fused_update = jax.jit(_fused_update, static_argnames="rules",
                        donate_argnames=("states", "masters"))


class Optimizer:
    """Base optimizer. A subclass implements ``create_state`` and either
    ``dense_rule`` (its update is one optimizer op: ``update`` and the
    compiled ``update_multi`` both follow from it) or ``update`` itself
    (anything else: ``update_multi`` then loops over it)."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.idx2name = dict(param_idx2name or {})
        self.param_dict = param_dict or {}
        self.aggregate_num = 0

    # -- registry-compat
    create_optimizer = staticmethod(create)

    def create_state(self, index, weight):
        return None

    def _has_master(self, weight):
        return self.multi_precision and str(weight.dtype) in ("float16", "bfloat16")

    def create_state_multi_precision(self, index, weight):
        """fp32 master weight for low-precision params (mp_* ops)."""
        if self._has_master(weight):
            weight_master_copy = weight.astype("float32")
            return (self.create_state(index, weight_master_copy), weight_master_copy)
        return self.create_state(index, weight)

    def dense_rule(self, state):
        """``(op name, constant hyperparameters)`` of the op in
        ndarray/op_impl_optimizer.py that updates one dense parameter
        whose state is ``state``: inputs ``(weight, grad, *state)``,
        keywords the constants, ``rescale_grad``, ``clip_gradient`` when
        set, and what `_step_hyper` gives. None: no such op."""
        return None

    def _step_hyper(self, index):
        """The op's keywords that change from step to step and from
        parameter to parameter (schedules, multipliers)."""
        return {"lr": self._get_lr(index), "wd": self._get_wd(index)}

    def _rule(self, state):
        rule = self.dense_rule(state)
        if rule is None or self.clip_gradient is None:
            return rule
        return rule[0], dict(rule[1], clip_gradient=self.clip_gradient)

    def update(self, index, weight, grad, state):
        rule = self._rule(state)
        if rule is None:
            raise NotImplementedError
        self._update_count(index)
        name, consts = rule
        kw = dict(consts, rescale_grad=self.rescale_grad,
                  **self._step_hyper(index))
        if isinstance(grad, _sparse.RowSparseNDArray) and name in _ROW_SPARSE_TWIN:
            getattr(_sparse, _ROW_SPARSE_TWIN[name])(
                weight, grad, *_leaves(state), **kw)
        else:
            _invoke(_get_op(name), [weight, grad, *_leaves(state)], kw,
                    out=weight)

    def update_multi_precision(self, index, weight, grad, state):
        if self._has_master(weight):
            inner_state, weight32 = state
            grad32 = grad.astype("float32")
            self.update(index, weight32, grad32, inner_state)
            weight32.copyto(weight)
        else:
            self.update(index, weight, grad, state)

    def update_multi(self, indices, weights, grads, states):
        """The whole parameter list in one call (Gluon ``Trainer.step``).
        Dense parameters of an optimizer that declares a ``dense_rule`` go
        through ONE compiled program (`_fused_update`: one dispatch, one
        ``invokes``, one ``mxtpu/op/fused_<op>`` span). The rest take the
        per-key loop: row-sparse gradients or weights, optimizers without
        a rule, and a subclass that overrides ``update`` (or
        ``update_multi_precision``) below the class that declared the
        rule, whose override the program would not run. Returns
        (fused, looped), the number of parameters each way."""
        cls = type(self)
        rule_cls = _definer(cls, "dense_rule")
        current = all(issubclass(rule_cls, _definer(cls, m))
                      for m in ("update", "update_multi_precision"))
        fused = []      # (index, weight, grad, state's arrays, master, rule)
        for i, w, g, s in zip(indices, weights, grads, states):
            inner, master = s if self._has_master(w) else (s, None)
            dense = current and not isinstance(w, _sparse.BaseSparseNDArray) \
                and not isinstance(g, _sparse.BaseSparseNDArray)
            rule = self._rule(inner) if dense else None
            if rule is None:
                self.update_multi_precision(i, w, g, s)
            else:
                fused.append((i, w, g, _leaves(inner), master,
                              (rule[0], tuple(sorted(rule[1].items())))))
        if fused:
            self._update_fused(*zip(*fused))
        looped = len(indices) - len(fused)
        _count("fused", len(fused))
        _count("looped", looped)
        return len(fused), looped

    def _update_fused(self, indices, weights, grads, states, masters, rules):
        hypers = []
        for i in indices:      # count, then read lr: as `update` does per key
            self._update_count(i)
            hypers.append(self._step_hyper(i))
        hyper = {k: np.array([h[k] for h in hypers], np.float32)
                 for k in hypers[0]}
        _count("invokes")
        with _op_span("fused_" + "+".join(sorted({name for name, _ in rules}))), \
                jax.default_device(weights[0].ctx.jax_device):
            new_w, new_s, new_m = _fused_update(
                rules, [_pinned(w) for w in weights], [_pinned(g) for g in grads],
                [tuple(_pinned(a) for a in st) for st in states],
                [None if m is None else _pinned(m) for m in masters],
                hyper, np.float32(self.rescale_grad))
        written = []
        for targets, arrays in ((weights, new_w), (masters, new_m),
                                *zip(states, new_s)):
            for t, a in zip(targets, arrays):
                if t is not None:
                    t._set_data(a)
                    written.append(a)
        _engine.on_dispatch(written)

    # -- lr/wd plumbing (mirrors reference semantics)
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("lr_scheduler is set; cannot set learning rate directly")
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index):
        if not isinstance(index, (list, tuple)):
            index = [index]
        for idx in index:
            if idx not in self._index_update_count:
                self._index_update_count[idx] = self.begin_num_update
            self._index_update_count[idx] += 1
            self.num_update = max(self._index_update_count[idx], self.num_update)

    def _get_lr(self, index):
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        name = self.idx2name.get(index, index)
        if name in self.param_dict:
            lr *= self.param_dict[name].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif name in self.lr_mult:
            lr *= self.lr_mult[name]
        return lr

    def _get_wd(self, index):
        wd = self.wd
        name = self.idx2name.get(index, index)
        if name in self.param_dict:
            wd *= self.param_dict[name].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif name in self.wd_mult:
            wd *= self.wd_mult[name]
        return wd


@register
class SGD(Optimizer):
    """SGD (+momentum, multi-precision) — sgd_update / sgd_mom_update ops."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.ctx, dtype=weight.dtype)

    def dense_rule(self, state):
        if state is None:
            return "sgd_update", {}
        return "sgd_mom_update", {"momentum": self.momentum,
                                  "lazy_update": self.lazy_update}


@register
class NAG(SGD):
    def dense_rule(self, state):
        if state is None:
            return "sgd_update", {}
        return "nag_mom_update", {"momentum": self.momentum}


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def _step_hyper(self, index):
        kw = super()._step_hyper(index)
        # bias correction folded into lr (reference Adam does the same)
        t = self._index_update_count[index]
        kw["lr"] *= math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        return kw

    def dense_rule(self, state):
        return "adam_update", {"beta1": self.beta1, "beta2": self.beta2,
                               "epsilon": self.epsilon,
                               "lazy_update": self.lazy_update}


@register
class AdamW(Adam):
    def dense_rule(self, state):
        return "adamw_update", {"beta1": self.beta1, "beta2": self.beta2,
                                "epsilon": self.epsilon}


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.ctx, dtype=weight.dtype)

    def dense_rule(self, state):
        return "adagrad_update", {"epsilon": self.float_stable_eps}


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def _step_hyper(self, index):
        return {"wd": self._get_wd(index)}     # the rule has no lr

    def dense_rule(self, state):
        return "adadelta_update", {"rho": self.rho, "epsilon": self.epsilon}


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                    zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                    zeros(weight.shape, weight.ctx, dtype=weight.dtype))
        return zeros(weight.shape, weight.ctx, dtype=weight.dtype)

    def dense_rule(self, state):
        consts = {"gamma1": self.gamma1, "epsilon": self.epsilon}
        if self.clip_weights:
            consts["clip_weights"] = self.clip_weights
        if self.centered:
            return "rmspropalex_update", dict(consts, gamma2=self.gamma2)
        return "rmsprop_update", consts


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def dense_rule(self, state):
        return "ftrl_update", {"lamda1": self.lamda1, "beta": self.beta}


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.ctx, dtype=weight.dtype)

    def dense_rule(self, state):
        if state is None:
            return "signsgd_update", {}
        return "signum_update", {"momentum": self.momentum,
                                 "wd_lh": self.wd_lh}


@register
class LAMB(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.ctx, dtype=weight.dtype),
                zeros(weight.shape, weight.ctx, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        mean, var = state
        kw = {"beta1": self.beta1, "beta2": self.beta2, "epsilon": self.epsilon,
              "t": t, "bias_correction": self.bias_correction,
              "wd": self._get_wd(index), "rescale_grad": self.rescale_grad}
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        g = _invoke(_get_op("lamb_update_phase1"), [weight, grad, mean, var], kw)
        r1 = weight.norm()
        r2 = g.norm()
        kw2 = {"lr": self._get_lr(index)}
        if self.lower_bound:
            kw2["lower_bound"] = self.lower_bound
        if self.upper_bound:
            kw2["upper_bound"] = self.upper_bound
        _invoke(_get_op("lamb_update_phase2"), [weight, g, r1, r2], kw2, out=weight)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        from .. import ndarray as nd
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        noise = nd.random.normal(0, math.sqrt(lr), weight.shape,
                                 dtype=str(weight.dtype), ctx=weight.ctx)
        weight._set_data(
            (weight - lr / 2 * (g + wd * weight) + noise)._data)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, weight.ctx, dtype=weight.dtype), weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        mom, previous_weight = state
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        comp = g + self.lamda * g * g * (weight - previous_weight)
        if mom is None:
            new = weight - lr * (comp + wd * weight)
        else:
            mom._set_data((self.momentum * mom - lr * (comp + wd * weight))._data)
            new = weight + mom
        previous_weight._set_data(weight._data)
        weight._set_data(new._data)


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise scaling (reference LBSGD).
    The trust ratio is read on the host, so ``update`` is its own."""

    def __init__(self, momentum=0.0, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32,
                 begin_epoch=0, num_epochs=60, **kwargs):
        super().__init__(momentum=momentum, **kwargs)
        self.warmup_strategy = warmup_strategy

    def update(self, index, weight, grad, state):
        # LARS trust ratio
        w_norm = float(weight.norm().asscalar())
        g_norm = float((grad * self.rescale_grad).norm().asscalar())
        trust = 1.0
        if w_norm > 0 and g_norm > 0:
            trust = 0.001 * w_norm / (g_norm + self._get_wd(index) * w_norm)
        saved_lr = self.lr
        try:
            if self.lr_scheduler is None:
                self.lr = self.lr * trust
            super().update(index, weight, grad, state)
        finally:
            self.lr = saved_lr


@register
class Test(Optimizer):
    """Trivial optimizer used by reference unit tests."""

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.ctx, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        weight._set_data((weight + grad * self.rescale_grad)._data)


class Updater:
    """Applies an optimizer by key — used by KVStore server-side updates
    (reference python/mxnet/optimizer/optimizer.py get_updater +
    kvstore server pickling round-trip)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}
        self.aggregate_updates = optimizer.aggregate_num > 0

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad, self.states[index])

    def update_multi(self, indices, grads, weights):
        """Aggregated entry (``Trainer.step``): `Optimizer.update_multi`
        over these keys' states; returns its (fused, looped)."""
        for index, weight in zip(indices, weights):
            if index not in self.states:
                self.states[index] = \
                    self.optimizer.create_state_multi_precision(index, weight)
                self.states_synced[index] = True
        return self.optimizer.update_multi(indices, weights, grads,
                                           [self.states[i] for i in indices])

    def set_states(self, states):
        payload = pickle.loads(states)
        if isinstance(payload, tuple) and len(payload) == 2:
            second = payload[1]
            if isinstance(second, Optimizer):
                # dump_optimizer=True payload: the optimizer itself
                # (with its schedules/num_update) rides along
                self.states, self.optimizer = payload
            else:
                self.states, self.optimizer.num_update = payload
        else:
            self.states = payload
        self.states_synced = {k: False for k in self.states}

    def get_states(self, dump_optimizer=False):
        return pickle.dumps(
            (self.states, self.optimizer.num_update) if not dump_optimizer
            else (self.states, self.optimizer))


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
