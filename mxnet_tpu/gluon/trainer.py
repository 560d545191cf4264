"""Gluon Trainer (python/mxnet/gluon/trainer.py analog).

Same contract as the reference: created over a ParameterDict + optimizer,
``step(batch_size)`` = allreduce gradients across devices/workers
(KVStore path) then apply the optimizer; supports ``update_on_kvstore``,
gradient rescale, sparse row pulls, save/load of optimizer states.

TPU mapping (SURVEY §3.2): on one process the per-context replicas are
chips of a slice, so _allreduce_grads sums replica gradients (XLA lowers
sharded sums to ICI AllReduce); multi-host uses a Dist KVStore whose
reduce rides DCN. The fused-step fast path (whole train step in one XLA
computation) lives in parallel/spmd.py and the benchmarks use it.
"""
from __future__ import annotations

from ..base import MXNetError
from .. import optimizer as opt
from .. import kvstore as _kvstore_mod
from .. import profiler as _profiler
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            self._param2idx[param.name] = i
            self._params.append(param)
            param._trainer = self
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_params = {
            "kvstore": kvstore, "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = list(self._params)

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer " \
                "instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _init_kvstore(self):
        config = self._kvstore_params
        kvstore = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        if kvstore is None:
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            kv = kvstore if not isinstance(kvstore, str) \
                else _kvstore_mod.create(kvstore)
            self._kvstore = kv
            if kv.type == "horovod":
                # the allreduce-only store never runs the optimizer
                # (reference trainer.py horovod branch)
                if update_on_kvstore:
                    raise ValueError(
                        "Cannot set update_on_kvstore=True when kvstore "
                        "is 'horovod'")
                update_on_kvstore = False
            elif update_on_kvstore is None:
                update_on_kvstore = kv.num_workers > 1
            self._update_on_kvstore = update_on_kvstore
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
        self._kv_initialized = True

    def _init_params(self):
        """Lazily register params with the kvstore once initialized."""
        pending = []
        for param in self._params_to_init:
            if param._deferred_init:
                pending.append(param)
                continue
            if self._kvstore is not None and self._uses_store(param):
                idx = self._param2idx[param.name]
                self._kvstore.init(idx, param.data())
        self._params_to_init = pending

    def _uses_store(self, param):
        """Will this parameter's key ever be pushed or pulled? A dense
        parameter with one replica on one worker, updated here and not on
        the store, never is (`_reduce_grads` sums replicas and workers
        only), and ``kvstore.init`` keeps a COPY of the value it is given:
        for a one-chip Trainer that was a second copy of every weight on
        the device for the life of the job (1.39 GB at 697 M bfloat16
        parameters), so such a parameter gets no slot."""
        return (self._update_on_kvstore or self._kvstore.num_workers > 1
                or len(param.list_ctx()) > 1
                or param._stype != "default"
                or param._grad_stype != "default")

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _row_sparse_pull(self, parameter, out, row_id, full_idx=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._kvstore is not None:
            idx = self._param2idx[parameter.name]
            self._kvstore.row_sparse_pull(idx, out=out, row_ids=row_id)

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce grads + update (reference Trainer.step)."""
        with _profiler.span("mxtpu/trainer/step", batch_size=batch_size) as sp:
            if not self._kv_initialized:
                self._init_kvstore()
            if self._params_to_init:
                self._init_params()
            self._optimizer.rescale_grad = self._scale / batch_size
            self._allreduce_grads()
            self._update(ignore_stale_grad)
            sp.set(step=self._optimizer.num_update)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), \
            "allreduce_grads() when parameters are updated on kvstore " \
            "is not supported. Try setting `update_on_kvstore` to False."
        self._allreduce_grads()

    def _allreduce_grads(self):
        with _profiler.span("mxtpu/trainer/allreduce") as sp:
            sp.set(keys=self._reduce_grads())

    def _reduce_grads(self):
        """Sum every multi-replica gradient in place; returns the number
        of keys (parameters) that took part."""
        if self._kvstore is None:
            # no kvstore configured, but multi-replica params still need
            # the sum — otherwise _update's update-once-and-broadcast
            # would silently drop every other replica's gradient
            from ..ndarray.sparse import BaseSparseNDArray
            from ..parallel import comm
            pending = []
            for param in self._params:
                if param.grad_req == "null":
                    continue
                g = param.list_grad()
                if len(g) > 1:
                    if isinstance(g[0], BaseSparseNDArray):
                        # reference contract: multi-device row_sparse
                        # training REQUIRES a kvstore (sparse grads
                        # cannot ride the dense stacked reduce)
                        raise MXNetError(
                            f"Parameter '{param.name}' has row_sparse "
                            "gradients on multiple contexts; Trainer "
                            "needs a kvstore for sparse multi-device "
                            "training (kvstore=None was given)")
                    pending.append(g)
            if pending:
                comm.reduce_grad_ndarrays_inplace(pending)
            return len(pending)
        if self._update_on_kvstore:
            pushed = 0
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    # push grads; optimizer runs in kvstore; pull weights
                    self._kvstore.push(i, param.list_grad())
                    pushed += 1
            return pushed
        # batch every key into ONE fused pushpull: the kvstore reduces the
        # whole gradient set in a single compiled XLA computation (the
        # kvstore_nccl.h fused-pushpull analog; bucketing is the
        # compiler's all-reduce combiner). Key order is the stable param
        # index order — identical on every worker by construction.
        keys, grads = [], []
        for i, param in enumerate(self._params):
            if param.grad_req != "null":
                g = param.list_grad()
                if len(g) > 1 or self._kvstore.num_workers > 1:
                    keys.append(i)
                    grads.append(g)
        if keys:
            self._kvstore.pushpull(keys, grads, out=grads)
        return len(keys)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), \
            "update() when parameters are updated on kvstore is not " \
            "supported. Try setting `update_on_kvstore` to False."
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        """Apply the optimizer ONCE per parameter (replica 0) and
        broadcast the new weight to the other replicas — gradients are
        identical after _allreduce_grads, so one update + copy keeps
        optimizer state/schedules exact (no shared-state mutation per
        replica) at the same traffic as a kvstore pull. The parameters go
        to `Optimizer.update_multi` in one call: one compiled program
        for the dense ones of an optimizer with a rule (``fused``), the
        per-key loop for the rest (``looped``)."""
        with _profiler.span("mxtpu/trainer/update") as sp:
            fused, looped = self._apply_updates(ignore_stale_grad)
            sp.set(params=fused + looped, fused=fused, looped=looped)

    def _apply_updates(self, ignore_stale_grad):
        """`_update`'s body; returns how many parameters the optimizer
        updated here (not those pulled from the kvstore), as
        `Optimizer.update_multi`'s (fused, looped)."""
        if self._update_on_kvstore and self._kvstore is not None:
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    # weights now live in the kvstore; pull them back
                    self._kvstore.pull(i, param.list_data(), ignore_sparse=False)
            return 0, 0
        idx, weights, grads = [], [], []
        for i, param in enumerate(self._params):
            if param.grad_req != "null":
                idx.append(i)
                weights.append(param.list_data())
                grads.append(param.list_grad()[0])
        counts = self._updaters[0].update_multi(
            idx, grads, [w[0] for w in weights])
        for src, *rest in weights:
            for dst in rest:
                src.copyto(dst)
        return counts

    def save_states(self, fname):
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
            # adopt the restored optimizer (it carries num_update /
            # index counts — resetting to the fresh one would restart
            # Adam bias correction and lr schedules)
            self._optimizer = self._updaters[0].optimizer
        self._optimizer.param_dict = {i: p for i, p in
                                      enumerate(self._params)}
