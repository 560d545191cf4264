"""KVStore — data-parallel parameter/gradient store.

API-compatible re-design of the reference KVStore
(include/mxnet/kvstore.h, src/kvstore/kvstore_local.h `KVStoreLocal`,
comm.h `CommCPU/CommDevice`, kvstore_nccl.h `KVStoreNCCL`,
kvstore_dist.h + ps-lite for multi-node) per SURVEY §5.8: one backend,
XLA collectives. Semantics preserved:

- ``init/push/pull/pushpull/broadcast``, ``set_optimizer``/``_set_updater``
  (update_on_kvstore), ``rank``/``num_workers``, sparse ``row_sparse_pull``;
- push aggregates the per-device values (the CommDevice reduce / NCCL
  allreduce analog) and either overwrites the stored value or runs the
  updater on it — matching KVStoreLocal::PushImpl;
- 'local'/'device'/'nccl' are single-process modes. On TPU the
  per-device gradient copies of one process are already on chips of one
  slice, so the reduce is a jitted sum that XLA lowers to ICI
  collectives when inputs are sharded (no P2P ring code: the XLA
  partitioner emits AllReduce).
- 'dist_sync'/'dist_async'/'dist_device_sync' are multi-process modes:
  ``jax.distributed.initialize`` (driven by tools/launch.py setting
  coordinator env vars — the dmlc tracker analog) gives every process
  the global device view; cross-host aggregation is a psum over the
  global mesh's data axis riding DCN. No server processes exist:
  `update_on_kvstore` means "run the optimizer on the aggregated value
  locally, identically on every worker" — bitwise-identical by SPMD
  construction, replacing the parameter-server role.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from . import envvars
from . import profiler as _profiler
from .base import MXNetError
from .ndarray import NDArray
from .ndarray.ndarray import _wrap
from .parallel import comm as _allreduce
from .telemetry import events as _events
from .telemetry import recorder as _recorder
from .telemetry import spans as _spans
from .telemetry.registry import REGISTRY as _REGISTRY
from .telemetry.trace import (current_trace_id as _current_trace_id,
                              new_trace_id as _new_trace_id)

__all__ = ["KVStore", "create"]


def _wire_metrics(side):
    """Registry families for the dist_async RPC channel, one set per
    side ('client' = worker RPCs, 'server' = the parameter server).
    Created lazily on first dist use — a local kvstore never touches
    them."""
    lat = _REGISTRY.histogram(
        f"mxnet_tpu_kvstore_{side}_rpc_ms",
        f"dist_async {side}-observed RPC latency by op", ("op",))
    byt = _REGISTRY.counter(
        f"mxnet_tpu_kvstore_{side}_bytes_total",
        f"dist_async {side} wire bytes by op and direction",
        ("op", "direction"))
    return lat, byt


def create(name="local") -> "KVStore":
    """mx.kv.create factory (src/kvstore/kvstore.cc KVStore::Create)."""
    name = name.lower()
    if name in ("local", "local_update_cpu", "local_allreduce_cpu",
                "device", "local_allreduce_device", "nccl"):
        return KVStore(name)
    if name == "dist_async":
        return AsyncDistKVStore()
    if name in ("dist_sync", "dist_device_sync", "dist_sync_device", "dist"):
        return DistKVStore(name)
    if name == "horovod":
        return HorovodKVStore()
    raise MXNetError(f"unknown kvstore type {name!r}")


class KVStore:
    """Single-process store: aggregates across this process's devices."""

    def __init__(self, kind="local"):
        self._kind = kind
        self._store: dict = {}
        self._updater = None
        self._optimizer = None
        self._grad_compression = None
        # error-feedback residual state per reduce signature (stacked
        # sharded arrays living on their devices)
        self._comp_state: dict = {}

    # -- identity ----------------------------------------------------------
    @property
    def type(self):
        return self._kind

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    # -- core ops ----------------------------------------------------------
    def init(self, key, value):
        keys, values = _normalize(key, value)
        for k, v in zip(keys, values):
            vs = v if isinstance(v, (list, tuple)) else [v]
            if k in self._store:
                raise MXNetError(f"key {k} already initialized")
            self._store[k] = vs[0].copy()

    def push(self, key, value, priority=0):
        keys, values = _normalize(key, value)
        for k, v in zip(keys, values):
            merged = self._reduce(v if isinstance(v, (list, tuple)) else [v],
                                  key=k)
            self._apply(k, merged)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, outs = _normalize(key, out)
        for k, o in zip(keys, outs):
            stored = self._get(k)
            for dst in (o if isinstance(o, (list, tuple)) else [o]):
                stored.copyto(dst)

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull (reference MXKVStorePushPullEx / the NCCL
        fused-pushpull of kvstore_nccl.h). When no server-side updater is
        set, ALL keys reduce in ONE compiled XLA computation
        (parallel/comm.py) whose all-reduces the compiler buckets —
        Trainer.step dispatches exactly one executable per step."""
        keys, values = _normalize(key, value)
        outs = values if out is None else _normalize(key, out)[1]
        with _profiler.span("mxtpu/kvstore/pushpull", keys=len(keys)) as sp:
            if sp.live:
                sp.set(bytes=_payload_bytes(values))
            if self._updater is None and \
                    self._try_fused_pushpull(keys, values, outs):
                return
            self.push(key, value, priority)
            self.pull(key, out if out is not None else value, priority)

    # -- fused reduce fast path -------------------------------------------
    def _reduce_devices(self, value_lists):
        """Participating device tuple for the fused reduce, or None when
        the layout doesn't qualify. Single-process: the devices of the
        per-context replicas (must agree across keys)."""
        if not _allreduce.can_fast_reduce(value_lists):
            return None
        devs = tuple(v.device for v in value_lists[0])
        return devs if len(devs) > 1 else None

    def _try_fused_pushpull(self, keys, values, outs) -> bool:
        from .ndarray import sparse as _sp
        vlists = []
        for v in values:
            vs = v if isinstance(v, (list, tuple)) else [v]
            if any(isinstance(a, _sp.BaseSparseNDArray) for a in vs):
                return False
            vlists.append([a._data for a in vs])
        devices = self._reduce_devices(vlists)
        if devices is None:
            return False
        # every read-back target must sit inside the reduce mesh; a
        # stored value or out on a foreign device takes the copyto path
        devset = set(devices)
        for k, o in zip(keys, outs):
            if self._get(k)._data.device not in devset:
                return False
            for dst in (o if isinstance(o, (list, tuple)) else [o]):
                if dst._data.device not in devset:
                    return False
        reduced = self._compiled_reduce(tuple(keys), vlists, devices)
        for k, garr, o in zip(keys, reduced, outs):
            stored = self._get(k)
            sh = _allreduce.shard_for_device(garr, stored._data.device)
            stored._set_data(sh.astype(stored._data.dtype)
                             if sh.dtype != stored._data.dtype else sh)
            for dst in (o if isinstance(o, (list, tuple)) else [o]):
                sh = _allreduce.shard_for_device(garr, dst._data.device)
                dst._set_data(sh.astype(dst._data.dtype)
                              if sh.dtype != dst._data.dtype else sh)
        return True

    def broadcast(self, key, value, out=None, priority=0):
        self.init(key, value)
        if out is not None:
            self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows (sparse embedding path —
        reference kvstore sparse pull, src/kvstore/kvstore_local.h
        unique-rowid merge). TPU-native: sort/unique/gather run
        ON-DEVICE (XLA); the only host sync is the unique count that
        sizes the row_sparse result — no value round-trips through
        numpy (the Wide&Deep hot loop stays on-chip)."""
        import jax.numpy as jnp
        from .ndarray import sparse as _sp
        keys, outs = _normalize(key, out)
        _, rids = _normalize(key, row_ids)
        for k, o, r in zip(keys, outs, rids):
            stored = self._get(k)
            dense = stored.todense()._data \
                if isinstance(stored, _sp.BaseSparseNDArray) else stored._data
            dsts = o if isinstance(o, (list, tuple)) else [o]
            rows = r if isinstance(r, (list, tuple)) else [r] * len(dsts)
            for dst, rid in zip(dsts, rows):
                ids = rid._data.reshape(-1).astype(jnp.int64)
                uniq = jnp.unique(ids)
                picked = jnp.take(dense, uniq, axis=0)
                if isinstance(dst, _sp.RowSparseNDArray):
                    # rebuild the row_sparse triple in place
                    dst._data = picked.astype(dst._data.dtype)
                    dst._aux = uniq
                    dst._version += 1
                else:
                    full = jnp.zeros(stored.shape, dst.dtype)
                    full = full.at[uniq].set(picked.astype(dst.dtype))
                    dst._set_data(full)

    # -- optimizer / updater ----------------------------------------------
    def set_optimizer(self, optimizer):
        from .optimizer import get_updater
        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def _set_updater(self, updater):
        self._updater = updater

    def set_gradient_compression(self, compression_params):
        """Enable compressed gradient reduce with error feedback
        (reference src/kvstore/gradient_compression.cc 2-bit path).
        {'type': '2bit', 'threshold': t} maps each (grad+residual)
        element to {±t, 0}; {'type': 'int8'} uses symmetric per-tensor
        int8 with in-graph scales. The quantize/residual-update/reduce
        pipeline compiles into the fused all-reduce program
        (parallel/comm.py reduce_compressed_replica_lists)."""
        params = dict(compression_params)
        ctype = params.get("type", "2bit")
        if ctype not in ("2bit", "int8", "none"):
            raise MXNetError(f"unsupported gradient compression type {ctype!r}")
        self._grad_compression = None if ctype == "none" else params
        self._comp_state.clear()

    # -- optimizer state io (reference save/load via updater pickle) ------
    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("there is no optimizer set to this kvstore")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("there is no optimizer set to this kvstore")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    # -- telemetry ---------------------------------------------------------
    def expose(self, port=0, host="127.0.0.1"):
        """Start a telemetry exposition server for this store's
        process: Prometheus ``/metrics`` off the process registry
        (dist_async RPC latency/bytes land there on both ends),
        ``/healthz`` from :meth:`_healthz`, and ``/stats`` with store
        identity + key count. ``port=0`` picks a free port."""
        from .telemetry.expo import TelemetryServer

        if getattr(self, "_expo", None) is None:
            def stats():
                return {"type": self.type, "rank": self.rank,
                        "num_workers": self.num_workers,
                        "keys": len(self._store)}

            self._expo = TelemetryServer(healthz_fn=self._healthz,
                                         stats_fn=stats,
                                         port=port, host=host)
            _events.emit("telemetry_expose", component="kvstore",
                         port=self._expo.port, host=self._expo.host)
        return self._expo

    def _healthz(self):
        return True, {"type": self.type, "rank": self.rank}

    # -- internals ---------------------------------------------------------
    def _get(self, k):
        if k not in self._store:
            raise MXNetError(f"key {k} was not initialized")
        return self._store[k]

    def _compiled_reduce(self, sig, vlists, devices):
        """Fused reduce for a batch of keys — compressed (with
        per-signature error-feedback state) when set_gradient_compression
        configured a supported type, plain stacked-sum otherwise."""
        comp = self._grad_compression
        if comp and comp.get("type") in ("2bit", "int8"):
            state_key = (sig, devices,
                         tuple((tuple(v[0].shape), str(v[0].dtype))
                               for v in vlists))
            reduced, new_res = _allreduce.reduce_compressed_replica_lists(
                vlists, self._comp_state.get(state_key), devices=devices,
                ctype=comp["type"],
                threshold=float(comp.get("threshold", 0.5)))
            self._comp_state[state_key] = new_res
            return reduced
        return _allreduce.reduce_replica_lists(vlists, devices=devices)

    def _reduce(self, arrays, key=None):
        """Sum per-device values — a single compiled stacked-sum whose
        output sharding is replicated, which the XLA SPMD partitioner
        lowers to an ICI AllReduce (the CommDevice/NCCL analog)."""
        from .ndarray import sparse as _sp
        if any(isinstance(a, _sp.RowSparseNDArray) for a in arrays):
            return _merge_row_sparse(arrays)
        merged = arrays[0]
        if len(arrays) > 1:
            datas = [a._data for a in arrays]
            devices = self._reduce_devices([datas])
            if devices is not None:
                garr = self._compiled_reduce((key,), [datas], devices)[0]
                return _wrap(_allreduce.shard_for_device(garr, datas[0].device),
                             merged.ctx)
            # fallback: replicas sharing a device (tests) — eager add tree
            ctx = merged.ctx
            acc = merged._data
            for a in arrays[1:]:
                other = a._data
                if other.device != acc.device:
                    other = jax.device_put(other, acc.device)
                acc = acc + other
            merged = _wrap(acc, ctx)
        return merged

    def _apply(self, k, merged):
        from .ndarray import sparse as _sp
        stored = self._get(k)
        if isinstance(merged, _sp.BaseSparseNDArray):
            # keep the sparse type intact: the updater's optimizer routes
            # row_sparse grads to the lazy rsp update rules (astype would
            # silently strip indices and corrupt the update)
            if self._updater is not None:
                self._updater(k, merged, stored)
            else:
                stored._set_data(
                    merged.todense()._data.astype(stored.dtype))
            return
        if self._updater is not None:
            self._updater(k, merged.astype(stored.dtype), stored)
        else:
            stored._set_data(merged._data.astype(stored.dtype))

    def __repr__(self):
        return f"<KVStore {self._kind} rank={self.rank}/{self.num_workers}>"


class DistKVStore(KVStore):
    """Multi-process store over jax.distributed (the ps-lite analog —
    but serverless: every worker holds the aggregated value by SPMD)."""

    def __init__(self, kind="dist_sync"):
        super().__init__(kind)
        self._initialized = _maybe_init_distributed()

    @property
    def rank(self):
        return jax.process_index() if self._initialized else 0

    @property
    def num_workers(self):
        return jax.process_count() if self._initialized else 1

    def _reduce_devices(self, value_lists):
        """Cross-process fused reduce: when every process's local arrays
        cover exactly its addressable devices, the global device list
        forms the 1-D reduce mesh and the compiled sum IS the DCN/ICI
        AllReduce (every worker runs the same SPMD program — no server,
        no host gather)."""
        if self.num_workers > 1:
            if not _allreduce.can_fast_reduce(value_lists):
                return None
            if len(value_lists[0]) == jax.local_device_count():
                return tuple(jax.devices())
            return None
        return super()._reduce_devices(value_lists)

    def _reduce(self, arrays, key=None):
        if self.num_workers > 1:
            datas = [a._data for a in arrays]
            devices = self._reduce_devices([datas])
            if devices is not None:
                garr = self._compiled_reduce((key,), [datas], devices)[0]
                return _wrap(_allreduce.shard_for_device(garr, datas[0].device),
                             arrays[0].ctx)
            return _cross_process_allreduce(super()._reduce(arrays, key=key))
        return super()._reduce(arrays, key=key)

    def barrier(self):
        """_barrier analog (ps-lite Barrier): sync all workers."""
        if self.num_workers > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("mxnet_tpu_kvstore_barrier")


class _ParameterServer:
    """Host-side parameter server (the ps-lite server role) for
    ``dist_async``: runs as a daemon thread in worker 0's process,
    speaking length-prefixed TYPED frames over TCP (``_wire_encode`` —
    plain data + raw ndarray bytes, nothing executable; and the socket
    binds the launcher-announced interface, not 0.0.0.0). State and
    updates live
    in a plain local :class:`KVStore` on host-CPU NDArrays — exactly
    the reference's CPU server-side update path
    (src/kvstore/kvstore_dist_server.h); workers push gradients and
    pull weights with NO inter-worker synchronization, so updates
    apply in arrival order (stale gradients by design — the dist_async
    contract)."""

    def __init__(self, host, port, num_workers):
        import socket
        import threading
        import time as _time

        self._store = KVStore("local")
        self._lock = threading.Lock()
        self._opt_payload = None
        self._num_workers = num_workers
        self._barrier_count = 0
        self._barrier_cv = threading.Condition()
        self._barrier_gen = 0
        # watchdog surface: per-connection in-flight handles (thread
        # ident -> (op, started)) and a last-served heartbeat so a
        # handle wedged in an optimizer update is detectable; own lock
        # because handler threads mutate it while the watchdog reads
        self._inflight = {}
        self._inflight_lock = threading.Lock()
        self._last_handle = _time.monotonic()
        _REGISTRY.gauge(
            "mxnet_tpu_kvstore_server_last_handle_age_s",
            "seconds since the parameter server last served an RPC"
        ).set_function(lambda: _time.monotonic() - self._last_handle)
        _recorder.install()
        _recorder.register_probe("kvstore_server", self._watchdog_probe)
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            srv.bind((host, port))
        except OSError:
            # the launcher-announced address may be a NAT/bridged
            # front address not assigned to any local interface
            # (containerized deployments); availability beats the
            # narrower bind there — fall back loudly to all interfaces
            import sys
            print(f"mxnet_tpu dist_async server: cannot bind "
                  f"{host}:{port} locally; falling back to 0.0.0.0",
                  file=sys.stderr)
            srv.bind(("0.0.0.0", port))
        srv.listen(num_workers + 2)
        self._srv = srv
        threading.Thread(target=self._accept_loop,
                         name="mxnet_tpu_kvstore_accept",
                         daemon=True).start()

    def _accept_loop(self):
        import threading
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             name=f"mxnet_tpu_kvstore_serve_fd{conn.fileno()}",
                             daemon=True).start()

    def _watchdog_probe(self):
        """Anomaly when any in-flight handle has been running past the
        stall threshold (an optimizer update or store op wedged)."""
        import time as _time
        now = _time.monotonic()
        stall = _recorder.stall_seconds()
        with self._inflight_lock:
            inflight = list(self._inflight.values())
        for op, started in inflight:
            if now - started > stall:
                return {"kind": "kvstore_server_stall", "op": op,
                        "seconds_in_flight": round(now - started, 3)}
        return None

    def _serve(self, conn):
        import threading
        import time as _time
        lat, byt = _wire_metrics("server")
        try:
            while True:
                sized = _recv_msg_sized(conn)
                if sized is None:
                    return
                msg, nbytes_in = sized
                if not isinstance(msg, tuple) or len(msg) not in (3, 4, 5):
                    raise ValueError(
                        "RPC frame must be (op, key, payload[, trace_id"
                        f"[, span_id]]), got {type(msg).__name__}")
                op, key, payload = msg[:3]
                # trace id rides the frame (4th field) so this handle
                # correlates with the worker-side rpc event on one
                # push; the 5th field (new) is the worker's RPC span
                # id, which this handle span parents under — a
                # cross-process span tree on one trace
                tid = msg[3] if len(msg) >= 4 else None
                remote_span = msg[4] if len(msg) >= 5 else None
                opname = op if isinstance(op, str) else "?"
                t0 = _time.perf_counter()
                handle_span = _spans.start_span(
                    f"kvstore/server/{opname}", trace_id=tid,
                    parent_id=remote_span, local_root=True,
                    attrs={"op": opname, "key": key,
                           "bytes_in": nbytes_in})
                me = threading.get_ident()
                with self._inflight_lock:
                    self._inflight[me] = (opname, _time.monotonic())
                try:
                    with _spans.use_span(handle_span):
                        try:
                            reply = ("ok", self._handle(op, key, payload))
                        except (ConnectionError, EOFError, OSError):
                            raise
                        except Exception as e:  # reply, don't kill the
                            import traceback    # server
                            reply = ("err", f"{e!r}\n"
                                     f"{traceback.format_exc(limit=5)}")
                    nbytes_out = _send_msg(conn, reply)
                    handle_span.end(status="ok" if reply[0] == "ok"
                                    else "error",
                                    error=None if reply[0] == "ok"
                                    else str(reply[1])[:200])
                finally:
                    with self._inflight_lock:
                        self._inflight.pop(me, None)
                    self._last_handle = _time.monotonic()
                    # end() is idempotent (first end wins): on success
                    # the real status was already recorded above and
                    # this is a no-op; it only closes the span when
                    # handle/send blew up, so a dropped connection
                    # can't pin the trace's active buffer with an open
                    # local root forever
                    handle_span.end(error="connection lost mid-handle")
                ms = (_time.perf_counter() - t0) * 1e3
                lat.labels(op=opname).observe(ms)
                byt.labels(op=opname, direction="in").inc(nbytes_in)
                byt.labels(op=opname, direction="out").inc(nbytes_out)
                _events.emit("kvstore_server_handle", op=opname, key=key,
                             ms=round(ms, 3), bytes_in=nbytes_in,
                             bytes_out=nbytes_out, ok=reply[0] == "ok",
                             trace_id=tid, span_id=handle_span.span_id,
                             parent_span_id=remote_span)
        except (ConnectionError, EOFError, OSError):
            return
        except (ValueError, MXNetError) as e:
            # malformed/refused wire frame: drop THIS client, keep
            # serving the rest (and leave a trace for the operator)
            import sys
            _REGISTRY.counter(
                "mxnet_tpu_kvstore_wire_refusals_total",
                "dist_async frames refused by the typed codec").inc()
            _events.emit("wire_frame_refused", error=str(e))
            print(f"mxnet_tpu dist_async server: dropping connection on "
                  f"bad frame: {e}", file=sys.stderr)
            return
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _handle(self, op, key, payload):
        from .context import cpu as _cpu
        from . import ndarray as _ndmod
        if op == "init":
            with self._lock:
                if key not in self._store._store:
                    self._store.init(key, _ndmod.array(payload, ctx=_cpu(0)))
            return None
        if op == "push":
            with self._lock:
                # the server-side optimizer update is the dist_async
                # hot path; its span parents under this handle (which
                # parents under the worker's RPC span across the wire)
                with _spans.span("kvstore/server/optimizer_update",
                                 key=key,
                                 updater=self._store._updater is not None):
                    self._store.push(key,
                                     _ndmod.array(payload, ctx=_cpu(0)))
            return None
        if op == "pull":
            with self._lock:
                return self._store._get(key).asnumpy()
        if op == "setopt":
            with self._lock:
                # replace on a genuinely different optimizer (resets
                # updater state, as setting a new optimizer should);
                # equal re-sends from other workers are idempotent
                if payload != self._opt_payload:
                    from . import optimizer as _optmod
                    name, attrs, sched_spec = payload
                    opt = _optmod.create(name)
                    for k, v in attrs.items():
                        setattr(opt, k, dict(v) if isinstance(v, dict)
                                else v)
                    if sched_spec is not None:
                        opt.lr_scheduler = _rebuild_wire_scheduler(
                            sched_spec)
                    self._opt_payload = payload
                    self._store.set_optimizer(opt)
                    _events.emit("kvstore_optimizer_update", kind="setopt",
                                 optimizer=name)
            return None
        if op == "optattr":
            # per-step optimizer attribute sync (rescale_grad changes on
            # every Trainer.step; the pickled optimizer would go stale)
            name, value = payload
            with self._lock:
                if self._store._optimizer is not None:
                    setattr(self._store._optimizer, name, value)
            _events.emit("kvstore_optimizer_update", kind="optattr",
                         attr=name, value=value)
            return None
        if op == "barrier":
            with self._barrier_cv:
                gen = self._barrier_gen
                self._barrier_count += 1
                if self._barrier_count >= self._num_workers:
                    self._barrier_count = 0
                    self._barrier_gen += 1
                    self._barrier_cv.notify_all()
                elif not self._barrier_cv.wait_for(
                        lambda: self._barrier_gen != gen, timeout=300.0):
                    # a silent 'ok' after timeout would let the caller
                    # proceed on orderings the barrier was guarding
                    self._barrier_count -= 1
                    raise MXNetError(
                        "dist_async barrier timed out after 300 s "
                        "(a worker is stuck or gone)")
            return None
        raise MXNetError(f"unknown op {op!r}")


# -- dist_async wire codec ------------------------------------------------
# The typed, NON-EXECUTABLE frame codec was born here (replacing the
# pickled frames whose decode was remote code execution) and now lives
# in mxnet_tpu/serving/wire.py, shared with the serving dispatch wire.
# These thin wrappers keep kvstore's historical names — tests and the
# 2-process workers import them from here — and pin the dist_async
# channel's own frame cap. The import is lazy on purpose: kvstore
# loads BEFORE the serving package during `import mxnet_tpu`, and at
# RPC time everything is initialized.
_WIRE_MAX_FRAME = 1 << 33          # 8 GiB: no 'length bomb' allocations


def _wire_mod():
    from .serving import wire
    return wire


def _wire_encode(obj) -> bytes:
    return _wire_mod().wire_encode(obj)


def _wire_decode(data) -> object:
    return _wire_mod().wire_decode(data)


def _send_msg(sock, obj):
    """Encode + length-prefix + send; returns the frame's byte size so
    callers can account wire traffic without re-encoding."""
    return _wire_mod().send_frame(sock, obj, max_frame=_WIRE_MAX_FRAME)


def _recv_msg_sized(sock):
    """(decoded object, frame bytes) — None on a cleanly closed peer.
    An over-cap length prefix raises FrameTooLargeError (an MXNetError
    AND a ValueError, matching both historical refusal paths)."""
    return _wire_mod().recv_frame(sock, max_frame=_WIRE_MAX_FRAME)


def _recv_msg(sock):
    sized = _recv_msg_sized(sock)
    return sized[0] if sized is not None else None


def _optimizer_wire_spec(optimizer):
    """(registry name, scalar attr table, scheduler spec) — what
    set_optimizer sends instead of a pickled object. The server
    rebuilds via ``optimizer.create(name)`` and overwrites every
    scalar (and dict-of-scalar: lr_mult/wd_mult/idx2name) attribute,
    so tuned hyperparameters survive the wire. The lr_scheduler rides
    the same way — (class name in mxnet_tpu.lr_scheduler, scalar/list
    attr table) — because server-side updates must follow the SCHEDULED
    lr as the server's num_update advances (the pickled path did; a
    spec that dropped it would silently train at the base lr forever).
    Device-backed state (param_dict) and anything else callable does
    not ride — same trade the reference made sending the optimizer
    STRING to ps-lite servers."""
    def scalar(v):
        return v is None or isinstance(v, (bool, int, float, str))

    def listy(v):
        return (isinstance(v, (list, tuple))
                and all(scalar(x) for x in v))

    attrs = {}
    for k, v in vars(optimizer).items():
        if k in ("param_dict", "lr_scheduler", "sym"):
            continue
        if scalar(v):
            attrs[k] = v
        elif isinstance(v, dict) and all(
                scalar(kk) and scalar(vv) for kk, vv in v.items()):
            attrs[k] = v
    sched = getattr(optimizer, "lr_scheduler", None)
    sched_spec = None
    if sched is not None:
        sattrs = {k: (list(v) if listy(v) and not scalar(v) else v)
                  for k, v in vars(sched).items()
                  if scalar(v) or listy(v)}
        sched_spec = (type(sched).__name__, sattrs)
    return (type(optimizer).__name__.lower(), attrs, sched_spec)


def _rebuild_wire_scheduler(sched_spec):
    """Server side: rebuild the lr scheduler from its typed spec.
    Only classes defined in mxnet_tpu.lr_scheduler are eligible —
    the name is a lookup in ONE trusted module, never an import."""
    from . import lr_scheduler as _lrs
    cls_name, sattrs = sched_spec
    cls = getattr(_lrs, cls_name, None)
    if not (isinstance(cls, type) and issubclass(cls, _lrs.LRScheduler)):
        raise MXNetError(f"unknown lr scheduler {cls_name!r} on the wire")
    sched = cls.__new__(cls)    # attr bag; __call__ reads attrs only
    for k, v in sattrs.items():
        setattr(sched, k, list(v) if isinstance(v, tuple) else v)
    return sched


class AsyncDistKVStore(KVStore):
    """``dist_async``: true asynchronous multi-process training
    (reference dist_async semantics, src/kvstore/kvstore_dist.h with
    server-side updates): worker 0's process hosts a TCP parameter
    server; every worker pushes gradients (applied on arrival — no
    gradient aggregation barrier, no lockstep between workers) and
    pulls the latest weights. Progress is per-worker; staleness is the
    accepted trade, exactly as in the reference. jax.distributed is
    NOT required — the PS channel is plain host TCP (DCN), keeping the
    accelerators free for compute."""

    def __init__(self):
        super().__init__("dist_async")
        import socket
        import time as _time
        self._rank = int(envvars.get_raw("MXNET_TPU_PROC_ID")
                         or os.environ.get("DMLC_WORKER_ID") or 0)
        self._n = int(envvars.get_raw("MXNET_TPU_NUM_PROCS")
                      or os.environ.get("DMLC_NUM_WORKER") or 1)
        host = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
        # the jax.distributed coordinator (dist_sync) owns ROOT_PORT;
        # the async server claims a fixed offset above it
        port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9000")) + 1717
        self._server = None
        if self._rank == 0 and self._n > 1:
            # bind the launcher-announced interface (the address every
            # worker dials), NOT 0.0.0.0 — the parameter store should
            # not listen on interfaces the job never asked for
            self._server = _ParameterServer(host, port, self._n)
        import threading
        self._rpc_lock = threading.Lock()
        self._wire_metrics = _wire_metrics("client")
        self._sent_optattrs = {}
        self._sock = None
        self._rpc_inflight = None      # (op, monotonic started) or None
        if self._n > 1:
            _recorder.install()
            _recorder.register_probe(f"kvstore_worker_{self._rank}",
                                     self._rpc_watchdog_probe)
            deadline = _time.monotonic() + 60.0
            last = None
            while _time.monotonic() < deadline:
                try:
                    s = socket.create_connection((host, port), timeout=5.0)
                    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    s.settimeout(None)  # barriers block far past the
                    # connect timeout; blocking mode for the RPC stream
                    self._sock = s
                    break
                except OSError as e:
                    last = e
                    _time.sleep(0.2)
            if self._sock is None:
                raise MXNetError(
                    f"dist_async worker {self._rank} could not reach the "
                    f"parameter server at {host}:{port}: {last!r}")

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._n

    def _healthz(self):
        """dist_async liveness: this worker still holds its server
        connection; on rank 0, the parameter server socket is open."""
        detail = {"type": self.type, "rank": self._rank,
                  "workers": self._n}
        ok = self._n <= 1 or self._sock is not None
        if self._server is not None:
            srv_up = self._server._srv.fileno() != -1
            detail["server_listening"] = srv_up
            ok = ok and srv_up
        return ok, detail

    def _rpc_watchdog_probe(self):
        """Anomaly when one RPC has been in flight past the stall
        threshold — the server stopped answering (stale heartbeat from
        this worker's point of view)."""
        import time as _time
        inflight = self._rpc_inflight
        if inflight is None:
            return None
        op, started = inflight
        waited = _time.monotonic() - started
        if waited > _recorder.stall_seconds():
            return {"kind": "kvstore_rpc_stall", "op": op,
                    "rank": self._rank,
                    "seconds_in_flight": round(waited, 3)}
        return None

    def _rpc(self, op, key, payload=None):
        import time as _time
        # the active trace id (a serving request, a Trainer step's
        # scope) rides the frame; an RPC outside any context mints its
        # own so worker- and server-side logs still correlate. The RPC
        # span's id rides as the 5th frame field — the server's handle
        # span parents under it, one tree across two processes.
        with _spans.span(f"kvstore/rpc/{op}", op=op, key=key,
                         rank=self._rank) as sp:
            tid = _current_trace_id() or sp.trace_id \
                or _new_trace_id("kv")
            t0 = _time.perf_counter()
            with self._rpc_lock:
                # read + check the socket INSIDE the lock: a concurrent
                # RPC that lost the connection nulls it, and a waiter
                # must see MXNetError, not _send_msg(None) blowing up
                sock = self._sock
                if sock is None:
                    raise MXNetError(
                        "dist_async parameter server connection is down "
                        f"(lost on an earlier RPC); cannot send {op!r}")
                self._rpc_inflight = (op, _time.monotonic())
                try:
                    # _rpc_lock IS the socket mutex: request/reply pairs
                    # from concurrent pushers must not interleave on one
                    # TCP stream, so holding it across the round trip is
                    # the design, not an accident
                    # mxlint: disable=lock-blocking-call
                    nbytes_out = _send_msg(
                        sock, (op, key, payload, tid, sp.span_id))
                    sized = _recv_msg_sized(sock)  # mxlint: disable=lock-blocking-call
                except OSError:
                    self._sock = None   # /healthz must see the loss
                    raise
                finally:
                    self._rpc_inflight = None
                if sized is None:
                    # half-closed peer: mark the connection dead so
                    # liveness probes (and later RPCs) report it
                    # instead of a live sock
                    self._sock = None
            if sized is None:
                raise MXNetError(
                    "dist_async parameter server connection lost "
                    f"(worker 0's process gone?) during {op!r}")
            reply, nbytes_in = sized
            ms = (_time.perf_counter() - t0) * 1e3
            sp.set_attr(bytes_out=nbytes_out, bytes_in=nbytes_in)
            lat, byt = self._wire_metrics
            lat.labels(op=op).observe(ms)
            byt.labels(op=op, direction="out").inc(nbytes_out)
            byt.labels(op=op, direction="in").inc(nbytes_in)
            _events.emit("kvstore_rpc", op=op, key=key, ms=round(ms, 3),
                         bytes_out=nbytes_out, bytes_in=nbytes_in,
                         rank=self._rank, trace_id=tid,
                         span_id=sp.span_id)
            status, out = reply
            if status != "ok":
                raise MXNetError(f"dist_async server error: {out}")
            return out

    def init(self, key, value):
        if self._n <= 1:
            return super().init(key, value)
        keys, values = _normalize(key, value)
        for k, v in zip(keys, values):
            vs = v if isinstance(v, (list, tuple)) else [v]
            self._rpc("init", k, vs[0].asnumpy())
            # local replica for pulls into stored dtype/shape checks
            self._store[k] = vs[0].copy()

    def _sync_optattrs(self):
        """Mirror scalar optimizer attributes the worker mutates after
        set_optimizer through the optattr RPC, so the server's copy
        applies the CURRENT values: rescale_grad changes on every
        Trainer.step, lr/wd via Trainer.set_learning_rate /
        setattr(trainer.optimizer, 'wd', ...) — without this the
        server would keep applying the pickled-at-setopt values
        forever."""
        opt = self._optimizer
        if opt is None:
            return
        for name in ("rescale_grad", "lr", "wd"):
            val = getattr(opt, name, None)
            if val is not None and val != self._sent_optattrs.get(name):
                self._rpc("optattr", None, (name, val))
                self._sent_optattrs[name] = val

    def push(self, key, value, priority=0):
        if self._n <= 1:
            return super().push(key, value, priority)
        # the server applies updates with ITS optimizer copy — mirror
        # the attributes Trainer mutates per step before the gradients
        # they govern arrive
        self._sync_optattrs()
        keys, values = _normalize(key, value)
        for k, v in zip(keys, values):
            merged = self._reduce(v if isinstance(v, (list, tuple))
                                  else [v], key=k)
            self._rpc("push", k, merged.asnumpy())

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if self._n <= 1:
            return super().pull(key, out, priority, ignore_sparse)
        from . import ndarray as _ndmod
        keys, outs = _normalize(key, out)
        for k, o in zip(keys, outs):
            arr = self._rpc("pull", k)
            for dst in (o if isinstance(o, (list, tuple)) else [o]):
                _ndmod.array(arr, ctx=dst.ctx,
                             dtype=str(dst.dtype)).copyto(dst)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        self.pull(key, out if out is not None else value, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        if self._n > 1:
            # the base implementation reads the LOCAL replica — refresh
            # it from the server first or sparse pulls would return
            # frozen init-time weights forever
            from . import ndarray as _ndmod
            keys, _ = _normalize(key, out)
            for k in keys:
                arr = self._rpc("pull", k)
                stored = self._store.get(k)
                if stored is None:
                    self._store[k] = _ndmod.array(arr)
                else:
                    _ndmod.array(arr, ctx=stored.ctx,
                                 dtype=str(stored.dtype)).copyto(stored)
        return super().row_sparse_pull(key, out, priority, row_ids)

    def set_optimizer(self, optimizer):
        if self._n <= 1:
            return super().set_optimizer(optimizer)
        # typed (name, scalar-attr-table) spec — nothing executable
        # crosses the wire; device-backed param_dict never rides (the
        # reference sends the optimizer string to servers the same way)
        self._rpc("setopt", None, _optimizer_wire_spec(optimizer))
        self._optimizer = optimizer  # tracked for per-step attr sync
        self._sent_optattrs = {}     # new server copy: resend attrs

    def barrier(self):
        if self._n > 1:
            self._rpc("barrier", None)


class HorovodKVStore(DistKVStore):
    """``kvstore='horovod'`` shim (reference python/mxnet/kvstore.py
    KVStoreHorovod, v>=1.5): the allreduce-only store. Upstream it
    delegates broadcast/pushpull to horovod.mxnet (MPI/NCCL rings) and
    supports ONLY ``broadcast`` + ``pushpull`` — no push/pull, no
    server-side optimizer (Trainer always updates locally). The
    TPU-native ring is the shared compiled XLA AllReduce: DistKVStore's
    reduce path covers both fabrics (ICI within a process, global-mesh /
    DCN psum across processes when jax.distributed is live), so this
    subclass only applies the horovod API restrictions on top."""

    def __init__(self):
        super().__init__("horovod")

    @property
    def local_rank(self):
        # set per worker by tools/launch.py (rank within this host);
        # single-process or unlaunched runs are local rank 0
        return envvars.get("MXNET_TPU_LOCAL_RANK")

    def push(self, key, value, priority=0):
        raise MXNetError("push is not supported by horovod kvstore; "
                         "use pushpull")

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        raise MXNetError("pull is not supported by horovod kvstore; "
                         "use pushpull or broadcast")

    def pushpull(self, key, value, out=None, priority=0):
        """hvd.allreduce(sum) analog: reduce values across all replicas
        into out (or in place). No store-side updater ever runs; the
        fused multi-key reduce (one compiled XLA program) is shared with
        the 'device'/'dist' stores. The stored value always ends up as
        the REDUCED result (so a later broadcast serves fresh data)."""
        keys, values = _normalize(key, value)
        outs = values if out is None else _normalize(key, out)[1]
        for k, v in zip(keys, values):
            if k not in self._store:
                vs = v if isinstance(v, (list, tuple)) else [v]
                self._store[k] = vs[0].copy()
        if self._try_fused_pushpull(keys, values, outs):
            return
        # fallback: the base push (reduce into store — no updater can
        # ever be set here) + pull (copy out), explicitly bypassing this
        # class's disabled overrides
        KVStore.push(self, key, value, priority)
        KVStore.pull(self, key, out if out is not None else value, priority)

    def broadcast(self, key, value, out=None, priority=0):
        """hvd.broadcast_parameters analog: the ROOT's (process 0's)
        CURRENT value wins — the store is overwritten on every call
        (upstream re-transmits each time; serving a stale stored value
        would silently drop updates). With num_workers > 1 the bytes
        really cross hosts via ``multihost_utils.broadcast_one_to_all``
        so rank-dependent initialization / rank-0-only checkpoint
        restores converge instead of silently diverging per worker."""
        keys, values = _normalize(key, value)
        firsts = [v[0] if isinstance(v, (list, tuple)) else v
                  for v in values]
        datas = [f._data for f in firsts]
        if self.num_workers > 1:
            from jax.experimental import multihost_utils
            # one pytree collective for the whole key list — N keys
            # cost one DCN round trip, not N host-synced ones
            datas = list(multihost_utils.broadcast_one_to_all(tuple(datas)))
        for k, f, new in zip(keys, firsts, datas):
            if self.num_workers == 1:
                # single-worker: ``new`` IS the caller's buffer and the
                # device_put below may alias it — the store must own a
                # copy (the caller may later donate its own buffer)
                new = new.copy()
            if k in self._store:
                stored = self._store[k]
                if new.dtype != stored.dtype:
                    new = new.astype(stored.dtype)
                # pin onto the stored replica's device (mirrors the
                # _try_fused_pushpull read-back path) so the store can't
                # drift off-device and decline the fused fast path later
                stored._set_data(jax.device_put(new, stored._data.device))
            else:
                self._store[k] = _wrap(jax.device_put(new, f._data.device),
                                       f.ctx)
        if out is not None:
            _, outs = _normalize(key, out)
            for k, o in zip(keys, outs):
                stored = self._get(k)
                for dst in (o if isinstance(o, (list, tuple)) else [o]):
                    stored.copyto(dst)

    def set_optimizer(self, optimizer):
        raise MXNetError("cannot set optimizer on horovod kvstore "
                         "(update_on_kvstore is always False)")

    def _set_updater(self, updater):
        raise MXNetError("cannot set updater on horovod kvstore")


def _maybe_init_distributed() -> bool:
    """jax.distributed.initialize from DMLC-compatible env (tools/launch.py
    sets MXNET_TPU_COORDINATOR / DMLC_PS_ROOT_URI+PORT, num/id).

    The env check runs FIRST: merely asking jax.process_count() would
    initialize the local XLA backend, after which the multi-process
    rendezvous is impossible (initialize() must precede any backend
    use)."""
    coord = envvars.get("MXNET_TPU_COORDINATOR")
    n = envvars.get_raw("MXNET_TPU_NUM_PROCS") or os.environ.get("DMLC_NUM_WORKER")
    pid = envvars.get_raw("MXNET_TPU_PROC_ID") or os.environ.get("DMLC_WORKER_ID")
    if not coord and os.environ.get("DMLC_PS_ROOT_URI"):
        coord = (os.environ["DMLC_PS_ROOT_URI"] + ":"
                 + os.environ.get("DMLC_PS_ROOT_PORT", "9000"))
    if coord and n and pid is not None:
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=int(n),
                                       process_id=int(pid))
            return True
        except Exception as e:  # already initialized or single-proc fallback
            import sys
            print(f"mxnet_tpu: jax.distributed.initialize failed: {e!r}",
                  file=sys.stderr)
            return jax.process_count() > 1
    return jax.process_count() > 1


def _cross_process_allreduce(merged: NDArray) -> NDArray:
    """psum across processes over the global mesh data axis (DCN/ICI)."""
    from jax.experimental import multihost_utils
    # simplest correct eager path: gather-to-all then sum locally.
    summed = multihost_utils.process_allgather(merged._data).sum(axis=0)
    return _wrap(jax.device_put(summed, merged._data.device), merged.ctx)


def _merge_row_sparse(arrays):
    """Sum row_sparse replicas by unique row id (the reference
    kvstore_local.h unique-rowid merge, ComputeMergedRowsFromRsp):
    concatenate (indices, values), segment-sum into the union rows."""
    import jax.numpy as jnp
    import numpy as np
    from .ndarray import sparse as _sp

    if len(arrays) == 1:
        return arrays[0]
    dev = arrays[0]._data.device
    idx = jnp.concatenate([a._aux if a._aux.device == dev
                           else jax.device_put(a._aux, dev)
                           for a in arrays])
    dat = jnp.concatenate([a._data if a._data.device == dev
                           else jax.device_put(a._data, dev)
                           for a in arrays])
    uniq, inv = jnp.unique(idx, return_inverse=True)
    summed = jnp.zeros((uniq.shape[0],) + dat.shape[1:], dat.dtype) \
        .at[inv.reshape(-1)].add(dat)
    out = _sp.RowSparseNDArray.__new__(_sp.RowSparseNDArray)
    NDArray.__init__(out, summed, arrays[0].ctx)
    out._aux = uniq
    out.shape = arrays[0].shape
    return out


def _normalize(key, value):
    if isinstance(key, (list, tuple)):
        return list(key), list(value)
    return [key], [value]


def _payload_bytes(values):
    """Bytes one replica contributes to a reduce: the first array of
    every key (a sparse array counts the values it stores)."""
    return sum((v[0] if isinstance(v, (list, tuple)) else v)._data.nbytes
               for v in values)
