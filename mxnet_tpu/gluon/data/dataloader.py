"""DataLoader (python/mxnet/gluon/data/dataloader.py analog).

Worker model parity with the reference (multiprocessing workers +
shared-memory NDArray rebuild, CPUSharedStorageManager):

- ``num_workers>0, thread_pool=False`` (the reference default): a
  forked PROCESS pool decodes and batchifies to numpy outside the GIL
  (Python/PIL decode does not scale on threads — SURVEY §7 hard part
  #6); the parent converts to device arrays. Workers never touch JAX
  (fork + XLA runtime don't mix); ``default_mp_batchify_fn`` therefore
  stacks to numpy, the parent wraps.
- ``thread_pool=True``: thread workers — cheaper startup, right when
  __getitem__ is numpy-bound and GIL-releasing.
- :class:`DevicePrefetcher` overlaps host→device transfer with compute
  (the PrefetcherIter/pin-memory role; PJRT device_put is async).
"""
from __future__ import annotations

import concurrent.futures as _futures
import multiprocessing as _mp
import threading
from collections import deque

import numpy as np

from ...base import MXNetError
from ...ndarray import NDArray, array
from ...telemetry import events as _telemetry_events
from .sampler import SequentialSampler, RandomSampler, BatchSampler

__all__ = ["DataLoader", "DevicePrefetcher", "default_batchify_fn",
           "default_mp_batchify_fn"]


def _holds_accelerator():
    """Has this process already opened a non-CPU JAX backend? A chip
    belongs to one process: forked after that point, a worker would
    inherit the parent's device handles and its client threads' locks.
    So workers fork before the parent first touches the backend, or the
    loader uses threads."""
    import jax
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() != "cpu")


def default_batchify_fn(data):
    """Stack samples into a batch (reference default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        from ... import ndarray as nd
        return nd.stack(*data, axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return array(data)


def default_mp_batchify_fn(data):
    """Worker-side batchify: numpy ONLY. A forked worker must never
    touch JAX — the parent holds a multithreaded XLA client and any
    device call after fork can deadlock — so NDArray samples are
    rejected with a fix-it message instead of being converted."""
    if isinstance(data[0], NDArray):
        raise MXNetError(
            "Dataset.__getitem__ returned an NDArray but the DataLoader "
            "uses forked process workers, which must not touch device "
            "arrays. Return numpy from the dataset/transforms, or pass "
            "thread_pool=True (thread workers), or num_workers=0.")
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_mp_batchify_fn(i) for i in data]
    return np.asarray(data)


def _to_nd(batch):
    if isinstance(batch, np.ndarray):
        return array(batch)
    if isinstance(batch, (list, tuple)):
        return [_to_nd(b) for b in batch]
    return batch


# worker globals installed by the pool initializer (fork start method:
# the dataset is inherited copy-on-write — no per-task pickling)
_WORKER_DATASET = None
_WORKER_FN = None

# arrays above this size ride shared memory instead of the result pipe —
# the CPUSharedStorageManager role: pickling a 20MB batch through a pipe
# costs more than the decode itself
_SHM_MIN_BYTES = 1 << 20


def _worker_init(dataset, batchify_fn):
    global _WORKER_DATASET, _WORKER_FN
    _WORKER_DATASET = dataset
    _WORKER_FN = batchify_fn


def _ship(obj):
    """Replace large numpy arrays with shared-memory descriptors."""
    if isinstance(obj, np.ndarray) and obj.nbytes >= _SHM_MIN_BYTES:
        from multiprocessing import shared_memory
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)[...] = obj
        name = shm.name
        shm.close()
        return ("__shm__", name, obj.shape, str(obj.dtype))
    if isinstance(obj, (list, tuple)):
        return [_ship(o) for o in obj]
    return obj


def _receive(obj):
    """Materialize shared-memory descriptors: one host memcpy out of
    the segment, unlink immediately, return numpy — the (async) device
    transfer happens downstream (_to_nd / DevicePrefetcher), so the
    result-drain loop never blocks on H2D."""
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        from multiprocessing import shared_memory
        _, name, shape, dtype = obj
        shm = shared_memory.SharedMemory(name=name)
        try:
            out = np.array(np.ndarray(shape, np.dtype(dtype), buffer=shm.buf),
                           copy=True)
        finally:
            shm.close()
            shm.unlink()
        return out
    if isinstance(obj, (list, tuple)):
        return [_receive(o) for o in obj]
    return obj


def _discard_shm(obj):
    """Unlink shared-memory descriptors without materializing them."""
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        from multiprocessing import shared_memory
        try:
            shm = shared_memory.SharedMemory(name=obj[1])
            shm.close()
            shm.unlink()
        except FileNotFoundError:
            pass
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _discard_shm(o)


def _worker_task(indices):
    return _ship(_WORKER_FN([_WORKER_DATASET[i] for i in indices]))


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=False, timeout=120):
        self._mp_pool = None  # persistent worker pool (created lazily);
        # assigned FIRST so __del__ is safe if validation below raises
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._timeout = timeout
        self._prefetch = max(0, prefetch or 2 * self._num_workers)
        self._fork_safe = None  # probed lazily on first __iter__

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless "
                                 "batch_sampler is specified")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be specified if sampler is "
                                 "specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError("batch_size, shuffle, sampler and last_batch must "
                             "not be specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        if batchify_fn is None:
            batchify_fn = default_mp_batchify_fn \
                if (self._num_workers > 0 and not thread_pool) \
                else default_batchify_fn
        self._batchify_fn = batchify_fn

    def _get_mp_pool(self):
        """Fork the worker pool ONCE and keep it across epochs (the
        reference keeps workers alive too). Only reached while this
        process holds no accelerator (see ``_holds_accelerator``)."""
        if self._mp_pool is None:
            ctx = _mp.get_context("fork")
            self._mp_pool = ctx.Pool(
                self._num_workers, initializer=_worker_init,
                initargs=(self._dataset, self._batchify_fn))
        return self._mp_pool

    def __del__(self):
        pool = getattr(self, "_mp_pool", None)
        if pool is not None:
            try:
                pool.terminate()
            except Exception:
                pass  # interpreter teardown: helpers may be gone already

    def __iter__(self):
        if self._num_workers == 0:
            def same_process_iter():
                for batch in self._batch_sampler:
                    yield self._batchify_fn([self._dataset[idx] for idx in batch])
            return same_process_iter()
        if not self._thread_pool:
            if self._fork_safe is None and _holds_accelerator():
                self._use_thread_workers()
            if self._fork_safe is None:
                # fork the pool BEFORE probing: the probe may materialize
                # lazy dataset state (open record files) in the parent,
                # and forked workers must inherit the clean instance —
                # a shared fd means interleaved seek/read corruption
                self._get_mp_pool()
                if not self._dataset_is_fork_safe():
                    # probe says thread fallback: don't keep idle forks
                    self._mp_pool.terminate()
                    self._mp_pool = None
            if self._fork_safe:
                return _MultiProcessIter(self)
        return _ThreadedIter(self)

    def _dataset_is_fork_safe(self):
        """Forked workers must not touch JAX: probe one sample and fall
        back to thread workers (with the eager batchify) when
        __getitem__ produces device arrays (e.g. the vision datasets'
        NDArray transforms). Call only AFTER the pool forked (see
        __iter__)."""
        if self._fork_safe is None:
            def has_nd(x):
                if isinstance(x, NDArray):
                    return True
                if isinstance(x, (list, tuple)):
                    return any(has_nd(i) for i in x)
                return False
            try:
                self._fork_safe = not has_nd(self._dataset[0])
            except Exception:
                self._fork_safe = False
            if not self._fork_safe:
                self._use_thread_workers()
        return self._fork_safe

    def _use_thread_workers(self):
        self._fork_safe = False
        if self._batchify_fn is default_mp_batchify_fn:
            self._batchify_fn = default_batchify_fn

    def __len__(self):
        return len(self._batch_sampler)


class _ThreadedIter:
    """Thread-pool prefetching iterator (PrefetcherIter analog)."""

    def __init__(self, loader: DataLoader):
        self._loader = loader
        self._pool = _futures.ThreadPoolExecutor(
            max_workers=loader._num_workers,
            thread_name_prefix="mxnet_tpu_dataloader_prefetch")
        self._batches = iter(loader._batch_sampler)
        self._pending = deque()
        for _ in range(loader._prefetch):
            self._submit_next()

    def _submit_next(self):
        try:
            batch = next(self._batches)
        except StopIteration:
            return
        fn = self._loader._batchify_fn
        ds = self._loader._dataset
        self._pending.append(
            self._pool.submit(lambda b: fn([ds[i] for i in b]), batch))

    def __iter__(self):
        return self

    def __next__(self):
        if not self._pending:
            self._shutdown()
            raise StopIteration
        fut = self._pending.popleft()
        self._submit_next()
        try:
            return fut.result()
        except Exception:
            self._shutdown()
            raise

    def _shutdown(self):
        self._pool.shutdown(wait=False, cancel_futures=True)

    def __del__(self):
        # abandoned mid-epoch (break/early stop): release worker threads
        self._shutdown()


class _MultiProcessIter:
    """Forked process-pool iterator (reference multiprocessing workers):
    decode/batchify run outside the GIL; batches come back as numpy and
    are wrapped to NDArrays in the parent."""

    def __init__(self, loader: DataLoader):
        self._loader = loader
        self._pool = loader._get_mp_pool()
        self._batches = iter(loader._batch_sampler)
        self._pending = deque()
        for _ in range(max(loader._prefetch, loader._num_workers)):
            self._submit_next()

    def _submit_next(self):
        try:
            batch = next(self._batches)
        except StopIteration:
            return
        self._pending.append(self._pool.apply_async(_worker_task, (list(batch),)))

    def __iter__(self):
        return self

    def __next__(self):
        if not self._pending:
            self._shutdown()
            raise StopIteration
        res = self._pending.popleft()
        self._submit_next()
        try:
            out = res.get(timeout=self._loader._timeout)
        except Exception:
            self._shutdown()
            raise
        return _to_nd(_receive(out))

    def _shutdown(self):
        # the pool belongs to the DataLoader (persistent across epochs),
        # but in-flight results hold shared-memory segments that only
        # _receive unlinks — drain and discard them or /dev/shm leaks a
        # batch per abandoned epoch
        while self._pending:
            res = self._pending.popleft()
            try:
                _discard_shm(res.get(timeout=self._loader._timeout))
            except Exception as e:
                # keep draining (every leaked result pins /dev/shm),
                # but a discard that itself fails is worth a trace
                _telemetry_events.emit("dataloader_discard_error",
                                       error=repr(e))

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass


class DevicePrefetcher:
    """Wraps a batch iterable; keeps ``depth`` batches already
    device_put to ``ctx`` so the accelerator never waits on H2D
    (reference PrefetcherIter + pin_memory role).

    ``threaded=True`` (default) runs source-pull + device_put on a
    dedicated thread, so decode waits and H2D RPCs overlap the
    consumer's step dispatches (double-buffering; the consumer only
    blocks when the queue is empty). ``threaded=False`` keeps the
    simple synchronous fill."""

    def __init__(self, it, ctx=None, depth=2, threaded=True):
        from ...context import current_context
        self._src = iter(it)
        self._ctx = ctx or current_context()
        self._depth = max(1, depth)
        self._queue = deque()
        self._threaded = bool(threaded)
        self._worker = None
        if self._threaded:
            import queue as _q
            import threading as _t
            self._q = _q.Queue(maxsize=self._depth)
            self._done = object()
            self._stop = False
            self._exhausted = False

            def put(item):
                # bounded put that gives up when the consumer closes —
                # a plain q.put would pin this thread (and depth device
                # batches) forever if iteration stops early
                while not self._stop:
                    try:
                        self._q.put(item, timeout=0.1)
                        return True
                    except _q.Full:
                        continue
                return False

            def pump():
                try:
                    for batch in self._src:
                        if not put(self._to_device(batch)):
                            return
                except BaseException as e:  # surfaced on the consumer
                    put(e)
                # ALWAYS terminate the stream: without the sentinel a
                # consumer that survives the raised error deadlocks on
                # the next get()
                put(self._done)

            self._worker = _t.Thread(target=pump, daemon=True)
            self._worker.start()

    def close(self):
        """Stop the pump thread and release queued device batches
        (safe to call repeatedly; no-op for the synchronous mode)."""
        if self._worker is None:
            return
        self._stop = True
        try:
            while True:
                self._q.get_nowait()
        except Exception:
            pass
        self._worker.join(timeout=2.0)
        self._worker = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _to_device(self, batch):
        if isinstance(batch, NDArray):
            return batch.as_in_context(self._ctx)
        if isinstance(batch, np.ndarray):
            return array(batch, ctx=self._ctx)
        if isinstance(batch, (list, tuple)):
            return [self._to_device(b) for b in batch]
        return batch

    def _fill(self):
        while len(self._queue) < self._depth:
            try:
                self._queue.append(self._to_device(next(self._src)))
            except StopIteration:
                break

    def __iter__(self):
        return self

    def __next__(self):
        if self._threaded:
            if self._exhausted or (self._worker is None
                                   and self._q.empty()):
                raise StopIteration  # repeatable: pump is gone
            item = self._q.get()
            if item is self._done:
                self._exhausted = True
                raise StopIteration
            if isinstance(item, BaseException):
                raise item
            return item
        self._fill()
        if not self._queue:
            raise StopIteration
        out = self._queue.popleft()
        self._fill()
        return out
