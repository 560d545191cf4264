"""Device contexts over PJRT devices.

Analog of the reference's ``python/mxnet/context.py`` (`Context`,
``mx.cpu()/mx.gpu(i)``) and ``include/mxnet/base.h`` (C++ `Context`).
The TPU design maps a Context directly onto a PJRT device obtained from
``jax.devices()``; ``mx.tpu(i)`` is the new first-class device type the
north star requires. Device placement of an op's outputs is realized by
running the op under ``jax.default_device`` (see ndarray/register.py),
so XLA compiles/executes on the right chip — there is no per-op stream
management: PJRT's async dispatch subsumes the reference's
StreamManager (src/engine/stream_manager.h).
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus", "default_device"]


class Context:
    """A device context.

    Parameters
    ----------
    device_type : str
        'cpu', 'gpu', 'tpu', or 'cpu_pinned'/'cpu_shared' (aliases of cpu
        on TPU systems — pinned host memory is a CUDA concept; host numpy
        buffers are already DMA-able by PJRT).
    device_id : int
        Device ordinal within its type.
    """

    # reference: Context::kCPU=1, kGPU=2, kCPUPinned=3, kCPUShared=5
    devtype2num = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devnum2type = {v: k for k, v in devtype2num.items()}

    _default = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devtype2num:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- PJRT mapping -----------------------------------------------------
    @property
    def jax_device(self):
        """The PJRT device backing this context.

        Resolution is PROCESS-LOCAL (jax.local_devices): under
        multi-process launch every worker's mx.cpu(0)/mx.tpu(0) is its
        own addressable chip — the reference's per-worker device ids —
        never another host's device from the global list."""
        kind = self.device_type
        if kind in ("cpu_pinned", "cpu_shared"):
            kind = "cpu"
        try:
            devs = jax.local_devices(backend=kind)
        except RuntimeError as e:
            # A context object may exist without its backend (the
            # reference allows mx.gpu(0) on a CPU host); USING it is an
            # error. It never resolves to a different kind of device: a
            # tpu context that quietly computed on the CPU is how a
            # benchmark once ground on one host core for an hour.
            raise MXNetError(
                f"{self} cannot be used: this process has no {kind!r} "
                f"backend (default backend: "
                f"{jax.default_backend()!r})") from e
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self} does not exist: only {len(devs)} {kind} device(s) visible"
            )
        return devs[self.device_id]

    @property
    def real_device_type(self) -> str:
        """Resolved platform of the backing PJRT device."""
        return self.jax_device.platform

    def empty_cache(self):
        """Analog of mx.Context.empty_cache (GPU pool flush). PJRT manages
        its own HBM pool; this is a best-effort hint (no-op)."""

    # -- default-context scoping ------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default, "stack"):
            Context._default.stack = []
        Context._default.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default.stack.pop()
        return False


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """The TPU device context — the north-star addition (`mx.tpu(i)`)."""
    return Context("tpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def num_gpus() -> int:
    try:
        return len(jax.local_devices(backend="gpu"))
    except RuntimeError:
        return 0


def num_tpus() -> int:
    try:
        return len(jax.local_devices(backend="tpu"))
    except RuntimeError:
        return 0


def _best_context() -> Context:
    plat = jax.default_backend()
    if plat == "tpu":
        return tpu(0)
    if plat == "gpu":
        return gpu(0)
    if plat == "cpu":
        return cpu(0)
    raise MXNetError(f"unsupported default JAX backend {plat!r}")


def current_context() -> Context:
    """The active default context (innermost ``with ctx:`` scope, else the
    best available device — TPU when present)."""
    stack = getattr(Context._default, "stack", None)
    if stack:
        return stack[-1]
    return _best_context()


def default_device():
    """PJRT device of the current default context."""
    return current_context().jax_device


# module-level convenience mirroring mx.context.current_context()
Context.default_ctx = property(lambda self: current_context())
