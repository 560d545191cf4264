"""Shared helpers for the Pallas kernel wrappers."""
from __future__ import annotations

import functools

import jax

from ... import envvars


def interpret_mode() -> bool:
    """Run pallas_call in interpreter mode (CPU testing of kernels)."""
    return envvars.get("MXNET_TPU_PALLAS_INTERPRET")


def pallas_enabled() -> bool:
    """Should ops dispatch to the Pallas kernel path?"""
    if envvars.get("MXNET_TPU_DISABLE_PALLAS"):
        return False
    if interpret_mode():
        return True
    return jax.default_backend() == "tpu"


def _platforms(data):
    """The set of platforms ``data`` is placed on: its devices' for a
    concrete jax.Array, the default device's (object or platform
    string) — else the default backend's — for a tracer, whose
    placement is decided by whoever runs the trace."""
    if isinstance(data, jax.core.Tracer):
        dev = jax.config.jax_default_device
        if dev is None:
            return {jax.default_backend()}
        return {dev if isinstance(dev, str) else dev.platform}
    if isinstance(data, jax.Array):
        return {d.platform for d in data.devices()}
    raise TypeError(
        f"cannot tell where a {type(data).__name__} is placed: kernel "
        "dispatch needs a jax.Array or a tracer")


def pallas_ok_for(data) -> bool:
    """Kernel or jnp twin for this value? The decision is explicit, never
    a quiet decline: off (MXNET_TPU_DISABLE_PALLAS) takes the twin;
    interpret mode (the MXNET_TPU_PALLAS_INTERPRET test switch) takes
    the kernel anywhere; on a TPU backend a value placed on (or traced
    for) TPU devices takes the kernel and one placed on CPU devices
    takes the twin — an op on a cpu(0) context in a TPU process must
    not reach Mosaic. Any other placement raises."""
    if not pallas_enabled():
        return False
    if interpret_mode():
        return True
    platforms = _platforms(data)
    if platforms == {"tpu"}:
        return True
    if platforms == {"cpu"}:
        return False
    raise ValueError(
        f"kernel dispatch cannot classify a value placed on "
        f"{sorted(platforms)} in a TPU process: expected all-TPU or "
        "all-CPU devices")


def resolve_interpret(interpret):
    """``interpret=None`` (the public-entry default) means "whatever
    MXNET_TPU_PALLAS_INTERPRET says" — so call sites can't forget to
    thread the flag and crash compiling Mosaic off-TPU."""
    return interpret_mode() if interpret is None else interpret


def x32(fn):
    """Trace ``fn`` with x64 disabled.

    The framework enables jax_enable_x64 globally (MXNet exposes
    int64/float64 NDArrays — base.py), but Mosaic requires i32 grid
    index maps and TPU hardware has no f64 anyway; tracing the kernel
    call under enable_x64(False) keeps every constant/iota i32. Tensor
    operands keep their concrete dtypes — the op layer only routes
    f32/bf16 here.
    """

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(False):
            return fn(*args, **kwargs)

    return wrapper
