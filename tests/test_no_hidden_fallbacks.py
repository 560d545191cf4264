"""The device is never hidden: a missing backend, an unclassifiable
placement or a CPU under the chip smoke is an error, not a quiet
second choice. All CPU-only and cheap."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops.pallas import _util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_context_raises_without_a_tpu_backend():
    """mx.tpu(0) may exist on a CPU-only process (the reference allows
    mx.gpu(0) objects without a GPU); USING it raises — it never
    resolves to a CPU."""
    ctx = mx.tpu(0)
    with pytest.raises(MXNetError, match="no 'tpu' backend"):
        ctx.jax_device
    with pytest.raises(MXNetError):
        mx.nd.zeros((2,), ctx=ctx)
    assert mx.cpu(0).jax_device.platform == "cpu"


def test_best_context_knows_tpu_gpu_cpu_only(monkeypatch):
    from mxnet_tpu import context

    for plat, want in (("tpu", mx.tpu(0)), ("gpu", mx.gpu(0)),
                       ("cpu", mx.cpu(0))):
        monkeypatch.setattr(jax, "default_backend", lambda p=plat: p)
        assert context._best_context() == want
    monkeypatch.setattr(jax, "default_backend", lambda: "mystery")
    with pytest.raises(MXNetError, match="mystery"):
        context._best_context()


def _trace_choice(x):
    """pallas_ok_for's answer for a TRACER of x (placement = the
    default device in force while tracing)."""
    seen = []
    jax.make_jaxpr(lambda a: seen.append(_util.pallas_ok_for(a)) or a)(x)
    return seen[0]


def test_pallas_dispatch_is_explicit_on_a_tpu_backend(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("MXNET_TPU_DISABLE_PALLAS", raising=False)
    x = jnp.ones((4,))
    assert _util.pallas_ok_for(x) is False          # cpu backend: twin

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _util.pallas_ok_for(x) is False          # array ON cpu: twin
    assert _trace_choice(x) is True                 # traced for the backend
    with jax.default_device("tpu"):                 # platform STRING
        assert _trace_choice(x) is True
    with jax.default_device("cpu"):
        assert _trace_choice(x) is False
    with jax.default_device(jax.devices("cpu")[0]):  # device OBJECT
        assert _trace_choice(x) is False
    # what cannot be classified raises instead of declining
    with jax.default_device("gpu"):
        with pytest.raises(ValueError, match="cannot classify"):
            _trace_choice(x)
    with pytest.raises(TypeError, match="cannot tell where"):
        _util.pallas_ok_for(np.ones(4))
    # the explicit switches still rule
    monkeypatch.setenv("MXNET_TPU_DISABLE_PALLAS", "1")
    assert _trace_choice(x) is False
    monkeypatch.delenv("MXNET_TPU_DISABLE_PALLAS")
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    assert _util.pallas_ok_for(x) is True


def test_chip_smoke_refuses_to_start_off_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout == ""           # no result line, no phase line


def test_dataloader_uses_threads_once_the_process_holds_a_chip(monkeypatch):
    """One process per chip: workers are forked before the parent first
    touches an accelerator backend, or not at all."""
    from mxnet_tpu.gluon.data import dataloader as dl

    data = [(np.full((2,), i, np.float32), np.float32(i)) for i in range(8)]
    monkeypatch.setattr(dl, "_holds_accelerator", lambda: True)
    loader = dl.DataLoader(data, batch_size=4, num_workers=2)
    it = iter(loader)
    assert isinstance(it, dl._ThreadedIter)
    assert loader._mp_pool is None
    assert [b[1].asnumpy().tolist() for b in it] == [[0, 1, 2, 3],
                                                     [4, 5, 6, 7]]
    monkeypatch.undo()
    assert dl._holds_accelerator() is False     # the real probe, on cpu


def test_device_memory_on_cpu_is_the_live_array_sum():
    from mxnet_tpu.telemetry import resources

    keep = jnp.ones((1024, 256), jnp.float32)       # 1 MiB, live
    in_use, live = resources.device_memory()
    assert in_use == 0          # the CPU backend reports no memory_stats
    assert live >= keep.nbytes
