"""The flash kernel's trace-time tallies of the chunks of its live tiles and
of those it computes, ``flash_chunks`` / ``flash_chunks_run``, as the
``program_counter`` reader reads them (100 x run / chunks): what the 8k
cells' flash calls tally when they are traced on the CPU at the cells'
shapes (interpret mode: the calls the chip compiles)."""
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")


@pytest.fixture(scope="module")
def chip_path():
    sys.path.insert(0, CHIP)
    yield CHIP
    sys.path.remove(CHIP)


# the spec a per-layer metric ``flash_chunk_run_pct`` would give the reader
ARGS = {"numerator": "flash_chunks_run", "denominator": "flash_chunks"}


# one layer-row's flash call of each kind the 8k cells make: (q, k, v shapes,
# the op's keywords); the forward and its gradient are traced
_CALLS = {
    "mla": (((1, 32, 8192, 192), (1, 32, 8192, 192), (1, 32, 8192, 128)),
            {"causal": True}, 85.0),                                 # Kimi, JoyAI
    "keye": (((1, 32, 8192, 128), (1, 4, 8192, 128), (1, 4, 8192, 128)),
             {"causal": True, "pair_mask": True}, 85.0),
    "sdar": (((1, 32, 16384, 128), (1, 4, 16384, 128), (1, 4, 16384, 128)),
             {"block_diffusion": 4}, 75.0),
}


@pytest.mark.parametrize("call", sorted(_CALLS))
def test_the_share_reads_the_cells_flash_calls_traced_on_the_cpu(chip_path, monkeypatch,
                                                                 call):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import op_impl_nn

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    shapes, kw, want = _CALLS[call]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
    if kw.pop("pair_mask", False):
        args.append(jax.ShapeDtypeStruct((1, 8192, 8192), jnp.int8))

    def op(q, k, v, *mask):
        return op_impl_nn.flash_attention_op(q, k, v, pair_mask=mask[0] if mask else None,
                                             **kw)
    before = mx.profiler.counters(device=False)
    jax.make_jaxpr(op)(*args)
    jax.make_jaxpr(jax.grad(lambda *a: op(*a).astype(jnp.float32).sum(), (0, 1, 2)))(*args)
    after = mx.profiler.counters(device=False)
    window = {k: after[k] - before[k] for k in ("flash_chunks", "flash_chunks_run")}
    assert window["flash_chunks"] > 0
    reader = importlib.import_module("readers.program_counter")
    ctx = {"program_counters": {"setup": {}, "window": window}}
    assert reader.read(ctx, **ARGS) == want
    # a parent without the tallies reads nothing
    assert reader.read({"program_counters": {"setup": {}, "window": {"invokes": 3}}},
                       **ARGS) is None
