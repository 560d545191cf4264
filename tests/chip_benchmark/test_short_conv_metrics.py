"""PR 36's two per-layer metrics of the short convolution, data files only:
``short_conv_ms.train`` (reader ``device_scope_ms`` under the block's scope
``mx.CausalConv1D``) and ``short_conv_kernel_pct`` (reader
``program_counter`` over the trace-time tallies ``conv1d_kernel_calls`` /
``conv1d_calls``). The Kimi cell rehearses on the CPU: the names its compiled
programs give the convolutions are what the first reads as a number, and its
tallies what the second reads (0: the rehearsal's 64 channels take the twin)."""
import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
CELLS = ["kimi_linear_48b_a3b.train_8k", "phi4_mini_flash.train_8k"]
METRICS = ["short_conv_ms.train", "short_conv_kernel_pct"]


@pytest.fixture(scope="module")
def chip_path():
    sys.path.insert(0, CHIP)
    yield CHIP
    sys.path.remove(CHIP)


@pytest.fixture
def fresh_compiles():
    """jax hashes a program for its persistent cache without its names: a
    warm cache would hand back the executable, and the names, of a tree from
    before the scope ``mxtpu_conv1d``. These compiles stay out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(metric):
    with open(os.path.join(CHIP, "metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_and_benchmark_entry_agree(chip_path, metric):
    spec = _spec(metric)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry.pop("workloads") == CELLS
    assert entry == {k: v for k, v in spec.items() if k not in ("reader", "args")}
    assert (entry["layer"], entry["moves"]) == ("kernels", "train_samples_per_s")
    assert bench["per_layer"][-2:] == [m for m in bench["per_layer"]
                                       if m["name"] in METRICS]     # appended
    importlib.import_module("readers." + spec["reader"])


@pytest.mark.parametrize("window,reads", [
    ({"conv1d_calls": 24.0, "conv1d_kernel_calls": 24.0}, 100.0),   # the chip
    ({"conv1d_calls": 24.0, "conv1d_kernel_calls": 0.0}, 0.0),      # the twin
    ({"invokes": 3.0}, None),                                      # the parent
])
def test_the_kernels_share_reads_the_two_tallies(chip_path, window, reads):
    reader = importlib.import_module("readers.program_counter")
    ctx = {"program_counters": {"setup": {}, "window": window}}
    assert reader.read(ctx, **_spec("short_conv_kernel_pct")["args"]) == reads


def test_the_kimi_cells_cpu_rehearsal_reads_both_metrics_as_numbers(
        chip_path, fresh_compiles, monkeypatch, tmp_path):
    """The cell's loop at rehearsal widths on the CPU, its programs caught at
    jax's compile call: the forward and the backward program name every
    convolution's operations ``.../mx.KDAMixer/mx.CausalConv1D/mxtpu_conv1d/
    ...``; a device trace whose events carry those names reads
    ``short_conv_ms.train`` as their milliseconds a step."""
    from jax._src import compiler

    monkeypatch.syspath_prepend(ROOT)
    harness = importlib.import_module("run")
    programs = []
    real = compiler.compile_or_get_cached

    def spy(backend, computation, devices, compile_options, *args, **kwargs):
        exe = real(backend, computation, devices, compile_options, *args, **kwargs)
        programs.append((computation.operation.attributes["sym_name"].value,
                         exe.hlo_modules()[0].to_string()))
        return exe

    monkeypatch.setattr(compiler, "compile_or_get_cached", spy)
    bench = harness.load_benchmark()
    cell, cfg, traffic, shape = harness.find_cell(bench, CELLS[0], rehearse=True)
    loop = importlib.import_module(f"loops.{traffic['loop']}")
    from spans import Spans

    import mxnet_tpu as mx

    # the tallies are the process's running sums: another test's kernels
    # (interpret mode, the same worker) are in them, so read this run's share
    before = mx.profiler.counters(device=False)
    run = loop.Run(cfg, traffic, shape, cell["chips"], 36, Spans(False),
                   rehearse=True)
    run.setup()
    measured = run.measure(0.2)
    ctx = run.reader_context()
    run.release()
    assert measured["failed"] == 0

    # four KDA mixers x (q, k, v), the twin by shape
    window = {k: v - before.get(k, 0)
              for k, v in ctx["program_counters"]["window"].items()
              if k.startswith("conv1d_")}
    assert window["conv1d_calls"] >= 12 and window["conv1d_kernel_calls"] == 0
    reader = importlib.import_module("readers.program_counter")
    assert reader.read({"program_counters": {"setup": {}, "window": window}},
                       **_spec("short_conv_kernel_pct")["args"]) == 0.0

    step = [(name, sorted(set(re.findall(r'op_name="([^"]*)"', text))))
            for name, text in programs if name.startswith("jit_mxtpu_fwd_")]
    assert len(step) == 2                       # the forward, the backward
    conv = [[n for n in names if "mx.CausalConv1D" in n.split("/")]
            for _, names in step]
    assert all(conv)
    for names in conv:                          # block scope, then the op's own
        parts = [n.split("/") for n in names]
        assert all("mx.KDAMixer" in p for p in parts)
        inside = [p for p in parts if "mxtpu_conv1d" in p]
        assert inside and all(p.index("mxtpu_conv1d")
                              == p.index("mx.CausalConv1D") + 1 for p in inside)

    scope_ms = importlib.import_module("readers.device_scope_ms")
    # a device trace (the reader tests' writer) whose events carry the
    # rehearsal's names, one after the other, 1 ms each, two equal steps
    from test_device_scope_ms import write_trace

    step_ops, t = {}, 0
    for module, names in ((f"{step[0][0]}(1)", step[0][1]),
                          (f"{step[1][0]}(2)", step[1][1]),
                          ("jit_mxtpu_update(3)", ["jit(mxtpu_update)/mxtpu_update/add"])):
        step_ops[module] = [(t + i, 1, n + ":") for i, n in enumerate(names)]
        t += len(names)
    path = write_trace(tmp_path / "step.xplane.pb", steps=2, step_ms=t + 10,
                       step_ops=step_ops)
    monkeypatch.setattr(scope_ms, "newest_trace", lambda: path)
    tr = type("T", (), {"device_events": {0: []}})()
    value = scope_ms.read({"trace": tr, "measured": {"steps": 2}},
                          **_spec("short_conv_ms.train")["args"])
    assert value == pytest.approx(len(conv[0]) + len(conv[1]))
    # under the same block: the mixer's row holds the convolutions
    mixer = scope_ms.read({"trace": tr, "measured": {"steps": 2}},
                          **_spec("device_mixer_ms.train")["args"])
    assert mixer > value
    # a program from before the block scopes (the recorded small trace): nothing
    monkeypatch.setattr(scope_ms, "newest_trace", lambda: os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "small.xplane.pb"))
    assert scope_ms.read({"trace": tr, "measured": {"steps": 2}},
                         **_spec("short_conv_ms.train")["args"]) is None
