"""Unit tests of the chip benchmark's own code (benchmark/chip): the
contract's naming rules, that every name in BENCHMARK.json finds its file,
the trace reduction on a small recorded trace, the FLOPs functions against
hand-worked values, the peaks table, and — at a size a test run can hold, on
the CPU — that the comparison which decides ``correct`` admits the program,
refuses the control (the reference in the next precision down) and refuses a run whose timed
path was broken underneath. Pure benchmark code: nothing here imports the
program's internals except through the harness's own entry."""
import argparse
import importlib
import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def chip_path():
    sys.path.insert(0, CHIP)
    yield CHIP
    sys.path.remove(CHIP)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# What the PR that proves resnet50_v1.train_b128 adds to BENCHMARK.json: the
# cell is kept out (its float8 control does not separate from the program at
# published size, PERF.md section 7) but its files are exercised here.
PENDING = {
    "configs": [{
        "name": "resnet50_v1",
        "source": "He et al. 2015, Deep Residual Learning for Image Recognition, "
                  "arXiv:1512.03385, Table 1, 50-layer (MXNet model zoo resnet50_v1)",
        "file": "benchmark/chip/configs/resnet50_v1.json", "reduced": [],
        "why": "the other north-star model: XLA convolutions and training-mode "
               "BatchNorm, no Pallas kernel, SGD+momentum through the one "
               "multi_sgd_mom_update call"}],
    "workloads": [{
        "name": "resnet50_v1.train_b128", "config": "resnet50_v1",
        "traffic": "train_steps", "chips": 1,
        "why": "same closed loop, 128 images of 224x224: bypasses every Pallas "
               "kernel (XLA convolutions), largest host share, so dispatch/optimizer "
               "work shows most and kernel work must show nothing"}],
}


@pytest.fixture(scope="module")
def bench_with_pending(bench):
    return {**bench, **{k: bench[k] + PENDING[k] for k in PENDING}}


def _load(*parts):
    with open(os.path.join(CHIP, *parts)) as f:
        return json.load(f)


# ---- BENCHMARK.json against the contract ------------------------------------

def test_benchmark_json_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["paths"]) <= 16
    assert len(json.dumps(bench)) < 64 * 1024
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_lines_are_of_the_allowed_characters(bench_with_pending, kind):
    seen = set()
    for entry in bench_with_pending[kind]:
        assert NAME.match(entry["name"]), entry["name"]
        assert entry["name"] not in seen
        seen.add(entry["name"])
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
            assert entry["better"] in ("lower", "higher")
        lines = ("layer",) if "unit" in entry else ("why", "source")
        for key in lines:
            if key in entry:
                text = entry[key]
                assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
        for key in ("config", "traffic"):
            if key in entry:
                assert NAME.match(entry[key])
        for key in entry.get("reduced", []):
            assert NAME.match(key)


def test_every_config_and_traffic_is_found_by_name(bench_with_pending):
    bench = bench_with_pending
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for cell in bench["workloads"]:
        cfg_entry = configs[cell["config"]]
        used.add(cell["config"])
        path = os.path.join(ROOT, cfg_entry["file"])
        assert any(cfg_entry["file"].startswith(p + "/") for p in bench["paths"])
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == cfg_entry["reduced"]
        for key, value in cfg["published"].items():
            if key not in cfg_entry["reduced"] and key in cfg:
                assert cfg[key] == value, f"{key} differs from the source and is not in reduced"
        traffic = _load("traffic", cell["traffic"] + ".json")
        assert os.path.exists(os.path.join(CHIP, "loops", traffic["loop"] + ".py"))
        for sub in ("models", "reference"):
            assert os.path.exists(os.path.join(CHIP, sub, cfg["model"] + ".py"))
        assert cell["chips"] in (1, 4)
    assert used == set(configs), "a configuration no cell uses"


def test_every_metric_finds_its_file_and_reader(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = set()
    for m in bench["per_layer"]:
        spec = _load("metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(CHIP, "readers", spec["reader"] + ".py"))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
        layers.add(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"].split("."):
            assert m["unit"] == "%"
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"layer {layer!r} is not in PERF.md's list of layers"


# ---- the trace reduction, on a trace recorded on a TPU v5e ----------------------

@pytest.fixture(scope="module")
def small_trace(chip_path):
    from xplane import Trace

    return Trace.from_file(os.path.join(HERE, "small.xplane.pb"),
                           ("data", "fwd", "bwd", "update"))


def test_trace_busy_share_and_kernel_time(small_trace):
    # three steps, each: an 11.45 us copy, a 90.99 us matmul+tanh fusion and
    # a 26.0 us multiply (read by hand from the XLA Ops line)
    assert list(small_trace.device_events) == [0]
    assert len(small_trace.device_events[0]) == 12
    assert small_trace.busy_s() == pytest.approx(384.4e-6, rel=0.01)
    assert small_trace.kernel_s("convolution_tanh_fusion") == pytest.approx(272.9e-6, rel=0.01)
    assert small_trace.kernel_s("mxtpu_flash") is None
    w0, w1 = small_trace.window()
    assert w1 - w0 == pytest.approx(22.7e-3, rel=0.01)
    assert 1.0 - small_trace.busy_s() / (w1 - w0) == pytest.approx(0.983, abs=0.002)
    top = small_trace.top_ops(10)
    assert top[0][0] == "%convolution_tanh_fusion" and len(top) == 4


def test_trace_gap_attribution(small_trace):
    # nine host spans (3 x data, fwd, update); the device's clock runs ~0.8 ms
    # ahead of the host's in this trace, so each step's device work falls
    # inside the 4 ms `data` sleep and every gap begins under `data`
    assert [s[0] for s in small_trace.host_spans] == ["data", "fwd", "update"] * 3
    gaps = small_trace.idle_gaps(10)
    assert [g[0] for g in gaps] == ["data"]
    w0, w1 = small_trace.window()
    assert gaps[0][1] == pytest.approx((w1 - w0) - small_trace.busy_s(), rel=1e-6)
    assert small_trace.span_at(small_trace.host_spans[1][1] + 1e-5) == "fwd"
    assert small_trace.span_at(w0 - 1.0) == "between_spans"


def test_union_merges_overlaps(chip_path):
    from xplane import union

    assert union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


# ---- work from shapes, by hand ---------------------------------------------------

def test_bert_base_flops_by_hand(chip_path):
    mod = importlib.import_module("models.bert_base")
    cfg = _load("configs", "bert_base.json")
    shape = {"batch": 64, "seq_len": 512}
    layer = 2 * 512 * 768 * 2304 + 4 * 512 * 512 * 768 + 2 * 512 * 768 * 768 \
        + 4 * 512 * 768 * 3072
    assert layer == 8_053_063_680
    head = 2 * 512 * 768 * 768 + 2 * 512 * 768 * 30522
    assert mod.flops_per_sample(cfg, shape) == 3 * (12 * layer + head) == 363_732_664_320
    work = mod.attention_work(cfg, shape)
    assert work["flops"] == 12 * 64 * 12 * 512 * 512 * 768
    assert work["bytes"] == 12 * 64 * 12 * 512 * 768 * 2


def test_resnet50_flops_by_hand(chip_path):
    mod = importlib.import_module("models.resnet50_v1")
    cfg = _load("configs", "resnet50_v1.json")
    macs = (118_013_952            # stem 7x7/2: 112^2 x 64 x 147
            + 667_942_912          # stage 1, 56^2
            + 950_534_144          # stage 2, 28^2
            + 1_387_266_048        # stage 3, 14^2
            + 732_168_192          # stage 4, 7^2
            + 2_048_000)           # classifier
    assert macs == 3_857_973_248   # He et al. 2015, Table 1: 3.8e9
    assert mod.flops_per_sample(cfg, {"batch": 128}) == 6 * macs
    specs = mod.param_specs(cfg)
    n = sum(math.prod(s) for name, s, _, _ in specs if "running_" not in name)
    assert n == 25_557_032 + 18_880  # the published model + the zoo's 1x1-convolution biases


def test_unknown_device_kind_raises(chip_path):
    from peaks import guard_impossible, peaks_for

    assert peaks_for("TPU v5 lite") == {"flops": 197e12, "bytes": 819e9}
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(RuntimeError):
        guard_impossible(1.0, 400e12, peaks_for("TPU v5e"))


def test_roofline_reader_names_its_bound(chip_path):
    reader = importlib.import_module("readers.kernel_roofline")
    mod = importlib.import_module("models.bert_base")
    work = mod.attention_work(_load("configs", "bert_base.json"),
                              {"batch": 64, "seq_len": 512})
    least, which = reader.bound(work, {"flops": 197e12, "bytes": 819e9})
    assert which == "compute" and least == pytest.approx(work["flops"] / 197e12)


# ---- `correct`, at a size a test run can hold (rehearsal widths, CPU) -----------

def _args(workload, seed=5):
    return argparse.Namespace(workload=workload, seed=seed, seconds=0.3, trace=0)


@pytest.fixture()
def harness(chip_path, monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    return importlib.import_module("run")


def _faulty(base, fault):
    """The loop's Run with its timed path broken underneath."""
    class StateUnchanged(base):
        def update(self):            # the step returns its state unchanged
            pass

    class HalfBatch(base):           # half of the batch left out, the mean
        def load(self, host_batch):  # taken over the rest
            return super().load(tuple(a[:len(a) // 2] for a in host_batch))

        def update(self):
            self.trainer.step(self.denom // 2)

        def mean_loss(self, outs):
            return 2.0 * super().mean_loss(outs)

    return {"state_unchanged": StateUnchanged, "half_batch": HalfBatch}[fault]


@pytest.mark.parametrize("workload", ["bert_base.train_b64x512",
                                      "resnet50_v1.train_b128"])
def test_a_sound_rehearsal_run_is_correct(harness, bench_with_pending, workload):
    result = harness.run_cell(_args(workload), rehearse=True, bench=bench_with_pending)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"] == {}          # a CPU run prints no metric
    assert list(result)[-1] == "checks"
    held = {k: row for k, row in result["checks"].items() if row["limit"] is not None}
    assert {"grad_gap", "change_gap"} <= set(held)
    for row in held.values():
        assert row["value"] <= row["limit"]


@pytest.mark.parametrize("workload,fault,number", [
    ("bert_base.train_b64x512", "state_unchanged", "change_gap"),
    ("bert_base.train_b64x512", "half_batch", "grad_gap"),
    ("resnet50_v1.train_b128", "state_unchanged", "change_gap"),
    ("resnet50_v1.train_b128", "half_batch", "grad_gap"),
])
def test_a_broken_timed_path_is_not_correct(harness, bench_with_pending,
                                            monkeypatch, workload, fault, number):
    loop = importlib.import_module("loops.gluon_train")
    monkeypatch.setattr(loop, "Run", _faulty(loop.Run, fault))
    result = harness.run_cell(_args(workload), rehearse=True, bench=bench_with_pending)
    assert result["correct"] is False
    row = result["checks"][number]
    assert row["value"] > row["limit"]
    if fault == "state_unchanged":   # 1 where the state is there and unmoved;
        assert row["value"] >= 1.0 - 1e-6   # inf where the optimizer made none


@pytest.mark.parametrize("workload,control,admitted", [
    ("bert_base.train_b64x512", "fp8", "bf16"),      # rehearsed in bfloat16
    ("resnet50_v1.train_b128", "bf16", "exact"),     # rehearsed in float32
])
def test_the_control_is_not_correct(harness, bench_with_pending, chip_path,
                                    workload, control, admitted):
    """The reference, put in the program's place and computed in the nearest
    precision below the one the (rehearsal) configuration states, fails the
    limits; computed in the stated precision it passes them."""
    import compare
    from spans import Spans

    cell, cfg, traffic, shape = harness.find_cell(bench_with_pending, workload,
                                                  rehearse=True)
    loop = importlib.import_module("loops.gluon_train")
    run = loop.Run(cfg, traffic, shape, 1, 5, Spans(False), rehearse=True)
    run.build()
    ref = run.reference()
    for precision, expected in ((control, False), (admitted, True)):
        side = ref if precision == "exact" else run.reference(precision=precision)
        ok, table = compare.judge(compare.numbers(side, ref)[0], cfg["limits"])
        assert ok is expected, (precision, table)
