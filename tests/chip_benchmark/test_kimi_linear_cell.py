"""The kimi_linear_48b_a3b.train_8k cell's own code, on the CPU at rehearsal
width: a sound run is ``correct``, the planted faults and the lower-precision
control are not, the FLOPs and the three kernels' work by hand, and the lean
follower (optimizer state in host memory) reads what ``reference/train.py``'s
``follow`` reads."""
import argparse
import importlib
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
CELL = "kimi_linear_48b_a3b.train_8k"


@pytest.fixture(scope="module")
def chip_path():
    sys.path.insert(0, CHIP)
    yield CHIP
    sys.path.remove(CHIP)


@pytest.fixture()
def harness(chip_path, monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    return importlib.import_module("run")


def _cfg():
    with open(os.path.join(CHIP, "configs", "kimi_linear_48b_a3b.json")) as f:
        return json.load(f)


def _args(seed=5):
    return argparse.Namespace(workload=CELL, seed=seed, seconds=0.3, trace=0)


# ---- the configuration's file --------------------------------------------------------

def test_every_published_width_is_kept_and_the_cut_is_written_out():
    cfg = _cfg()
    pub = cfg["published"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                              "linear_attn_config"]
    for key, value in pub.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    lc, plc = cfg["linear_attn_config"], pub["linear_attn_config"]
    assert {k: v for k, v in lc.items() if not k.endswith("_layers")} \
        == {k: v for k, v in plc.items() if not k.endswith("_layers")}
    assert lc["kda_layers"] == [1, 2, 3, 5] and lc["full_attn_layers"] == [4]
    assert cfg["num_hidden_layers"] == 5 and cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["num_experts"] == 8 and cfg["experts_held"] == [0, 8]
    assert cfg["router_experts"] == pub["num_experts"] == 256
    for key in ("deployment", "departures", "assumed", "limits_from"):
        assert cfg[key]
    r = cfg["rehearsal"]
    assert r["linear_attn_config"]["kda_layers"] == [1, 2, 3, 5]   # every kind of layer


# ---- work from shapes, by hand ---------------------------------------------------------

def test_kimi_linear_flops_and_kernel_work_by_hand(chip_path):
    mod = importlib.import_module("models.kimi_linear")
    cfg = _cfg()
    shape = {"batch": 2, "seq_len": 8192}
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304
    expert = 3 * 2304 * 1024
    moe = 2304 * 256 + expert + expert * 8 * 8 / 256
    per_token = 4 * kda + mla + 4 * moe + 3 * 2304 * 9216 + 2304 * 20480
    assert mod.matmul_params_per_token(cfg) == per_token == 335_593_472
    flops = 3 * 8192 * (2 * per_token + 32 * 8192 * 320 + 4 * 32 * 6 * 128 * 128)
    assert mod.flops_per_sample(cfg, shape) == flops
    assert 37.7e12 < 2 * flops < 37.8e12                  # a step of two rows
    specs = mod.param_specs(cfg)
    n = sum(math.prod(s) for name, s, _, _ in specs if "running_" not in name)
    assert n == 602_433_408                                # 9.64 GB at 16 bytes each
    token_heads = 2 * 8192 * 32 * 4
    assert mod.kda_work(cfg, shape) == {
        "flops": float(token_heads * 3 * 6 * 128 * 128),
        "bytes": float(token_heads * 2 * (2 * (4 * 128 + 1) + 4 * 128))}
    assert mod.moe_expert_work(cfg, shape) == {
        "flops": float(4 * 3 * 2 * expert * (16384 * 8 * 8 / 256)),
        "bytes": float(4 * 8 * expert * 2)}
    assert mod.mla_attention_work(cfg, shape) == {
        "flops": float(2 * 32 * 3 * 8192 * 8192 * 320),
        "bytes": float(2 * 32 * 8192 * 2 * (640 + 1280))}


# ---- `correct`, at rehearsal width ------------------------------------------------------

def test_a_sound_rehearsal_run_is_correct(harness):
    result = harness.run_cell(_args(), rehearse=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    held = {k for k, row in result["checks"].items() if row["limit"] is not None}
    assert {"grad_gap", "change_gap"} <= held


@pytest.mark.parametrize("fault,number", [("state_unchanged", "change_gap"),
                                          ("half_batch", "grad_gap")])
def test_a_broken_timed_path_is_not_correct(harness, monkeypatch, fault, number):
    loop = importlib.import_module("loops.gluon_train_lean")

    class StateUnchanged(loop.Run):
        def update(self):
            pass

    class HalfBatch(loop.Run):
        def load(self, host_batch):
            return super().load(tuple(a[:len(a) // 2] for a in host_batch))

        def update(self):
            self.trainer.step(self.denom // 2)

        def mean_loss(self, outs):
            return 2.0 * super().mean_loss(outs)

    monkeypatch.setattr(loop, "Run", {"state_unchanged": StateUnchanged,
                                      "half_batch": HalfBatch}[fault])
    result = harness.run_cell(_args(), rehearse=True)
    assert result["correct"] is False
    row = result["checks"][number]
    assert row["value"] > row["limit"]


def test_the_control_is_not_correct_and_the_witness_is(harness, chip_path):
    import compare
    from spans import Spans

    cell, cfg, traffic, shape = harness.find_cell(harness.load_benchmark(), CELL,
                                                  rehearse=True)
    loop = importlib.import_module("loops.gluon_train_lean")
    run = loop.Run(cfg, traffic, shape, 1, 5, Spans(False), rehearse=True)
    run.build()
    ref = run.reference()
    for precision, expected in (("fp8", False), ("bf16", True)):
        side = run.reference(precision=precision)
        ok, table = compare.judge(compare.numbers(side, ref)[0], cfg["limits"])
        assert ok is expected, (precision, table)


# ---- the lean follower reads what reference/train.py's follow reads ----------------------

def test_the_lean_follower_matches_follow_on_the_bert_rehearsal(harness, chip_path):
    import numpy as np

    import weights as W
    from reference import lowp, train, train_lean

    cell, cfg, traffic, shape = harness.find_cell(
        harness.load_benchmark(), "bert_base.train_b64x512", rehearse=True)
    mod = importlib.import_module("models.bert_base")
    ref_mod = importlib.import_module("reference.bert_base")
    shape = {**shape, "batch": shape["batch_per_chip"]}
    rng = np.random.default_rng(3)
    batches = [mod.host_batch(cfg, shape, rng) for _ in range(3)]
    _, denom = mod.samples_and_denominator(cfg, shape)
    readings = []
    for follower in (train.follow, train_lean.follow):
        w = W.make_weights(mod, cfg, 3)
        readings.append(follower(ref_mod, cfg, mod.param_specs(cfg), w, batches, denom,
                                 cfg["optimizer"], lowp.exact, rows_per_block=2))
    full, lean = readings
    assert lean["losses"] == pytest.approx(full["losses"], rel=1e-6)
    for key in ("grad1", "change"):
        assert set(lean[key]) == set(full[key])
        for leaf, value in full[key].items():
            assert lean[key][leaf] == pytest.approx(value, rel=2e-5, abs=1e-9), (key, leaf)


# ---- the new readers ------------------------------------------------------------------

def test_moe_counters_reader_reads_the_window_and_nothing_without_counters(chip_path):
    reader = importlib.import_module("readers.moe_counters")
    setup = {"moe_slots": 30.0, "moe_dropped": 0.0, "moe_slots/a": [10.0, 20.0]}
    window = {"moe_slots": 130.0, "moe_dropped": 0.0, "moe_slots/a": [70.0, 60.0],
              "moe_slots/b": [25.0, 75.0]}
    ctx = {"program_counters": {"setup": setup, "window": window}}
    assert reader.read(ctx, what="dropped") == 0.0
    assert reader.read(ctx, what="load_max_over_mean") == pytest.approx(1.5)   # layer b
    assert reader.read({}, what="dropped") is None                 # another loop
    assert reader.read({"program_counters": {"setup": {}, "window": {"invokes": 3}}},
                       what="load_max_over_mean") is None          # a program without them


def test_scope_roofline_reads_the_time_under_a_name_scope(chip_path, monkeypatch):
    """On the recorded chip trace of the harness's own tests: the three
    matmul + tanh fusions traced under ``jit(bench_small_matmul)`` take 272.9 us
    (what ``kernel_s`` reads by the event's name), every named event 349.6 us
    of the 384.4 us busy; a scope that no event carries reads nothing."""
    from types import SimpleNamespace

    reader = importlib.import_module("readers.scope_roofline")
    trace = os.path.join(os.path.dirname(os.path.abspath(__file__)), "small.xplane.pb")
    assert reader.scope_seconds(trace, "bench_small_matmul") == pytest.approx(272.9e-6, rel=0.01)
    assert reader.scope_seconds(trace, "jit(", {0}) == pytest.approx(349.6e-6, rel=0.01)
    assert reader.scope_seconds(trace, "jit(", {1}) is None        # not this cell's device
    assert reader.scope_seconds(trace, "mxtpu_kda") is None
    monkeypatch.setattr(reader, "newest_trace", lambda: trace)
    model = SimpleNamespace(work=lambda cfg, shape: {"flops": 2e9, "bytes": 8e5})
    ctx = {"peaks": {"flops": 100e12, "bytes": 800e9}, "trace": SimpleNamespace(device_events={0: []}),
           "model_mod": model, "measured": {"steps": 3}, "cfg": {}, "shape": {}, "chips": 1}
    # least time 20 us a step (compute), three steps, over 272.9 us
    assert reader.read(ctx, scope="bench_small_matmul", work="work") == pytest.approx(21.99, rel=0.01)
    assert reader.read(ctx, scope="mxtpu_kda", work="work") is None
    assert reader.read({**ctx, "peaks": None}, scope="jit(", work="work") is None


# bert_base.train_dp4_b256x512 is not registered (PERF.md section 7: its set-up
# does not warm and one run costs 28 chip-minutes); it needs no file of its own,
# so it is rehearsed here as the entry a later PR would add.
FOUR_CHIPS = {"name": "bert_base.train_dp4_b256x512", "config": "bert_base",
              "traffic": "train_steps", "chips": 4,
              "why": "data parallel over one four-chip host, 64 rows x 512 tokens a chip"}
ALLREDUCE_SPAN = "mxtpu/trainer/allreduce"


def test_the_four_chip_cell_rehearses_and_its_allreduce_span_is_in_the_trace(harness, chip_path):
    """The same BERT loop on four (virtual CPU) contexts: correct under the
    configuration's rehearsal limits, and the span a later ``allreduce_ms.train``
    would read (reader ``program_span_ms``) is in the trace, once a step."""
    import program_spans

    bench = harness.load_benchmark()
    bench = {**bench, "workloads": bench["workloads"] + [FOUR_CHIPS]}
    args = argparse.Namespace(workload=FOUR_CHIPS["name"], seed=5, seconds=0.5, trace=1)
    result = harness.run_cell(args, rehearse=True, bench=bench)
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4
    trace = program_spans.newest_trace(
        os.path.join(ROOT, ".bench_trace", FOUR_CHIPS["name"]))
    ms = program_spans.load(trace, chips=4).ms_per_step(ALLREDUCE_SPAN)
    assert isinstance(ms, float) and ms > 0.0
