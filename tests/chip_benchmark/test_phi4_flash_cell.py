"""The phi4_mini_flash.train_8k cell's own code, on the CPU at rehearsal
width: a sound run is ``correct``; the float8 control and a planted fault
(half of the batch left out) read over the rehearsal limits, the bfloat16
witness under them; the layers held are the published rule's at those
indices; the parameter count of the whole published model; the work
functions by hand; the new reader."""
import argparse
import importlib
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
CELL = "phi4_mini_flash.train_8k"


@pytest.fixture(scope="module")
def chip_path():
    sys.path.insert(0, CHIP)
    sys.path.insert(0, ROOT)
    yield CHIP
    sys.path.remove(CHIP)
    sys.path.remove(ROOT)


@pytest.fixture()
def harness(chip_path):
    return importlib.import_module("run")


def _cfg():
    with open(os.path.join(CHIP, "configs", "phi4_mini_flash.json")) as f:
        return json.load(f)


# ---- the configuration's file --------------------------------------------------------

@pytest.mark.parametrize("key", sorted(_cfg()["published"]))
def test_every_published_key_not_reduced_is_kept(key):
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    if key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key]
    else:
        assert cfg[key] == cfg["published"][key]


def test_the_cut_is_written_out():
    cfg = _cfg()
    assert cfg["published_layers"] == cfg["published"]["num_hidden_layers"] == 32
    assert cfg["layers_held"] == [14, 20] and cfg["num_hidden_layers"] == 6
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["mamba_expand"] * cfg["hidden_size"] == 5120
    assert cfg["mamba_dt_rank"] == math.ceil(cfg["hidden_size"] / 16) == 160
    for key in ("deployment", "cut", "departures", "assumed", "limits", "limits_from"):
        assert cfg[key], key
    r = cfg["rehearsal"]
    assert r["layers_held"] == [0, r["published_layers"]] == [0, 8]   # every kind


def test_the_layers_held_are_the_published_rules_at_those_indices(chip_path):
    mod = importlib.import_module("models.phi4_flash")
    ref = importlib.import_module("reference.phi4_flash")
    kinds = mod.sizes(_cfg())["kinds"]
    assert kinds == {14: "mamba", 15: "window", 16: "mamba_source",
                     17: "attention_source", 18: "gmu", 19: "cross"}
    for index, kind in kinds.items():       # the reference writes the rule out itself
        assert ref._kind(index, 32, 2) == kind
    rehearsal = mod.sizes({**_cfg(), **_cfg()["rehearsal"]})["kinds"]
    assert set(rehearsal.values()) == set(kinds.values())


def test_the_whole_published_model_counts_the_cards_parameters(chip_path):
    mod = importlib.import_module("models.phi4_flash")
    cfg = _cfg()
    whole = {**cfg, "layers_held": [0, 32], "vocab_size": 200064}
    n = sum(math.prod(shape) for _, shape, _, _ in mod.param_specs(whole))
    assert n == 3_852_562_944                   # the "3.8B" of its card
    held = sum(math.prod(shape) for _, shape, _, _ in mod.param_specs(cfg))
    assert held == 697_094_272                  # 11.15 GB at 16 bytes each
    by_kind = {}
    for name, shape, _, _ in mod.param_specs(cfg):
        if "_layer" in name:
            index = int(name.split("_layer")[1].split("_")[0])
            by_kind[index] = by_kind.get(index, 0) + math.prod(shape)
    assert by_kind[14] == by_kind[16] and by_kind[15] == by_kind[17]
    assert [round(by_kind[i] / 1e6, 1) for i in (14, 15, 18, 19)] \
        == [119.9, 98.3, 104.9, 91.8]


# ---- work from shapes, by hand ---------------------------------------------------------

def test_phi4_flash_flops_and_kernel_work_by_hand(chip_path):
    mod = importlib.import_module("models.phi4_flash")
    cfg = _cfg()
    shape = {"batch": 2, "seq_len": 8192}
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2560 * 5120 + 2560 * 2560
    mlp = 3 * 2560 * 10240
    per_token = (2 * mamba + 2 * attn + 2 * 2560 * 5120 + 2 * 2560 * 2560
                 + 6 * mlp + 2560 * 25008)
    assert mod.matmul_params_per_token(cfg) == per_token
    band = 8192 * 512 - 512 * 511 // 2
    half = 8192 * 8193 // 2
    flops = 3 * (8192 * 2 * per_token + 20 * (band + 2 * half) * 512
                 + 2 * 8192 * 5120 * 16 * 7)
    assert mod.flops_per_sample(cfg, shape) == flops
    assert 72e12 < 2 * flops < 76e12                      # a step of two rows
    assert mod.ssm_work(cfg, shape) == {
        "flops": float(2 * 2 * 8192 * 3 * 5120 * 16 * 7),
        "bytes": float(2 * 2 * 8192 * 2 * 2 * (3 * 5120 + 32))}
    q, kv = 2560, 2560
    assert mod.swa_attention_work(cfg, shape) == {
        "flops": float(2 * 20 * band * 3 * 512),
        "bytes": float(2 * 8192 * 2 * (2 * q + kv + 4 * q + q + kv + kv))}
    assert mod.yoco_attention_work(cfg, shape) == {
        "flops": float(2 * 2 * 20 * half * 3 * 512),
        "bytes": float(2 * 8192 * 2 * (2 * (2 * q + kv + 4 * q + q + kv) + kv))}


# ---- `correct`, at rehearsal width ------------------------------------------------------

def test_a_sound_rehearsal_run_is_correct(harness):
    args = argparse.Namespace(workload=CELL, seed=5, seconds=0.3, trace=0)
    result = harness.run_cell(args, rehearse=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    held = {k for k, row in result["checks"].items() if row["limit"] is not None}
    assert {"grad_gap_mean", "change_gap_mean"} <= held


@pytest.fixture(scope="module")
def sides(chip_path):
    """The reference and three more followers of the same three batches: the
    float8 control, the bfloat16 witness, half of the batch left out."""
    import compare
    from spans import Spans

    harness = importlib.import_module("run")
    cell, cfg, traffic, shape = harness.find_cell(harness.load_benchmark(), CELL,
                                                  rehearse=True)
    loop = importlib.import_module("loops.gluon_train_lean")
    run = loop.Run(cfg, traffic, shape, 1, 5, Spans(False), rehearse=True)
    run.build()
    ref = run.reference()
    half = slice(0, run.shape["batch"] // 2)
    out = {}
    for name, kwargs in (("fp8", {"precision": "fp8"}), ("bf16", {"precision": "bf16"}),
                         ("half_batch", {"keep_rows": half})):
        out[name] = compare.judge(compare.numbers(run.reference(**kwargs), ref)[0],
                                  cfg["limits"])
    return out


@pytest.mark.parametrize("side,expected", [("fp8", False), ("half_batch", False),
                                           ("bf16", True)])
def test_the_control_and_the_fault_are_not_correct_and_the_witness_is(
        sides, side, expected):
    ok, table = sides[side]
    assert ok is expected, table
    if not expected:
        assert any(row["limit"] is not None and row["value"] > row["limit"]
                   for row in table.values())


# ---- the new reader ---------------------------------------------------------------------

def test_program_counter_reader_reads_a_ratio_and_nothing_without_the_tallies(chip_path):
    reader = importlib.import_module("readers.program_counter")
    args = dict(numerator="flash_tiles_live", denominator="flash_tiles")
    ctx = {"program_counters": {"setup": {}, "window": {"flash_tiles": 400,
                                                        "flash_tiles_live": 90}}}
    assert reader.read(ctx, **args) == pytest.approx(22.5)
    assert reader.read({}, **args) is None                          # another loop
    assert reader.read({"program_counters": {"setup": {}, "window": {"invokes": 3}}},
                       **args) is None                              # the parent's program


def test_the_cell_and_its_metrics_are_registered_with_their_files(chip_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4_mini_flash", "train_long_rows", 1)
    mod = importlib.import_module("models.phi4_flash")
    for name in ("ssm_scan_roofline", "swa_flash_roofline", "yoco_flash_roofline",
                 "flash_live_tile_pct"):
        entry = {m["name"]: m for m in bench["per_layer"]}[name]
        assert entry["workloads"] == [CELL]
        with open(os.path.join(CHIP, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in entry if k != "workloads"} \
            == {k: v for k, v in entry.items() if k != "workloads"}
        importlib.import_module("readers." + spec["reader"])
        if "work" in spec["args"]:
            assert callable(getattr(mod, spec["args"]["work"]))
