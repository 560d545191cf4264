"""The sdar_30b_a3b.train_bd_8k cell's own code, on the CPU at rehearsal
width: the configuration keeps every published key it does not list as
reduced; the parameter count of the whole published model and of the cut;
the work functions by hand; a sound run is ``correct`` and a float32 program
is the reference's to rounding; the float8 control and four planted faults
(a noisy block that sees its own clean block, a noisy block that is causal,
1/t dropped, half of the batch left out) read over the rehearsal limits, the
bfloat16 witness under them; the mask witness reads the kernel under its
limits and the mask's two faults over them; the chip limits between their
readings; the cell's registration and the metrics it is appended to."""
import argparse
import importlib
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
CELL = "sdar_30b_a3b.train_bd_8k"
# the accepted metrics this cell is appended to, and the two it brings
SHARED = ("moe_expert_roofline", "moe_dropped_slots.train", "moe_load_max_over_mean.train",
          "device_experts_ms.train", "moe_dense_branch_ms.train")
METRICS = SHARED + ("bdiff_attn_roofline", "bdiff_live_tile_pct")


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
    with open(os.path.join(CHIP, "configs", "sdar_30b_a3b.json")) as f:
        return json.load(f)


# ---- the configuration's file --------------------------------------------------------

@pytest.mark.parametrize("key", sorted(_cfg()["published"]))
def test_every_published_key_not_reduced_is_kept(key):
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    if key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key]
    else:
        assert cfg[key] == cfg["published"][key]


def test_the_published_keys_are_the_catalogs():
    """Against the model-config catalog, where MODEL_CONFIG_CATALOG names its
    architectures.jsonl: key for key."""
    path = os.environ.get("MODEL_CONFIG_CATALOG", "")
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if '"SDAR-30B-A3B-Chat"' in line]
    assert rows and rows[0]["config"] == _cfg()["published"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["sdar_30b_a3b"]
    assert entry["source"] == rows[0]["source_url"]


def test_the_widths_are_published_and_the_cut_is_written_out():
    cfg = _cfg()
    widths = dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
                  head_dim=128, moe_intermediate_size=768, num_experts_per_tok=8,
                  rope_theta=1000000, rms_norm_eps=1e-6, norm_topk_prob=True)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["router_experts"] == cfg["published"]["num_experts"] == 128
    assert cfg["experts_held"] == [0, 16] and cfg["num_experts"] == 16
    assert cfg["layers_held"] == [0, 6] and cfg["num_hidden_layers"] == 6
    assert cfg["published_layers"] == cfg["published"]["num_hidden_layers"] == 48
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    assert (cfg["block_length"], cfg["noise_level_min"]) == (4, 0.001)
    assert cfg["traffic_shapes"]["train_long_rows"] == {"batch_per_chip": 1,
                                                        "seq_len": 8192}
    for key in ("deployment", "departures", "assumed", "limits", "limits_from",
                "fallback"):
        assert cfg[key], key
    for key in ("block_length", "noise_schedule", "target", "mask_token_id", "qk_norm"):
        assert key in cfg["assumed"], key
    r = cfg["rehearsal"]
    assert (r["hidden_size"], r["num_attention_heads"], r["router_experts"],
            r["experts_held"], r["vocab_size"], r["num_hidden_layers"]) == (
                64, 4, 8, [0, 4], 256, 2)


def test_the_whole_published_model_and_the_cut_count_the_issues_parameters(chip_path):
    mod = importlib.import_module("models.sdar")
    cfg = _cfg()

    def count(c, prefix=""):
        return sum(math.prod(shape) for name, shape, _, _ in mod.param_specs(c)
                   if "running_" not in name and name.startswith(prefix))

    whole = {**cfg, "layers_held": [0, 48], "experts_held": [0, 128],
             "vocab_size": 151936}
    assert count(whole) == 30_532_122_624                 # the "30B"
    assert count(cfg) == 645_623_296                      # 10.33 GB at 16 bytes each
    assert count(cfg, "sdar_layer3_") == 94_638_336
    # q, k, v, o, the q / k gains and the attention norm's gain
    assert count(cfg, "sdar_layer3_attn_") == 18_874_368 + 2 * 128 + 2048
    assert count(cfg, "sdar_embed_") + count(cfg, "sdar_head_") == 77_791_232
    # active a token: everything but the experts not chosen (the "A3B")
    assert count(whole) - 48 * (128 - 8) * 3 * 2048 * 768 == 3_353_032_704


# ---- work from shapes, by hand ---------------------------------------------------------

def test_the_live_pairs_are_the_masks(chip_path):
    mod = importlib.import_module("models.sdar")
    assert mod.live_pairs(8192, 4) == 8192 * 8192 + 8192 * 4 == 67_141_632
    for length, block in ((16, 4), (18, 4), (10, 3), (7, 1)):
        want = 0
        for t in range(2 * length):
            for s in range(2 * length):
                tn, sn = t >= length, s >= length
                bt, bs = (t - length * tn) // block, (s - length * sn) // block
                want += (not sn and bs <= bt) if not tn else (bs < bt if not sn else bs == bt)
        assert mod.live_pairs(length, block) == want


def test_sdar_flops_and_kernel_work_by_hand(chip_path):
    mod = importlib.import_module("models.sdar")
    cfg = _cfg()
    shape = {"batch": 1, "seq_len": 8192}
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    moe = 2048 * 128 + 3 * 2048 * 768 * 8 * 16 / 128
    assert mod.matmul_params_per_token(cfg) == 6 * (attn + moe)
    pairs = 67_141_632
    forward = (16384 * 2 * 6 * (attn + moe) + 8192 * 2 * 2048 * 18992
               + 6 * 32 * pairs * 4 * 128)
    assert mod.flops_per_sample(cfg, shape) == 3 * forward
    assert mod.block_diffusion_attention_work(cfg, shape) == {
        "flops": float(6 * 32 * pairs * 128 * 14),
        "bytes": float(6 * 16384 * 2 * 2 * (2 * 4096 + 2 * 512))}
    slots = 16384 * 8 * 16 / 128
    assert slots == 16384                                  # 1,024 tokens a held expert
    expert = 3 * 2048 * 768
    assert mod.moe_expert_work(cfg, shape) == {
        "flops": float(6 * 3 * 2 * expert * slots),
        "bytes": float(6 * 16 * expert * 2)}


# ---- `correct`, at rehearsal width ------------------------------------------------------

def test_a_sound_rehearsal_run_is_correct(harness):
    args = argparse.Namespace(workload=CELL, seed=5, seconds=0.3, trace=0)
    result = harness.run_cell(args, rehearse=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    held = {k for k, row in result["checks"].items() if row["limit"] is not None}
    assert {"grad_gap", "grad_gap_mean", "change_gap"} <= held


@pytest.fixture(scope="module")
def sides(chip_path):
    """The program and six followers of the same three batches: the float8
    control, the bfloat16 witness, and the reference with each of four faults
    planted (the readings a program with that fault would give); and the
    program in float32."""
    import compare
    from spans import Spans

    harness = importlib.import_module("run")
    cell, cfg, traffic, shape = harness.find_cell(harness.load_benchmark(), CELL,
                                                  rehearse=True)
    loop = importlib.import_module("loops.gluon_train_lean")
    run = loop.Run(cfg, traffic, shape, 1, 5, Spans(False), rehearse=True)
    run.build()
    run.check_steps()
    ref = run.reference()

    def judged(readings):
        nums = compare.numbers(readings, ref)[0]
        return compare.judge(nums, cfg["limits"]) + (nums,)

    out = {"program": judged(run.readings)}
    for name, kwargs in (("fp8", {"precision": "fp8"}), ("bf16", {"precision": "bf16"})):
        out[name] = judged(run.reference(**kwargs))
    calibrate = importlib.import_module("calibrate_sdar_30b_a3b")
    for name in calibrate.FAULTS:
        out[name.replace("fault_", "")] = judged(calibrate.planted(run, name))
    f32 = loop.Run({**cfg, "dtype": "float32"}, traffic, shape, 1, 5, Spans(False),
                   rehearse=True)
    f32.build()
    f32.check_steps()
    out["float32"] = compare.numbers(f32.readings, f32.reference())[0]
    return out


@pytest.mark.parametrize("side,expected", [
    ("program", True), ("bf16", True), ("fp8", False), ("sees_own_clean_block", False),
    ("noisy_causal", False), ("no_inverse_t", False), ("half_batch", False)])
def test_the_control_and_the_faults_are_not_correct_and_the_witness_is(
        sides, side, expected):
    ok, table, _ = sides[side]
    assert ok is expected, table
    if not expected:
        assert any(row["limit"] is not None and row["value"] > row["limit"]
                   for row in table.values())


def test_a_float32_program_is_the_reference_to_rounding(sides):
    """The program in float32 against the float32 reference: the model is the
    reference's; what the bfloat16 program reads above this is rounding."""
    nums = sides["float32"]
    assert nums["loss_gap"] < 1e-6 and nums["grad_gap"] < 1e-5
    assert nums["grad_gap_mean"] < 1e-6 and nums["change_gap_mean"] < 1e-3


@pytest.mark.parametrize("seed", [5, 3900000041])
def test_the_mask_witness_tells_the_kernel_from_the_masks_faults(chip_path, seed):
    """One layer's attention at rehearsal width (interpret mode): the kernel
    against the reference's mask reads under the witness's limits, against
    each of the mask's two faults over one of them."""
    import compare

    calibrate = importlib.import_module("calibrate_sdar_30b_a3b")
    cfg = {**_cfg(), **_cfg()["rehearsal"]}
    w = cfg["mask_witness"]
    got = calibrate.mask_witness(
        seed, cfg["traffic_shapes"]["train_long_rows"]["seq_len"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["block_length"], w["rows"], interpret=True)
    assert set(got) == {"program", *calibrate.MASK_FAULTS}
    for side, nums in got.items():
        assert compare.judge(nums, w["limits"])[0] is (side == "program"), (side, nums)


# ---- the registration -------------------------------------------------------------------

def test_the_cell_and_its_metrics_are_registered_with_their_files(chip_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar_30b_a3b", "train_long_rows", 1)
    assert len(cell["why"]) <= 200
    mod = importlib.import_module("models.sdar")
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        entry = per_layer[name]
        assert CELL in entry["workloads"]
        assert entry["workloads"] == [CELL] or name in SHARED
        with open(os.path.join(CHIP, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in entry if k != "workloads"} \
            == {k: v for k, v in entry.items() if k != "workloads"}
        importlib.import_module("readers." + spec["reader"])
        if "work" in spec["args"]:
            assert callable(getattr(mod, spec["args"]["work"]))
    assert [m["name"] for m in bench["per_layer"][-2:]] == ["bdiff_attn_roofline",
                                                            "bdiff_live_tile_pct"]


def test_the_live_tile_share_reads_the_block_diffusion_tallies(chip_path):
    with open(os.path.join(CHIP, "metrics", "bdiff_live_tile_pct.json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("readers." + spec["reader"])
    window = {"bdiff_tiles": 256 * 32 * 12, "bdiff_tiles_live": 96 * 32 * 12,
              "flash_tiles": 1, "flash_tiles_live": 1}
    ctx = {"program_counters": {"setup": {}, "window": window}}
    assert reader.read(ctx, **spec["args"]) == 37.5
    assert reader.read({"program_counters": {"setup": {}, "window": {}}},
                       **spec["args"]) is None           # a parent without the tallies
    with open(os.path.join(CHIP, "metrics", "bdiff_attn_roofline.json")) as f:
        spec = json.load(f)
    assert (spec["reader"], spec["args"]) == ("scope_roofline", {
        "scope": "mxtpu_bdiff", "work": "block_diffusion_attention_work"})


@pytest.mark.parametrize("number,sound,caught", [
    ("grad_gap", 0.0569, 0.387),           # half of the row's loss terms left out
    ("grad_gap_mean", 0.00299, 0.0231),    # the float8 control
    ("change_gap", 0.0160, 0.130)])        # 1/t dropped
def test_the_cells_limits_lie_between_their_chip_readings(number, sound, caught):
    """Each held number's limit against the program's worst reading on the
    chip and the least reading of what it is there to refuse (PERF.md section
    2), with room on both sides."""
    limit = _cfg()["limits"][number]
    assert 1.8 * sound < limit < caught / 1.25
    assert set(_cfg()["limits"]) == {"grad_gap", "grad_gap_mean", "change_gap"}


@pytest.mark.parametrize("number,sound,caught", [
    ("out_row_gap", 0.0058, 1.506),        # a noisy block seeing its own clean block
    ("dq_row_gap", 0.0198, 1.959),         # the same
    ("dkv_gap", 0.00335, 0.299)])          # a causal noisy block
def test_the_mask_witness_limits_lie_between_their_chip_readings(number, sound, caught):
    """Each of the mask witness's limits against the program's worst reading
    on the chip and the least reading of the mask's two faults (PERF.md
    section 2), with room on both sides."""
    limits = _cfg()["mask_witness"]["limits"]
    assert 1.8 * sound < limits[number] < caught / 1.25
    assert set(limits) == {"out_row_gap", "dq_row_gap", "dkv_gap"}
    assert _cfg()["rehearsal"]["mask_witness"]["limits"] == limits
