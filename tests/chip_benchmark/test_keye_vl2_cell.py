"""The keye_vl2_30b_a3b.train_8k cell's own code, on the CPU at rehearsal
width: a sound run is ``correct``; the float8 control and two planted faults
(half of the batch left out; the selection's mask dropped, so that the
attention is dense) read over the rehearsal limits, the bfloat16 witness
under them; with the program's own selection injected the float32 program
and the reference agree tightly; the parameter count of the whole published
model; the work functions by hand; the cell's registration."""
import argparse
import importlib
import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
CELL = "keye_vl2_30b_a3b.train_8k"
METRICS = ("dsa_index_roofline", "dsa_topk_roofline", "dsa_attn_roofline",
           "dsa_selected_pct", "keye_moe_expert_roofline",
           "keye_moe_dropped_slots.train", "keye_moe_load_max_over_mean.train")


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
    with open(os.path.join(CHIP, "configs", "keye_vl2_30b_a3b.json")) as f:
        return json.load(f)


# ---- the configuration's file --------------------------------------------------------

@pytest.mark.parametrize("key", sorted(_cfg()["published"]))
def test_every_published_key_not_reduced_is_kept(key):
    cfg = _cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_local_experts", "vocab_size"]
    if key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key]
    else:
        assert cfg[key] == cfg["published"][key]


def test_the_published_keys_are_the_catalogs():
    """Where the guide's catalog is on this machine: key for key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if "Keye-VL-2.0-30B-A3B" in line]
    assert rows and rows[0]["config"] == _cfg()["published"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["keye_vl2_30b_a3b"]
    assert entry["source"] == rows[0]["source_url"]


def test_the_cut_is_written_out():
    cfg = _cfg()
    assert cfg["published_layers"] == cfg["published"]["num_hidden_layers"] == 48
    assert cfg["layers_held"] == [0, 6] and cfg["num_hidden_layers"] == 6
    assert cfg["router_experts"] == cfg["published"]["num_experts"] == 128
    assert cfg["experts_held"] == [0, 16]
    assert cfg["num_experts"] == cfg["num_local_experts"] == 16
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["traffic_shapes"]["train_long_rows"] == {"batch_per_chip": 2,
                                                        "seq_len": 8192}
    for key in ("deployment", "departures", "assumed", "limits", "limits_from"):
        assert cfg[key], key
    r = cfg["rehearsal"]
    assert (r["hidden_size"], r["num_attention_heads"], r["num_key_value_heads"],
            r["head_dim"]) == (64, 4, 2, 16)
    assert (r["sa_config"]["indexer_num_heads"], r["sa_config"]["indexer_head_dim"],
            r["sa_config"]["topk"]) == (2, 8, 16)
    assert (r["router_experts"], r["experts_held"], r["num_experts_per_tok"],
            r["moe_intermediate_size"], r["vocab_size"], r["layers_held"]) \
        == (8, [0, 4], 2, 32, 256, [0, 2])


@pytest.mark.parametrize("number,sound,faulty", [
    ("grad_gap", 0.0434, 0.909),        # one leaf gone wrong cannot hide in the mean
    ("grad_gap_mean", 0.00175, 0.01328),
    ("change_gap", 0.0154, 0.211)])
def test_the_cells_limits_lie_between_their_chip_readings(number, sound, faulty):
    """Each held number's limit against the program's worst reading and the
    least reading of what it has to refuse (PERF.md section 2, PR 33), with
    room on both sides; the worst leaf is held, not only the mean of 99."""
    limit = _cfg()["limits"][number]
    assert 2 * sound < limit < faulty / 2


def test_the_whole_published_model_counts_the_cards_parameters(chip_path):
    mod = importlib.import_module("models.keye_vl2")
    cfg = _cfg()

    def count(c):
        return sum(math.prod(shape) for name, shape, _, _ in mod.param_specs(c)
                   if "running_" not in name)

    whole = {**cfg, "layers_held": [0, 48], "experts_held": [0, 128],
             "vocab_size": 151936}
    assert count(whole) == 30_640_656_384                  # the "30B"
    assert count(cfg) == 659_190_016                       # 10.55 GB at 16 bytes each
    layer = sum(math.prod(shape) for name, shape, _, _ in mod.param_specs(cfg)
                if name.startswith("keye_layer3_") and "running_" not in name)
    assert layer == 96_899_456
    indexer = sum(math.prod(shape) for name, shape, _, _ in mod.param_specs(cfg)
                  if name.startswith("keye_layer3_attn_index_") and "norm" not in name)
    assert indexer == 2_260_992
    # active a token: everything but the experts not chosen
    active = count(whole) - 48 * (128 - 8) * 3 * 2048 * 768
    assert 3.4e9 < active < 3.5e9                          # the "A3B"


# ---- work from shapes, by hand ---------------------------------------------------------

def test_keye_vl2_flops_and_kernel_work_by_hand(chip_path):
    mod = importlib.import_module("models.keye_vl2")
    cfg = _cfg()
    shape = {"batch": 2, "seq_len": 8192}
    causal, selected = 8192 * 8193 // 2, 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert (causal, selected) == (33_558_528, 14_681_088)
    assert mod._pairs(cfg, 8192) == (causal, selected)
    assert 100 * selected / causal == pytest.approx(43.75, abs=0.05)   # 43.7477
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    index = 2048 * (1024 + 64 + 16)
    moe = 2048 * 128 + 3 * 2048 * 768 * 8 * 16 / 128
    per_token = 6 * (attn + index + moe) + 2048 * 18992
    assert mod.matmul_params_per_token(cfg) == per_token
    forward = 8192 * 2 * per_token + 6 * 32 * selected * 512 + 6 * causal * 2048
    flops = 3 * forward + 6 * 32 * selected * 256
    assert mod.flops_per_sample(cfg, shape) == flops
    assert 9.9e12 < 2 * forward < 10.3e12                  # ISSUE 33's ~10.1 TFLOP forward
    assert mod.dsa_index_work(cfg, shape) == {
        "flops": float(6 * 2 * causal * 3 * 2048),
        "bytes": float(6 * 2 * 2 * (8192 * 1104 * 2 + causal * 4))}
    assert mod.dsa_topk_work(cfg, shape) == {
        "flops": float(6 * 2 * causal), "bytes": float(6 * 2 * causal * 5)}
    assert mod.dsa_attention_work(cfg, shape) == {
        "flops": float(6 * 2 * 32 * selected * 128 * 16),
        "bytes": float(6 * 2 * 8192 * 2 * ((2 * 4096 + 1024) + (4 * 4096 + 2048)))}
    assert mod.moe_expert_work(cfg, shape) == {
        "flops": float(6 * 3 * 2 * 3 * 2048 * 768 * 16384),
        "bytes": float(6 * 16 * 3 * 2048 * 768 * 2)}


# ---- `correct`, at rehearsal width ------------------------------------------------------

def test_a_sound_rehearsal_run_is_correct(harness):
    args = argparse.Namespace(workload=CELL, seed=5, seconds=0.3, trace=0)
    result = harness.run_cell(args, rehearse=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    held = {k for k, row in result["checks"].items() if row["limit"] is not None}
    assert {"grad_gap_mean", "change_gap_mean"} <= held


@pytest.fixture(scope="module")
def rehearsal(chip_path):
    from spans import Spans

    harness = importlib.import_module("run")
    cell, cfg, traffic, shape = harness.find_cell(harness.load_benchmark(), CELL,
                                                  rehearse=True)
    loop = importlib.import_module("loops.gluon_train_lean")
    run = loop.Run(cfg, traffic, shape, 1, 5, Spans(False), rehearse=True)
    run.build()
    return run, cfg, run.reference()


def _dense_selection(ref_mod, cfg, batch):
    """Every causal pair chosen: what the program would compute if it dropped
    the mask."""
    import jax.numpy as jnp

    b, s = batch[0].shape
    return {i: jnp.ones((b, s, s), bool) for i in range(*cfg["layers_held"])}


@pytest.fixture(scope="module")
def sides(rehearsal):
    """Four more followers of the same three batches: the float8 control, the
    bfloat16 witness, half of the batch left out, the mask dropped."""
    import compare
    from reference import lowp, train_lean

    run, cfg, ref = rehearsal
    half = slice(0, run.shape["batch"] // 2)
    out = {}
    for name, kwargs in (("fp8", {"precision": "fp8"}), ("bf16", {"precision": "bf16"}),
                         ("half_batch", {"keep_rows": half})):
        out[name] = compare.judge(compare.numbers(run.reference(**kwargs), ref)[0],
                                  cfg["limits"])

    class Dense:        # the reference with every causal pair selected
        ROWS_INDEPENDENT = True

        @staticmethod
        def loss_sum(c, w, batch, lin):
            ref_mod = importlib.import_module("reference.keye_vl2")
            return ref_mod.loss_sum(c, w, batch, lin,
                                    selection=_dense_selection(ref_mod, c, batch))

    import weights as W

    w = W.make_weights(run.model_mod, cfg, run.seed, run.devices[0])
    batches = [run.pool[k] for k in range(run.traffic["check_steps"])]
    dropped = train_lean.follow(Dense, cfg, run.model_mod.param_specs(cfg), w, batches,
                                run.denom, cfg["optimizer"], lowp.PRECISIONS["exact"],
                                rows_per_block=1)
    out["mask_dropped"] = compare.judge(compare.numbers(dropped, ref)[0], cfg["limits"])
    return out


@pytest.mark.parametrize("side,expected", [("fp8", False), ("half_batch", False),
                                           ("mask_dropped", False), ("bf16", True)])
def test_the_control_and_the_faults_are_not_correct_and_the_witness_is(
        sides, side, expected):
    ok, table = sides[side]
    assert ok is expected, table
    if not expected:
        assert any(row["limit"] is not None and row["value"] > row["limit"]
                   for row in table.values())


def test_with_the_programs_selection_injected_float32_agrees_tightly(
        chip_path, monkeypatch):
    """The float32 program against the reference that is HANDED the program's
    own selection (read from an eager pass of the program's blocks): what is
    compared is everything but the selection; and the selection is the
    reference's own ``lax.top_k`` one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functionalize
    import weights as W

    harness = importlib.import_module("run")
    mod = importlib.import_module("models.keye_vl2")
    ref_mod = importlib.import_module("reference.keye_vl2")
    _, cfg, _, shape = harness.find_cell(harness.load_benchmark(), CELL, rehearse=True)
    cfg = {**cfg, "dtype": "float32", "hybridize": {}}
    model, _ = mod.build(cfg, [mx.cpu(0)])
    weights = W.make_weights(mod, cfg, 9)
    W.load_into(model.collect_params(), weights, model.prefix, [mx.cpu(0)])
    batch = mod.host_batch(cfg, {"batch": 2, "seq_len": 64}, np.random.default_rng(9))

    # an eager pass of the program's own blocks, its selections written down
    seen, select = [], mx.nd.dsa_topk_mask

    def recording(scores, **kw):
        mask, tally = select(scores, **kw)
        seen.append(jnp.asarray(mask._data) != 0)
        return mask, tally

    monkeypatch.setattr(mx.nd, "dsa_topk_mask", recording)
    model.hybridize(active=False)
    model(*[mx.nd.array(a, dtype="int32") for a in batch])
    monkeypatch.undo()
    assert len(seen) == 2 and all(m.shape == (2, 64, 64) for m in seen)
    assert all((np.asarray(m).sum(-1) == np.minimum(16, np.arange(64) + 1)).all()
               for m in seen)

    fn, params = functionalize(model, training=True, ctx=mx.cpu(0))
    trained = {k: v for k, v in params.items() if "running_" not in k}
    rest = {k: v for k, v in params.items() if "running_" in k}
    arrays = tuple(jnp.asarray(a) for a in batch)
    loss, grads = jax.value_and_grad(
        lambda p: fn({**p, **rest}, jax.random.PRNGKey(0), *arrays).sum())(trained)

    fixed = {k: v for k, v in weights.items() if "running_" in k}
    moving = {k: v for k, v in weights.items() if "running_" not in k}
    with jax.default_matmul_precision("highest"):
        want, ref_grads = jax.value_and_grad(
            lambda w: ref_mod.loss_sum(cfg, {**w, **fixed}, arrays, lambda f: f,
                                       selection=dict(enumerate(seen))))(moving)
        own = ref_mod.loss_sum(cfg, weights, arrays, lambda f: f)
    assert float(own) == pytest.approx(float(want), rel=1e-6)   # the same selection
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for name, g in ref_grads.items():
        got = grads[model.prefix + name]
        scale = float(jnp.abs(g).max()) + 1e-12
        assert float(jnp.abs(got - g).max()) / scale < 2e-4, name


# ---- the readers on this cell's counters --------------------------------------------------

def test_the_selected_share_reads_from_the_device_tallies(chip_path):
    reader = importlib.import_module("readers.program_counter")
    with open(os.path.join(CHIP, "metrics", "dsa_selected_pct.json")) as f:
        args = json.load(f)["args"]
    window = {"dsa_pairs_selected": 14_681_088.0 * 12, "dsa_pairs_causal": 33_558_528.0 * 12}
    ctx = {"program_counters": {"setup": {}, "window": window}}
    assert reader.read(ctx, **args) == pytest.approx(43.7477, abs=0.0001)
    assert reader.read({"program_counters": {"setup": {}, "window": {"invokes": 3}}},
                       **args) is None                              # the parent's program


def test_the_cell_and_its_metrics_are_registered_with_their_files(chip_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye_vl2_30b_a3b", "train_long_rows", 1)
    assert len(cell["why"]) <= 200
    mod = importlib.import_module("models.keye_vl2")
    for name in METRICS:
        entry = {m["name"]: m for m in bench["per_layer"]}[name]
        assert entry["workloads"] == [CELL]
        with open(os.path.join(CHIP, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert {k: spec[k] for k in entry if k != "workloads"} \
            == {k: v for k, v in entry.items() if k != "workloads"}
        importlib.import_module("readers." + spec["reader"])
        if "work" in spec["args"]:
            assert callable(getattr(mod, spec["args"]["work"]))
