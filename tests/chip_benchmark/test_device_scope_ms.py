"""Unit tests of ``readers/device_scope_ms.py``, ``readers/device_unscoped_pct.py``
and ``scope_report.py``: on the small trace recorded on a TPU v5e
(``small.xplane.pb``, a program without block scopes: the parent's case) and
on synthetic traces written with the reader's own schema
(``scope_roofline._xspace_class``), whose events carry the names the program
gives its device work (``tests/test_block_scopes.py`` pins those on compiled
programs)."""
import importlib
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "small.xplane.pb")

NEW_METRICS = [
    "device_forward_ms.train", "device_rebuild_ms.train", "device_backward_ms.train",
    "device_update_ms.train", "device_mixer_ms.train", "device_mlp_ms.train",
    "device_norm_ms.train", "device_head_ms.train", "device_embed_ms.train",
    "device_experts_ms.train", "moe_dense_branch_ms.train", "dsa_pbar_ms.train",
    "device_unscoped_pct.train"]

MS = 10 ** 9        # picoseconds


@pytest.fixture(scope="module")
def chip_path():
    sys.path.insert(0, CHIP)
    yield CHIP
    sys.path.remove(CHIP)


@pytest.fixture(scope="module")
def reader(chip_path):
    return importlib.import_module("readers.device_scope_ms")


@pytest.fixture(scope="module")
def unscoped(chip_path):
    return importlib.import_module("readers.device_unscoped_pct")


FWD, BWD, UPD = ("jit_mxtpu_fwd_LM(11)", "jit_mxtpu_fwd_LM(22)",
                 "jit_mxtpu_update(33)")
P = "jit(mxtpu_fwd_LM)/mx.LM/mx.Model/"
LAYER = P + "mx.Layer/"
REMAT = P + "mx.Layer/mx.LM/mx.Model/mx.Layer/checkpoint/"

# one step of a traced program, in milliseconds: (start, length, tf_op)
STEP_OPS = {
    FWD: [(0, 2, P + "mx.Embedding/gather:"),
          (2, 4, LAYER + "mx.SparseGQAttention/mx.Dense/dot_general:"),
          (6, 1, LAYER + "mx.RMSNorm/mul:"),
          (7, 3, LAYER + "mx.HeldExperts/mxtpu_moe/mx.GatedMLP/dot_general:"),
          (10, 2, P + "mx._LMHead/mxtpu_softmax_xent_fwd/pallas_call:"),
          (12, 1, None)],                     # a copy XLA added: no name
    BWD: [(20, 3, P + "mx._LMHead/mxtpu_softmax_xent_bwd/pallas_call:"),
          (23, 5, REMAT + "rematted_computation/mx.SparseGQAttention/"
                          "mxtpu_dsa_attn/mxtpu_dsa_pbar/exp:"),
          # a loop's event that carries the name, and its body inside it
          (28, 6, REMAT + "mx.SparseGQAttention/mx.DenseX/while:"),
          (29, 2, REMAT + "mx.SparseGQAttention/mx.DenseX/while/body/dot_general:"),
          (34, 2, LAYER + "mx.RMSNorm/mul:")],
    UPD: [(40, 4, "jit(mxtpu_update)/mxtpu_update/add:"),
          (44, 1, "weights[0]")],
}


def write_trace(path, steps=3, step_ms=50, devices=(0,), step_ops=STEP_OPS,
                modules=None):
    """A trace of ``steps`` equal steps on each of ``devices`` (``modules``:
    the order of the step's module runs; default the dictionary's)."""
    sys.path.insert(0, CHIP)
    try:
        from readers.scope_roofline import _xspace_class
    finally:
        sys.path.remove(CHIP)
    space = _xspace_class()()
    for device in devices:
        plane = space.planes.add(name=f"/device:TPU:{device}".encode())
        stat = plane.stat_metadata.add(key=1)
        stat.value.name = b"tf_op"
        ids = {}

        def metadata(name, tf_op=None):
            if (name, tf_op) not in ids:
                ids[name, tf_op] = len(ids) + 1
                entry = plane.event_metadata.add(key=ids[name, tf_op])
                entry.value.name = name.encode()
                if tf_op is not None:
                    entry.value.stats.add(metadata_id=1, str_value=tf_op.encode())
            return ids[name, tf_op]

        runs = plane.lines.add(name=b"XLA Modules")
        ops = plane.lines.add(name=b"XLA Ops")
        for step in range(steps):
            t0 = step * step_ms
            for module in modules or step_ops:
                events = step_ops[module]
                first, last = events[0][0], max(s + n for s, n, _ in events)
                runs.events.add(metadata_id=metadata(module),
                                offset_ps=(t0 + first) * MS,
                                duration_ps=(last - first) * MS)
                for i, (start, length, tf_op) in enumerate(events):
                    ops.events.add(
                        metadata_id=metadata(f"%fusion.{i} = f32[] fusion()", tf_op),
                        offset_ps=(t0 + start) * MS, duration_ps=length * MS)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return str(path)


@pytest.fixture
def trace(tmp_path):
    return write_trace(tmp_path / "step.xplane.pb")


def test_components_unwrap_jaxs_transforms_and_keep_the_programs_name(reader):
    parts, transposed = reader.components(
        "jit(mxtpu_fwd_LM)/transpose(jvp(mx.LM))/mx.Dense/dot_general:")
    assert parts == ["jit(mxtpu_fwd_LM)", "mx.LM", "mx.Dense", "dot_general"]
    assert transposed
    assert reader.components("jit(f)/jvp(mx.LM)/mul") == (
        ["jit(f)", "mx.LM", "mul"], False)
    assert not reader.is_scope("jit(mxtpu_fwd_LM)") and reader.is_scope("mxtpu_kda")


def test_recorded_small_trace_reads_what_scope_seconds_reads(reader, unscoped,
                                                             chip_path):
    from readers.scope_roofline import scope_seconds

    ms = reader.scope_ms(SMALL, ["jit(bench_small_matmul)"])
    assert ms == pytest.approx(0.2729, rel=0.01)
    assert ms == pytest.approx(1e3 * scope_seconds(SMALL, "bench_small_matmul"))
    # a whole component, not a substring; a device the cell did not use
    assert reader.scope_ms(SMALL, ["bench_small_matmul"]) is None
    assert reader.scope_ms(SMALL, ["jit(bench_small_matmul)"], device_ids={1}) is None
    # a program that names no block: no phase, no unscoped share
    assert all(reader.scope_ms(SMALL, phase=p) is None for p in reader.PHASES)
    assert unscoped.unscoped_pct(SMALL) is None


@pytest.mark.parametrize("phase, ms", [
    ("forward", 12), ("rebuild", 5), ("backward", 11), ("update", 4)])
def test_the_phases_partition_the_scoped_events(reader, trace, phase, ms):
    # 3 steps; backward: the head's 3, the loop's 6 with its body inside
    # counted once, the norm's 2
    assert reader.scope_ms(trace, phase=phase) == pytest.approx(3 * ms)


def test_the_phases_add_up_to_the_time_under_a_scope(reader, unscoped, trace):
    device = reader.devices_of(trace)[0]
    under = device.union_ps(lambda ev: ev.scoped)
    assert sum(reader.scope_ms(trace, phase=p) for p in reader.PHASES) * 1e9 \
        == pytest.approx(under)
    # the unnamed copy and the update's parameter: 2 ms of a step's 34
    assert device.busy_ps == 3 * 34 * MS
    assert unscoped.unscoped_pct(trace) == pytest.approx(100 * 2 / 34)


@pytest.mark.parametrize("scopes, ms", [
    (["mx.Dense"], 4),                  # mx.DenseX is another block
    (["mx.DenseX"], 6),                 # the loop and its body once
    (["mx.RMSNorm", "mx.LayerNorm"], 3),
    (["mx.HeldExperts"], 3), (["mx.GatedMLP"], 3),      # the rows overlap
    (["mx._LMHead", "mxtpu_softmax_xent_fwd", "mxtpu_softmax_xent_bwd"], 5),
    (["mxtpu_dsa_pbar"], 5), (["mxtpu_update"], 4), (["mx.LM"], 28)])
def test_a_scope_is_a_whole_component(reader, trace, scopes, ms):
    assert reader.scope_ms(trace, scopes) == pytest.approx(3 * ms)


def test_a_branch_not_taken_reads_zero_and_a_layer_not_there_nothing(reader, trace):
    assert reader.scope_ms(trace, ["mxtpu_moe_dense"],
                           within=["mx.HeldExperts"]) == 0.0
    assert reader.scope_ms(trace, ["mxtpu_moe_dense"],
                           within=["mx.NoSuchBlock"]) is None
    # a block kind the model does not have: 0.0 of a program that names blocks
    assert reader.scope_ms(trace, ["mx.MambaMixer"]) == 0.0


def test_steps_that_do_not_split_read_no_phase(reader, tmp_path):
    # a forward whose output nobody differentiated: three runs a step
    ops = dict(STEP_OPS, **{"jit_mxtpu_fwd_Eval(44)": [(15, 1, P + "mx.Dense/add:")]})
    path = write_trace(tmp_path / "odd.xplane.pb", step_ops=ops,
                       modules=[FWD, "jit_mxtpu_fwd_Eval(44)", BWD, UPD])
    assert reader.devices_of(path)[0].backward is None
    assert reader.scope_ms(path, phase="forward") is None
    assert reader.scope_ms(path, phase="rebuild") == pytest.approx(15)
    assert reader.scope_ms(path, ["mxtpu_update"]) == pytest.approx(12)


def test_mean_over_the_cells_devices(reader, tmp_path):
    path = write_trace(tmp_path / "two.xplane.pb", devices=(0, 1, 2))
    assert set(reader.devices_of(path, {0, 1})) == {0, 1}
    assert reader.scope_ms(path, phase="update", device_ids={0, 1}) \
        == pytest.approx(12)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metric_files_and_benchmark_entries_agree(chip_path, trace, metric,
                                                  monkeypatch):
    with open(os.path.join(CHIP, "metrics", metric + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    listed = entry.pop("workloads", None)
    assert entry == {k: v for k, v in spec.items() if k not in ("reader", "args")}
    assert (listed is not None) == (metric in (
        "device_experts_ms.train", "moe_dense_branch_ms.train", "dsa_pbar_ms.train"))
    # as run.py calls it, on the synthetic step and on the parent's case
    module = importlib.import_module(f"readers.{spec['reader']}")
    tr = type("T", (), {"device_events": {0: []}})()
    for path, reads in ((trace, True), (SMALL, False)):
        monkeypatch.setattr(module, "newest_trace", lambda path=path: path)
        value = module.read({"trace": tr, "measured": {"steps": 3}},
                            **spec.get("args", {}))
        assert (value is not None) == reads, (metric, path, value)
    assert module.read({"trace": None, "measured": {"steps": 3}},
                       **spec.get("args", {})) is None


def test_scope_report_prints_the_tables(chip_path, trace):
    scope_report = importlib.import_module("scope_report")
    out = io.StringIO()
    assert scope_report.report(trace, depth=2, out=out) == 0
    text = out.getvalue()
    assert "3 steps" in text and "backward programs: 1 of 2" in text
    rows = {line[:60].strip(): line[60:].split() for line in text.splitlines()
            if len(line) > 60}
    assert float(rows["forward"][0]) == pytest.approx(12)
    assert float(rows["mx.Layer/mx.RMSNorm"][-1]) == pytest.approx(3)
    # a remat'd block's repeated outer names are walked once
    assert float(rows["mx.SparseGQAttention/mx.DenseX"][-1]) == pytest.approx(8)
    assert float(rows["jit_mxtpu_fwd_LM x1 [backward]"][0]) == pytest.approx(16)
    assert float(rows["%fusion"][0]) == pytest.approx(1)
    assert scope_report.block_path(
        ["jit(f)", "mx.A", "mx.B", "mx.A", "mx.B", "checkpoint", "mx.C", "add"],
        2) == "mx.B/mx.C"
