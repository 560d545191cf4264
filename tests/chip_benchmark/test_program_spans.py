"""Unit tests of the readers of the PROGRAM's spans (benchmark/chip/
program_spans.py and the readers over it): on a small trace recorded on a
TPU v5e by ``record_program_trace.py`` (two steps of the rehearsal-width
BERT; the counts below were read by hand from that trace), on a trace of a
program that writes no such span (PR 24's small trace: the parent's case),
and on one CPU-traced rehearsal run on which every new reader, called
directly, returns a number. A rehearsal run prints no metric, so
``run_cell``'s result cannot show them; ``programs_per_step.train`` needs a
device plane and is checked on the chip-recorded traces only. The tests hand
``program_spans`` the trace's path: "the newest under .bench_trace/" is only
right in a process that runs one cell."""
import gzip
import importlib
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmark", "chip")
HERE = os.path.dirname(os.path.abspath(__file__))

NEW_METRICS = {
    # name: (unit, source, layer, reader)
    "cachedop_call_ms.train": ("ms", "program_span", "graph capture", "program_span_ms"),
    "backward_ms.train": ("ms", "program_span", "autograd", "program_span_ms"),
    "trainer_update_ms.train": ("ms", "program_span", "optimizer and dispatch",
                                "program_span_ms"),
    "invoke_ms.train": ("ms", "program_span", "optimizer and dispatch", "program_span_ms"),
    "invokes_per_step.train": ("count", "program_counter", "optimizer and dispatch",
                               "invokes_per_step"),
    "programs_per_step.train": ("count", "device_trace", "device", "programs_per_step"),
    "cachedop_builds_in_window": ("count", "program_span", "graph capture",
                                  "program_span_count"),
}


@pytest.fixture(scope="module")
def chip_path():
    sys.path.insert(0, CHIP)
    yield CHIP
    sys.path.remove(CHIP)


@pytest.fixture(scope="module")
def program_spans(chip_path):
    return importlib.import_module("program_spans")


def _read(chip_path, metric, ctx):
    """The metric's reader, called as ``run.py`` calls it."""
    with open(os.path.join(CHIP, "metrics", metric + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(ctx, **spec.get("args", {}))


def _ctx(pt, steps):
    return {"program_trace": pt, "chips": 1, "measured": {"steps": steps}}


# ---- every new entry finds its file and its reader ------------------------------

@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_per_layer_entry_finds_its_file_and_reader(chip_path, name):
    unit, source, layer, reader = NEW_METRICS[name]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "train_samples_per_s"}
    with open(os.path.join(CHIP, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry} == entry and spec["reader"] == reader
    assert callable(importlib.import_module(f"readers.{reader}").read)


# ---- the trace recorded on a TPU v5e ----------------------------------------------

@pytest.fixture(scope="module")
def chip_trace(program_spans, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("program_trace") / "program.xplane.pb")
    with gzip.open(os.path.join(HERE, "program.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return program_spans.load(path, chips=1)


def test_chip_trace_spans_nest_as_the_table_says(chip_trace):
    # two steps of a 2-layer BERT with 37 parameters: per step one CachedOp
    # call, one backward, one Trainer.step holding allreduce and update, and
    # 38 op spans (37 x adamw_update inside update, the CachedOp inside call)
    pt = chip_trace
    assert len(pt.spans) == 86 and len(pt.steps) == 2
    names = [s.name for s in pt.spans if not s.name.startswith("mxtpu/op/")]
    assert names == ["mxtpu/cachedop/call", "mxtpu/autograd/backward",
                     "mxtpu/trainer/step", "mxtpu/trainer/allreduce",
                     "mxtpu/trainer/update"] * 2
    by = {}
    for i, s in enumerate(pt.spans):
        by.setdefault(s.name, []).append((i, s))
    assert len(by["mxtpu/op/adamw_update"]) == 74
    assert len(by["mxtpu/op/CachedOp_mlm0"]) == 2
    for k, (i, step) in enumerate(by["mxtpu/trainer/step"]):
        assert step.parent is None
        assert step.stats == {"batch_size": 128, "step": 4 + k, "invokes": 37}
        for name, attr in (("mxtpu/trainer/allreduce", {"keys": 0, "invokes": 0}),
                           ("mxtpu/trainer/update", {"params": 37, "invokes": 37})):
            j, child = by[name][k]
            assert child.parent == i and child.stats == attr
            assert step.start <= child.start and child.end <= step.end
        update = by["mxtpu/trainer/update"][k][0]
        assert sum(s.parent == update for _, s in by["mxtpu/op/adamw_update"]) == 37
        call_i, call = by["mxtpu/cachedop/call"][k]
        assert call.stats == {"block": "mlm0", "built": 0, "invokes": 1}
        assert by["mxtpu/op/CachedOp_mlm0"][k][1].parent == call_i
        assert by["mxtpu/autograd/backward"][k][1].stats == {"nodes": 1, "invokes": 0}
    assert [pt.step_of(s) for _, s in by["mxtpu/cachedop/call"]] == [0, 1]
    assert "mxtpu/cachedop/build" not in by and "mxtpu/kvstore/pushpull" not in by


def test_chip_trace_device_programs_by_hand(chip_trace):
    # the XLA Modules line of /device:TPU:0: 1,286 programs in two steps. Per
    # step 37 parameters x 17 (adamw_update's 15 primitives: 7 multiply,
    # 4 add, square, sqrt, divide, subtract; the gradient's and the weight's
    # cast outside invoke) + 14 (forward, backward, key split and unstack,
    # 4 casts and 6 broadcasts of the batch and the head gradient)
    mods = chip_trace.modules
    assert list(mods) == [0] and len(mods[0]) == 1286 == 2 * (37 * 17 + 14)
    count = {}
    for name, _, _ in mods[0]:
        count[name.split("(")[0]] = count.get(name.split("(")[0], 0) + 1
    assert count == {"jit_multiply": 518, "jit_add": 296, "jit_square": 74,
                     "jit_sqrt": 74, "jit_true_divide": 74, "jit_subtract": 74,
                     "jit_convert_element_type": 156, "jit_broadcast_in_dim": 12,
                     "jit_traced": 4, "jit__threefry_split": 2, "jit__unstack": 2}
    assert chip_trace.programs_per_step() == 643.0
    assert chip_trace.programs_per_step(steps=4) == 321.5


@pytest.mark.parametrize("metric,expected", [
    ("cachedop_call_ms.train", 6.784),       # (7.068 + 6.501) / 2
    ("backward_ms.train", 10.107),           # (10.379 + 9.835) / 2
    ("trainer_update_ms.train", 220.665),    # (220.899 + 220.431) / 2
    ("invoke_ms.train", 197.411),            # 74 x adamw_update + 2 x CachedOp
    ("invokes_per_step.train", 38.0),
    ("programs_per_step.train", 643.0),
    ("cachedop_builds_in_window", 0.0),
])
def test_new_readers_on_the_chip_trace(chip_path, chip_trace, metric, expected):
    value = _read(chip_path, metric, _ctx(chip_trace, steps=2))
    assert isinstance(value, float)
    assert value == pytest.approx(expected, rel=1e-4)


def test_the_inside_spans_are_the_shorter_twins(chip_trace):
    # Trainer.step = update + allreduce + the two init tests; ops are inside
    pt = chip_trace
    step, update = pt.ms_per_step("mxtpu/trainer/step"), pt.ms_per_step("mxtpu/trainer/update")
    assert 0 < step - update - pt.ms_per_step("mxtpu/trainer/allreduce") < 0.05
    assert pt.ms_per_step("mxtpu/op/*") < update + pt.ms_per_step("mxtpu/cachedop/call")
    assert pt.ms_per_step("mxtpu/kvstore/pushpull") is None
    assert pt.invokes_per_step() == (38.0, 38.0)


def test_report_by_hand(program_spans, chip_trace):
    lines = []
    program_spans.report(chip_trace, step=1, out=lines.append)
    text = "\n".join(lines)
    assert "86 mxtpu/ spans, 2 steps" in text
    assert "invokes 38.0 (counter) / 38.0 (events), programs 643.0" in text
    assert "    mxtpu/trainer/update" in text            # nested under step
    assert any(ln.split()[:3] == ["adamw_update", "37", "x"] for ln in lines)


# ---- a program that writes no span of its own (the parent commit's case) ----------

@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_readers_find_nothing_in_a_trace_without_program_spans(
        chip_path, program_spans, metric):
    pt = program_spans.load(os.path.join(HERE, "small.xplane.pb"), chips=1)
    assert pt.spans == [] and pt.steps == []
    value = _read(chip_path, metric, _ctx(pt, steps=3))
    if metric == "programs_per_step.train":   # the device plane is there: 6 programs
        assert value == 2.0
    else:
        assert value is None


# ---- the rules, on spans made by hand ---------------------------------------------

def _made(program_spans, rows, modules=None):
    """rows: (name, start, end, stats, parent index)."""
    spans = []
    for name, s, e, stats, parent in rows:
        top = len(spans) if parent is None else spans[parent].top
        spans.append(program_spans.Span(name, s, e, stats, parent, top))
    return program_spans.ProgramTrace(spans, modules or {})


def test_steps_outermost_matches_and_what_follows_the_last_step(program_spans):
    step = "mxtpu/trainer/step"
    pt = _made(program_spans, [
        ("mxtpu/op/outer", 0.0, 0.4, {}, None),           # 0: step 0
        ("mxtpu/op/inner", 0.1, 0.2, {}, 0),              # 1: inside an op: time not summed
        (step, 0.5, 1.0, {"invokes": 1}, None),           # 2
        ("mxtpu/op/a", 0.6, 0.7, {}, 2),                  # 3
        ("mxtpu/cachedop/call", 1.0, 1.5, {"invokes": 1}, None),   # 4: step 1
        ("mxtpu/op/b", 1.1, 1.2, {}, 4),                  # 5
        (step, 1.5, 2.0, {"invokes": 0}, None),           # 6
        ("mxtpu/op/late", 2.5, 2.6, {}, None),            # 7: after the last step
    ])
    assert [pt.step_of(s) for s in pt.spans] == [0, 0, 0, 0, 1, 1, 1, None]
    assert pt.ms_per_step("mxtpu/op/*") == pytest.approx(1e3 * (0.4 + 0.1 + 0.1) / 2)
    assert pt.ms_per_step("mxtpu/cachedop/call") == pytest.approx(250.0)
    assert pt.ms_per_step("mxtpu/cachedop/build") is None
    assert pt.invokes_per_step() == (2.0, 2.0)   # under an outermost op, by the events
    assert pt.count("mxtpu/cachedop/call") == 1 and pt.count("mxtpu/cachedop/build") == 0
    assert pt.programs_per_step() is None


def test_invokes_reader_reports_nothing_where_counts_disagree(chip_path, program_spans,
                                                              capsys):
    """It says why on stderr and does not raise: a traced run keeps its
    other metrics (an op dispatched by another thread would do this)."""
    reader = importlib.import_module("readers.invokes_per_step")
    step = "mxtpu/trainer/step"
    lost = _made(program_spans, [(step, 0.0, 1.0, {"invokes": 2}, None),
                                 ("mxtpu/op/a", 0.1, 0.2, {}, 0)])
    assert reader.read(_ctx(lost, steps=1)) is None
    assert "counter says 2.0" in capsys.readouterr().err
    sound = _made(program_spans, [(step, 0.0, 1.0, {"invokes": 1}, None),
                                  ("mxtpu/op/a", 0.1, 0.2, {}, 0)])
    assert reader.read(_ctx(sound, steps=1)) == 1.0
    assert reader.read(_ctx(_made(program_spans, []), steps=1)) is None


def test_the_newest_trace_is_found_by_modification_time(program_spans, tmp_path):
    assert program_spans.newest_trace(str(tmp_path)) is None
    old = tmp_path / "cell_a" / "plugins" / "profile" / "2026_01_01" / "h.xplane.pb"
    new = tmp_path / "cell_b" / "plugins" / "profile" / "2025_01_01" / "h.xplane.pb"
    for p, t in ((old, 1_000_000_000), (new, 1_000_000_100)):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
        os.utime(p, (t, t))
    assert program_spans.newest_trace(str(tmp_path)) == str(new)


# ---- one CPU-traced rehearsal run: every new reader returns a number ---------------

@pytest.fixture(scope="module")
def cpu_trace(chip_path, program_spans, tmp_path_factory):
    recorder = importlib.import_module("record_program_trace")
    out = str(tmp_path_factory.mktemp("cpu_program_trace"))
    sys.path.insert(0, ROOT)
    try:
        recorder.main(out=out, cpu=True)
    finally:
        sys.path.remove(ROOT)
    return program_spans.load(os.path.join(out, "program.xplane.pb"), chips=1)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_readers_on_a_cpu_traced_rehearsal(chip_path, cpu_trace, metric):
    value = _read(chip_path, metric, _ctx(cpu_trace, steps=None))
    if metric == "programs_per_step.train":    # no device plane on the CPU
        assert value is None
    elif metric == "invokes_per_step.train":   # 37 x adamw_update + the CachedOp
        assert value == 38.0
    elif metric == "cachedop_builds_in_window":
        assert value == 0.0
    else:
        assert isinstance(value, float) and value > 0.0


def test_the_cpu_rehearsal_shows_the_same_spans_as_the_chip(cpu_trace, chip_trace):
    def shape(pt):   # but for the block's serial number and the step's
        return [(s.name.rstrip("0123456789"),
                 {k: v for k, v in s.stats.items() if k not in ("step", "block")})
                for s in pt.spans]
    assert shape(cpu_trace) == shape(chip_trace)
    assert cpu_trace.modules == {}
