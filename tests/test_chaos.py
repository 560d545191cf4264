"""Chaos harness (mxnet_tpu/serving/chaos.py) + the self-healing
drill (ISSUE 14 capstone): schedule parsing, the determinism golden
(same seed + schedule => identical fault sequence), fault wrap/restore
mechanics, the disabled path (CHAOS=0 patches NOTHING — the mxsan
pattern), and the end-to-end chaos drill: hot-spot weight shed + seat
kill/autoscaler replacement + router kill/in-flight adoption under
load, zero lost requests, one correlated incident per fault.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu.serving import ServingEngine
from mxnet_tpu.serving.chaos import (ChaosController, chaos_enabled,
                                     load_schedule)
from mxnet_tpu.telemetry import events

from test_selfheal import StubModel, _stub_engine, _wait  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# schedule + determinism
# ---------------------------------------------------------------------------

def test_schedule_parsing_inline_file_and_validation(tmp_path):
    sched = [{"at": 2.0, "fault": "kill_engine", "target": "e1"},
             {"at": 0.5, "fault": "hotspot", "target": "e0",
              "ms": 40, "duration_s": 1.0}]
    parsed = load_schedule(json.dumps(sched))
    assert [e["fault"] for e in parsed] == ["hotspot", "kill_engine"]
    p = tmp_path / "sched.json"
    p.write_text(json.dumps(sched))
    assert load_schedule(str(p)) == parsed
    assert load_schedule(None) == []
    with pytest.raises(ValueError):
        load_schedule('[{"fault": "meteor_strike", "target": "e0"}]')
    with pytest.raises(ValueError):
        load_schedule('[{"at": 1.0}]')


class _Tap:
    """Collect chaos_* run events (the determinism golden's record)."""

    def __init__(self):
        self.recs = []

    def __call__(self, rec):
        if str(rec.get("event", "")).startswith("chaos_"):
            self.recs.append({k: rec[k] for k in
                              ("event", "seq", "fault", "target", "at",
                               "duration_s", "ms", "p", "tag")
                              if k in rec})


def _campaign(seed):
    """One scripted campaign on a FAKE clock: returns (events, drop
    pattern of 64 frame draws) — everything the rng touches."""
    sched = [
        {"at": 0.1, "fault": "hotspot", "target": "det-e0", "ms": 5,
         "duration_s": 0.2},
        {"at": 0.3, "fault": "drop_frames", "target": "det-e0",
         "p": 0.5, "duration_s": 0.4},
    ]
    clock = [0.0]

    def fake_clock():
        clock[0] += 0.02            # each peek advances scripted time
        return clock[0]

    tap = _Tap()
    events.add_tap(tap)
    eng = _stub_engine("det-e0")
    try:
        ctl = ChaosController(schedule=sched, seed=seed,
                              clock=fake_clock, sleep=lambda s: None)
        ctl.register_engine(eng)
        # drive the schedule walk deterministically on THIS thread
        ctl._t0 = fake_clock()
        ctl._stop.clear()
        ctl._run()
        # the probabilistic fault's draw pattern (hook armed on a
        # fake listener stand-in)
        hook = ctl._frame_hook("drop", 0.5, 0.0)
        pattern = [hook("SUBMIT") for _ in range(64)]
        ctl.stop()
    finally:
        events.remove_tap(tap)
    return tap.recs, pattern


def test_chaos_determinism_same_seed_identical_sequence():
    """The determinism contract: same MXNET_TPU_CHAOS_SEED + schedule
    replays an identical fault sequence (event golden incl. rng-drawn
    frame drops); a different seed diverges."""
    ev_a, pat_a = _campaign(seed=7)
    ev_b, pat_b = _campaign(seed=7)
    assert ev_a == ev_b
    assert pat_a == pat_b
    faults = [e for e in ev_a if e["event"] == "chaos_fault"]
    assert [f["fault"] for f in faults] == ["hotspot", "drop_frames"]
    cleared = [e for e in ev_a if e["event"] == "chaos_fault_cleared"]
    assert [c["fault"] for c in cleared] == ["hotspot", "drop_frames"]
    _ev_c, pat_c = _campaign(seed=8)
    assert pat_c != pat_a           # 2^-64 false-failure odds
    assert any(pat_a) and not all(pat_a)    # p=0.5 actually drops


# ---------------------------------------------------------------------------
# fault mechanics: wrap, act, restore
# ---------------------------------------------------------------------------

def test_hotspot_and_wedge_wrap_and_restore():
    eng = _stub_engine("fx-e0")
    orig = eng._model
    ctl = ChaosController(schedule=None, seed=1)
    ctl.register_engine(eng)
    with eng:
        eng.warmup()
        t0 = time.perf_counter()
        eng.infer([1, 2, 3], timeout=30)
        base_ms = (time.perf_counter() - t0) * 1e3
        ctl.apply({"fault": "hotspot", "target": "fx-e0", "ms": 60})
        assert eng._model is not orig
        t0 = time.perf_counter()
        eng.infer([1, 2, 3], timeout=30)
        hot_ms = (time.perf_counter() - t0) * 1e3
        assert hot_ms > base_ms + 30, (base_ms, hot_ms)
        ctl.clear({"fault": "hotspot", "target": "fx-e0"})
        assert eng._model is orig               # restored, not wrapped

        ctl.apply({"fault": "wedge", "target": "fx-e0"})
        fut = eng.submit([4, 5])
        time.sleep(0.3)
        assert not fut.done()                   # wedged, worker alive
        assert eng.running
        ctl.clear({"fault": "wedge", "target": "fx-e0"})
        assert fut.result(timeout=30)[0, 0] == 4.0
        assert eng._model is orig
    ctl.stop()


def test_overlapping_wraps_clear_independently():
    """Two faults stacked on one engine: each clear unlinks ITS
    wrapper (in any order), and the original model is always restored
    at the end — overlapping schedule entries can't strand a
    wrapper."""
    eng = _stub_engine("ovl-e0")
    orig = eng._model
    ctl = ChaosController(schedule=None, seed=1)
    ctl.register_engine(eng)
    try:
        ctl.apply({"fault": "hotspot", "target": "ovl-e0", "ms": 5})
        ctl.apply({"fault": "wedge", "target": "ovl-e0"})
        # clear the INNER fault first: the outer wedge must relink
        # past the hotspot wrapper straight to the original
        ctl.clear({"fault": "hotspot", "target": "ovl-e0"})
        assert eng._model is not orig           # wedge still on
        assert eng._model.fn is orig            # relinked past hotspot
        ctl.clear({"fault": "wedge", "target": "ovl-e0"})
        assert eng._model is orig
        # and the other order, torn down by clear_all
        ctl.apply({"fault": "hotspot", "target": "ovl-e0", "ms": 5})
        ctl.apply({"fault": "wedge", "target": "ovl-e0"})
        ctl.clear({"fault": "wedge", "target": "ovl-e0"})
        assert eng._model.delay_s == 0.005      # hotspot back on top
        ctl.clear_all()
        assert eng._model is orig
    finally:
        ctl.stop()


def test_frame_fault_clear_is_identity_checked():
    """A superseded frame fault's scheduled clear must not cancel the
    newer fault's hook (last-writer-wins install, owner-only
    clear)."""
    class FakeWire:
        chaos_rx = None

    eng = _stub_engine("fh-e0")
    eng._wire = FakeWire()
    ctl = ChaosController(schedule=None, seed=1)
    ctl.register_engine(eng)
    try:
        ctl.apply({"fault": "drop_frames", "target": "fh-e0", "p": 1.0})
        drop_hook = eng._wire.chaos_rx
        assert drop_hook is not None
        ctl.apply({"fault": "delay_frames", "target": "fh-e0", "ms": 1})
        delay_hook = eng._wire.chaos_rx
        assert delay_hook is not drop_hook
        # the expired DROP fault's clear: delay hook must survive
        ctl.clear({"fault": "drop_frames", "target": "fh-e0"})
        assert eng._wire.chaos_rx is delay_hook
        ctl.clear({"fault": "delay_frames", "target": "fh-e0"})
        assert eng._wire.chaos_rx is None
    finally:
        eng._wire = None
        ctl.stop()


def test_kill_engine_fault_and_events():
    eng = _stub_engine("fx-kill")
    tap = _Tap()
    events.add_tap(tap)
    ctl = ChaosController(schedule=None, seed=1)
    ctl.register_engine(eng)
    try:
        eng.start()
        ctl.apply({"fault": "kill_engine", "target": "fx-kill"})
        _wait(lambda: not eng.running, what="engine death")
        faults = [e for e in tap.recs if e["event"] == "chaos_fault"]
        assert faults and faults[-1]["fault"] == "kill_engine"
    finally:
        events.remove_tap(tap)
        ctl.stop()
        try:
            eng.stop(drain=False)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# disabled path: CHAOS=0 patches nothing (the mxsan pattern)
# ---------------------------------------------------------------------------

def test_chaos_disabled_patches_nothing_and_is_free():
    """In THIS process (chaos off): no controller, engine start leaves
    the model identity untouched, and the gate costs nanoseconds."""
    from mxnet_tpu.serving import chaos

    assert not chaos_enabled()
    assert chaos.controller() is None
    assert chaos.register_engine(object()) is None
    model = StubModel()
    eng = ServingEngine(model, bucket_lens=(16,), max_rows=2,
                        engine_id="off-e0")
    with eng:
        assert eng._model is model      # nothing wrapped
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        chaos_enabled()
    per_us = (time.perf_counter() - t0) * 1e6 / n
    assert per_us < 50, f"disabled chaos gate costs {per_us:.2f} us"


def test_chaos_disabled_subprocess_no_families_no_threads():
    """Fresh process, CHAOS unset: no chaos thread, no
    mxnet_tpu_chaos_* family, wire listener hook unarmed."""
    code = """
import threading
import jax; jax.config.update("jax_platforms", "cpu")
from mxnet_tpu.serving import ServingEngine, chaos
from mxnet_tpu.telemetry.registry import REGISTRY
from mxnet_tpu import nd
import numpy as np

class M:
    def __call__(self, ids, tt, vl, seg, pos):
        return nd.array(ids.asnumpy().astype(np.float32)[..., None])

m = M()
eng = ServingEngine(m, bucket_lens=(16,), max_rows=2, engine_id="sub0")
with eng:
    srv = eng.expose()
    assert eng._model is m
    assert chaos.controller() is None
    if eng._wire is not None:
        assert eng._wire.chaos_rx is None
assert REGISTRY.get("mxnet_tpu_chaos_faults_total") is None
assert not [t for t in threading.enumerate()
            if t.name == "mxnet_tpu_chaos"]
print("DISABLED-OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_TPU_CHAOS", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                         env=env, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DISABLED-OK" in out.stdout


def test_chaos_env_registration_arms_controller():
    """CHAOS=1 in a fresh process: engine start registers with the
    process controller; an env schedule injects on its own."""
    code = """
import time
import jax; jax.config.update("jax_platforms", "cpu")
from mxnet_tpu.serving import ServingEngine, chaos
from mxnet_tpu import nd
import numpy as np

class M:
    def __call__(self, ids, tt, vl, seg, pos):
        return nd.array(ids.asnumpy().astype(np.float32)[..., None])

eng = ServingEngine(M(), bucket_lens=(16,), max_rows=2,
                    engine_id="armed0")
with eng:
    ctl = chaos.controller()
    assert ctl is not None
    assert "armed0" in ctl._engines
    # the fault counter ticks when the kill is ISSUED; the worker
    # thread winds down a moment later — wait for both, one deadline
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and (ctl._seq < 1 or eng.running):
        time.sleep(0.02)
    assert ctl._seq >= 1, "scheduled fault never injected"
    assert not eng.running          # kill_engine@0.1s did its job
print("ARMED-OK")
"""
    sched = json.dumps([{"at": 0.1, "fault": "kill_engine",
                         "target": "armed0"}])
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_CHAOS="1",
               MXNET_TPU_CHAOS_SEED="3", MXNET_TPU_CHAOS_SCHEDULE=sched)
    out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                         env=env, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ARMED-OK" in out.stdout


# ---------------------------------------------------------------------------
# THE drill: hot-spot shed + seat kill/replace + router kill/adopt
# ---------------------------------------------------------------------------

@pytest.fixture()
def chaos_drill_env(monkeypatch, tmp_path):
    """Drill-speed judging clocks + a clean incident slate."""
    from mxnet_tpu.telemetry import incidents, spans

    monkeypatch.setenv("MXNET_TPU_SLO_WINDOW_SCALE", "0.01")
    monkeypatch.setenv("MXNET_TPU_SLO_EVAL_S", "0.1")
    # margin matters: normal stub latency must stay WELL under the
    # objective even instrumented (mxsan) or on a host shared with
    # five other test workers — only the induced hot-spot (1500 ms,
    # below) may breach it, or fleet-wide burn alerts hold the incident
    # open (and the hot seat's weight down) past the drill's patience.
    # The drill's six closed-loop clients, both routers and all three
    # engines share ONE interpreter: a request takes ~70 ms at the
    # median on an idle host, and beside ten busy processes 100-150 ms
    # at the median, 250-290 at p90 and 650-860 at p99. The objective
    # is a p99 (a 6x burn is 6% of requests over it), so 100 ms and
    # then 250 ms still timed out re-converging under six xdist
    # workers; 1000 ms (objectives snap to histogram boundaries) is
    # over the loaded p99
    monkeypatch.setenv("MXNET_TPU_SLO_LATENCY_MS", "1000")
    monkeypatch.setenv("MXNET_TPU_CANARY_INTERVAL_S", "0.25")
    monkeypatch.setenv("MXNET_TPU_CANARY_TIMEOUT_S", "5")
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    saved = (spans.enabled(), spans.RECORDER.slow_ms)
    spans.configure(enabled=True, slow_ms=40.0)
    spans.reset()
    incidents.TRACKER.reset()
    yield
    spans.configure(enabled=saved[0], slow_ms=saved[1])
    spans.reset()
    incidents.TRACKER.reset()


def test_chaos_drill_end_to_end(chaos_drill_env):
    """The acceptance drill (stub-model tier-1 shape): under closed-loop
    load through two active/active routers —

    - an induced hot-spot sheds routing weight off the slow seat and
      its measured share moves;
    - a seat kill triggers an autoscaler replacement that admits
      traffic warm (manifest replay + TTFT probe);
    - a router kill hands the in-flight requests to the survivor;

    with re-convergence to SLO compliance, one correlated incident
    per fault, and ZERO lost requests."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from serve_loadgen import run_chaos_drill
    finally:
        sys.path.pop(0)

    def make_engine(engine_id):
        return ServingEngine(StubModel(), bucket_lens=(16,),
                             max_rows=2, engine_id=engine_id)

    report = run_chaos_drill(make_engine, n_engines=3, n_clients=6,
                             hot_ms=1500.0, phase_timeout_s=60.0,
                             vocab=60, min_len=4, max_len=12)
    assert report["lost"] == 0
    assert report["completed"] == report["attempts"] > 0
    ph = report["phases"]
    assert ph["hotspot"]["weight_min"] < 0.7
    assert ph["hotspot"]["hot_share"] < 0.5 * ph["hotspot"]["fair_share"]
    assert ph["seat_kill"]["manifest_shapes"] >= 1
    assert ph["seat_kill"]["ttft_ms"] is not None
    assert ph["router_kill"]["adopted"] >= 1
    assert len(report["incidents"]) >= 3
    # one incident per fault: each phase attributed distinct ids
    per_phase = [ph[k]["incident"] for k in
                 ("hotspot", "seat_kill", "router_kill")]
    flat = [i for ids in per_phase for i in ids]
    assert len(flat) == len(set(flat))
    # re-converged: short-window burns back under the SRE page factor
    # ("met" judges the whole budget window, which CONTAINS the
    # induced faults by design — not the convergence signal)
    for name, row in report["slo"].items():
        b = row.get("burn_5m")
        assert b is None or b < 14.4, (name, row)
