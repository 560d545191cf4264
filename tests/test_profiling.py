"""Continuous profiling + resource & cost accounting (ISSUE 8).

Covers the always-on sampling profiler (mxnet_tpu/telemetry/profiling)
under a LIVE loaded serving engine, the per-bucket cost ledger's
exactness contract (sum of per-request amortized device time == batch
forward wall), the /profile and /costs scrape surface, resource
gauges/watermarks, flight-bundle profile.txt, the disabled-path
(MXNET_TPU_PROF=0) microbench guard and the loadgen cost cross-check.
Marker-clean tier-1.
"""
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu.serving import ServingEngine, ServingRouter
from mxnet_tpu.serving.metrics import CostLedger, merge_cost_buckets
from mxnet_tpu.telemetry import profiling, resources
from mxnet_tpu.telemetry.profiling import ContinuousProfiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


class StubModel:
    """Contract-shaped model; the sleep keeps the worker thread inside
    a NAMED frame long enough for the sampler to catch it."""

    def __init__(self, delay=0.0):
        self.delay = delay

    def __call__(self, ids, token_types, valid_length, segment_ids,
                 positions):
        if self.delay:
            time.sleep(self.delay)
        return nd.array(ids.asnumpy().astype(np.float32)[..., None])


# ---------------------------------------------------------------------------
# profiler unit: folded stacks, thread attribution, bounds
# ---------------------------------------------------------------------------

def test_profiler_folds_stacks_with_thread_attribution():
    prof = ContinuousProfiler(hz=250)
    stop = threading.Event()

    def _spin_hot_loop():
        while not stop.is_set():
            sum(i * i for i in range(500))

    t = threading.Thread(target=_spin_hot_loop, name="prof_test_spinner",
                         daemon=True)
    prof.start()
    t.start()
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            txt = prof.collapsed_text()
            if "prof_test_spinner" in txt and "_spin_hot_loop" in txt:
                break
            time.sleep(0.05)
    finally:
        stop.set()
        t.join(timeout=5)
        prof.stop()
    txt = prof.collapsed_text()
    # collapsed format: thread;root;...;leaf count — thread name is the
    # first segment, the hot function appears in its stack
    lines = [l for l in txt.splitlines()
             if l.startswith("prof_test_spinner;")]
    assert lines, txt
    assert any("_spin_hot_loop" in l for l in lines), lines
    head, _, count = lines[0].rpartition(" ")
    assert int(count) >= 1
    # self-time attribution sees the same frames
    snap = prof.snapshot()
    assert snap["samples"] > 0 and snap["top_self"]


def test_profiler_stack_table_is_bounded():
    prof = ContinuousProfiler(hz=100, max_stacks=2)
    with prof._lock:
        prof._counts[("a", ("x (f.py)",))] = 1
        prof._counts[("b", ("y (f.py)",))] = 1
    # the sampler excludes its own thread, so park a real one in a
    # distinctly-named frame for it to find
    stop = threading.Event()

    def _parked_sleeper():
        stop.wait(10.0)

    t = threading.Thread(target=_parked_sleeper, name="prof_test_parked",
                         daemon=True)
    t.start()
    try:
        time.sleep(0.05)
        # its distinct stack must fold into the overflow bucket, not
        # grow the table past its bound (plus the overflow keys)
        prof._sample_once()
    finally:
        stop.set()
        t.join(timeout=5)
    with prof._lock:
        keys = list(prof._counts)
    real = [k for k in keys if k[1] != ("(stack-table-full)",)]
    assert len(real) == 2, keys
    assert any(k[1] == ("(stack-table-full)",) for k in keys), keys


# ---------------------------------------------------------------------------
# resources: /proc gauges, device zeros, watermarks
# ---------------------------------------------------------------------------

def test_resources_sample_and_watermarks():
    snap = resources.sample()
    assert snap["rss_bytes"] > 0
    assert snap["open_fds"] > 0
    assert snap["threads"] >= 1
    # CPU backend: device stats may be zero, but never negative/None
    assert snap["device_bytes_in_use"] >= 0
    assert snap["live_buffer_bytes"] >= 0
    marks = resources.watermarks()
    assert marks["rss_peak_bytes"] >= snap["rss_bytes"] > 0
    compact = resources.compact()
    assert compact["rss_mb"] > 0 and compact["rss_peak_mb"] > 0


# ---------------------------------------------------------------------------
# the live-engine acceptance: /profile names the worker, /costs
# reconciles, amortized sums == batch forward
# ---------------------------------------------------------------------------

def test_live_engine_profile_and_costs_acceptance():
    profiling.PROFILER.configure(hz=200)
    eng = ServingEngine(StubModel(delay=0.004), bucket_lens=(16, 64),
                        max_rows=4, engine_id="prof-e0")
    futs = []
    with eng:
        srv = eng.expose()
        eng.warmup()
        rs = np.random.RandomState(3)
        deadline = time.monotonic() + 20.0
        worker_seen = False
        while time.monotonic() < deadline:
            batch = [eng.submit(rs.randint(1, 50, rs.randint(3, 40))
                                .tolist()) for _ in range(6)]
            for f in batch:
                f.result(timeout=30)
            futs.extend(batch)
            txt = _get(srv.url("/profile"))
            if any(l.startswith("mxnet_tpu_serving;")
                   for l in txt.splitlines()):
                worker_seen = True
                break
        # /profile is collapsed-stack text naming the serving worker
        # thread under load
        assert worker_seen, _get(srv.url("/profile"))
        profj = json.loads(_get(srv.url("/profile?format=json&top=5")))
        assert profj["running"] and profj["samples"] > 0
        assert profj["top_self"] and len(profj["top_self"]) <= 5

        # /costs: per-bucket ledger reconciles with what the clients saw
        costs = json.loads(_get(srv.url("/costs")))
    assert costs["engine_id"] == "prof-e0"
    totals = costs["totals"]
    bills = [f.cost for f in futs]
    assert all(b is not None for b in bills)
    assert totals["requests"] == len(futs)
    assert totals["valid_tokens"] == sum(b["tokens"] for b in bills)
    # the exactness contract: amortized per-request device time sums
    # back to the batch forward wall (ledger request_s) within 5%
    client_s = sum(b["device_s"] for b in bills)
    assert abs(client_s - totals["request_s"]) \
        <= 0.05 * totals["request_s"], (client_s, totals)
    # warmup compiles were accounted as compile/warmup, never device
    assert totals["compile_s"] > 0
    per_bucket = costs["buckets"]
    assert set(per_bucket) <= {"16", "64"}
    for row in per_bucket.values():
        if row["requests"]:
            assert row["device_ms_per_request"] > 0
            assert row["device_s_per_1k_tokens"] > 0


def test_cost_ledger_unit_and_merge():
    led = CostLedger("unit-e")
    led.observe_batch(64, 0.5, requests=2, valid_tokens=100,
                      compiled=False)
    led.observe_batch(64, 1.5, requests=1, valid_tokens=50, compiled=True)
    led.observe_warmup(256, 2.0, compiled=True)
    led.observe_warmup(256, 0.1, compiled=False)
    tbl = led.table()
    assert tbl["64"]["device_s"] == 0.5
    assert tbl["64"]["compile_s"] == 1.5
    assert tbl["64"]["request_s"] == 2.0          # both carried requests
    assert tbl["64"]["requests"] == 3
    assert tbl["256"]["compile_s"] == 2.0
    assert tbl["256"]["warmup_s"] == 0.1
    assert tbl["256"]["requests"] == 0
    tot = led.totals()
    assert tot["requests"] == 3 and tot["valid_tokens"] == 150
    assert tot["device_ms_per_request"] == pytest.approx(2000.0 / 3,
                                                         rel=1e-3)
    merged = merge_cost_buckets([tbl["64"], tbl["256"]])
    assert merged["compile_s"] == 3.5 and merged["batches"] == 4


# ---------------------------------------------------------------------------
# router: fleet /costs merge + cost bill propagation
# ---------------------------------------------------------------------------

def test_router_fleet_costs_and_bill_propagation(monkeypatch):
    # canary pinned off: its probes bill real device time into the
    # ledger, and this golden pins EXACT fleet request counts (the
    # canary-inclusive books are covered by the loadgen-exclusion
    # test in test_blackbox.py)
    monkeypatch.setenv("MXNET_TPU_CANARY", "0")
    engines = [ServingEngine(StubModel(), bucket_lens=(32,), max_rows=2,
                             engine_id=f"cost-e{i}") for i in range(2)]
    for e in engines:
        e.start()
        e.warmup()
    router = ServingRouter(engines=engines).start()
    try:
        futs = [router.submit(list(range(1, 6))) for _ in range(8)]
        for f in futs:
            f.result(timeout=30)
        # the engine's amortized bill rode through the router
        assert all(f.cost is not None for f in futs)
        assert {f.cost["engine_id"] for f in futs} \
            <= {"cost-e0", "cost-e1"}
        srv = router.expose()
        fleet = json.loads(_get(srv.url("/costs")))
        assert set(fleet["engines"]) == {"cost-e0", "cost-e1"}
        assert fleet["totals"]["requests"] == 8
        assert fleet["fleet"]["32"]["requests"] == 8
        client_s = sum(f.cost["device_s"] for f in futs)
        assert abs(client_s - fleet["totals"]["request_s"]) \
            <= 0.05 * max(fleet["totals"]["request_s"], 1e-9)
    finally:
        router.stop()
        for e in engines:
            e.stop()


def test_loadgen_cost_cross_check():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from serve_loadgen import run_load

    eng = ServingEngine(StubModel(), bucket_lens=(64,), max_rows=4,
                        engine_id="lg-cost")
    with eng:
        srv = eng.expose()
        eng.warmup()
        report = run_load(eng, n_clients=4, requests_per_client=6,
                          min_len=4, max_len=32, vocab=60,
                          metrics_url=srv.url("/metrics"))
    cost = report["cost"]
    assert cost["reconciled"] is True, cost["mismatches"]
    assert cost["client_requests"] == 24 and cost["missing_bills"] == 0
    assert cost["ledger_delta"]["requests"] == 24
    assert cost["device_s_per_1k_tokens"] > 0


# ---------------------------------------------------------------------------
# flight bundle carries profile.txt
# ---------------------------------------------------------------------------

def test_flight_bundle_contains_profile_txt(tmp_path, monkeypatch):
    from mxnet_tpu.telemetry import recorder

    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    profiling.ensure_started()
    time.sleep(0.15)                  # let the sampler take a wakeup
    path = recorder.dump("prof_test", min_interval_s=0.0)
    assert path is not None
    names = os.listdir(path)
    assert "profile.txt" in names, names
    with open(os.path.join(path, "profile.txt")) as f:
        head = f.readline()
    assert head.startswith("# mxnet_tpu continuous profile")


# ---------------------------------------------------------------------------
# disabled path: MXNET_TPU_PROF=0 costs ~nothing
# ---------------------------------------------------------------------------

def test_disabled_prof_and_ledger_paths_stay_cheap(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PROF", "0")
    assert profiling.ensure_started() is None
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        profiling.ensure_started()
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 50e-6, f"ensure_started {per_call * 1e6:.1f}us"
    # the ledger's hot path (one observe per dispatched BATCH) stays
    # micro-cheap too — budget ~50x observed, catches regressions
    led = CostLedger("bench-led")
    t0 = time.perf_counter()
    for _ in range(n):
        led.observe_batch(64, 0.001, requests=4, valid_tokens=100,
                          compiled=False)
    per_obs = (time.perf_counter() - t0) / n
    assert per_obs < 200e-6, f"ledger observe {per_obs * 1e6:.1f}us"


# ---------------------------------------------------------------------------
# telemetry_dump --profile / --costs
# ---------------------------------------------------------------------------

def test_telemetry_dump_profile_and_costs(capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_dump

    profiling.PROFILER.configure(hz=200)
    eng = ServingEngine(StubModel(), bucket_lens=(16,), max_rows=2,
                        engine_id="dump-cost")
    with eng:
        srv = eng.expose()
        eng.warmup()
        eng.infer([1, 2, 3], timeout=30)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if profiling.PROFILER.snapshot()["samples"]:
                break
            time.sleep(0.05)
        rc = telemetry_dump.main(["--profile", "--costs",
                                  srv.url("/metrics")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "continuous profile" in out
    assert "self%" in out
    assert "costs, engine dump-cost" in out
    assert "bucket" in out and "device s" in out
