"""Retrospective observability tests (ISSUE 18): the bounded on-disk
history store (tier downsampling, segment rotation, crash reload with
a torn final line), the scrape→store→query golden path (rate and
quantile-over-time, straight numbers), the exposition endpoints
(``/query_range`` + ``/series``), incident forensics (an opened
incident freezes the PRECEDING window into the flight bundle), retro
SLO replay (the live firing decision reproduces from the persisted
evidence — and fails to reproduce at a healthy instant, proving the
audit has teeth), the exemplar-bearing tenant merge round-trip, and the
torn-tail ``read_events`` hardening.

CPU-only, thread-light: the store and scraper are driven manually
with explicit timestamps wherever determinism matters.
"""
import glob
import io
import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu.serving import ServingEngine
from mxnet_tpu.telemetry import alerts as alerts_mod
from mxnet_tpu.telemetry import events as events_mod
from mxnet_tpu.telemetry import history as hist_mod
from mxnet_tpu.telemetry import incidents as incidents_mod
from mxnet_tpu.telemetry import recorder as flight
from mxnet_tpu.telemetry import slo as slo_mod
from mxnet_tpu.telemetry.expo import (merge_prometheus_texts,
                                      parse_prometheus_text)
from mxnet_tpu.telemetry.registry import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

T0 = 1_700_000_000.0        # 10s/60s-aligned synthetic wall epoch


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _get_json(url, timeout=10):
    return json.loads(_get(url, timeout)[1])


class StubModel:
    def __init__(self, delay=0.0):
        self.delay = delay

    def __call__(self, ids, token_types, valid_length, segment_ids,
                 positions):
        if self.delay:
            time.sleep(self.delay)
        return nd.array(ids.asnumpy().astype(np.float32)[..., None])


@pytest.fixture(autouse=True)
def _no_history_env(monkeypatch):
    """Stores built here are memory-only unless a test passes a dir."""
    monkeypatch.delenv("MXNET_TPU_HISTORY_DIR", raising=False)
    monkeypatch.delenv("MXNET_TPU_HISTORY", raising=False)


def _key(family, **labels):
    inner = ",".join(f'{k}="{v}"' for k, v in labels.items())
    return f"{family}{{{inner}}}"


# ---------------------------------------------------------------------------
# store: tiers, retention, range evaluation goldens
# ---------------------------------------------------------------------------

def test_family_of_strips_suffixes_and_labels():
    assert hist_mod.family_of(
        'mxnet_tpu_serving_latency_ms_bucket{le="10"}') \
        == "mxnet_tpu_serving_latency_ms"
    assert hist_mod.family_of("mxnet_tpu_serving_latency_ms_count") \
        == "mxnet_tpu_serving_latency_ms"
    assert hist_mod.family_of(
        'mxnet_tpu_serving_requests_total{event="completed"}') \
        == "mxnet_tpu_serving_requests_total"


def test_tier_downsampling_keeps_last_sample_per_bucket():
    store = hist_mod.HistoryStore(dirpath="", retain_s=7200)
    key = _key("mxnet_tpu_serving_queue_depth", engine_id="tier0")
    for i in range(26):
        store.append(T0 + i, {key: float(i)})
    raw = store.tiers[0].series[key]
    assert len(raw) == 26
    # 10s tier: two CLOSED buckets, each flushed at its END edge with
    # the bucket's LAST sample (cumulative counters diff exactly
    # across edges); the third bucket is still pending
    t10 = store.tiers[1].series[key]
    assert t10 == [(T0 + 10.0, 9.0), (T0 + 20.0, 19.0)]
    assert store.tiers[2].series.get(key) is None   # 60s: still open
    # stitched view prefers the finest tier wherever raw covers
    pts = store.points(key)
    assert pts == raw


def test_store_rate_increase_and_counter_reset_golden():
    store = hist_mod.HistoryStore(dirpath="", retain_s=7200)
    key = _key("mxnet_tpu_serving_requests_total",
               engine_id="g0", event="completed")
    for i in range(61):
        store.append(T0 + i, {key: 2.0 * i})
    out = store.query_range("mxnet_tpu_serving_requests_total",
                            start=T0 + 30, end=T0 + 60, step=5,
                            window=10, fn="rate", now=T0 + 60)
    [row] = out["series"]
    assert row["labels"] == {"engine_id": "g0", "event": "completed"}
    for _, v in row["points"]:
        assert v == pytest.approx(2.0)
    inc = store.query_range("mxnet_tpu_serving_requests_total",
                            start=T0 + 60, end=T0 + 60, step=1,
                            window=10, fn="increase", now=T0 + 60)
    assert inc["series"][0]["points"][-1][1] == pytest.approx(20.0)

    # counter reset: climb to 50, restart at 0, climb to 27 — the
    # increase over the whole window re-anchors at the reset value
    rkey = _key("mxnet_tpu_serving_requests_total",
                engine_id="reset0", event="completed")
    for i in range(11):
        store.append(T0 + i, {rkey: 5.0 * i})
    for i in range(11, 21):
        store.append(T0 + i, {rkey: 3.0 * (i - 11)})
    out = store.query_range("mxnet_tpu_serving_requests_total",
                            start=T0 + 20, end=T0 + 20, step=1,
                            window=20, fn="increase", now=T0 + 20,
                            match={"engine_id": "reset0"})
    [row] = out["series"]
    assert row["points"][-1][1] == pytest.approx(50.0 + 27.0)


def test_query_range_quantile_over_time_golden():
    store = hist_mod.HistoryStore(dirpath="", retain_s=7200)
    fam = "mxnet_tpu_serving_latency_ms"
    for i in range(31):
        store.append(T0 + i, {
            _key(fam + "_bucket", engine_id="q0", le="10"): float(i),
            _key(fam + "_bucket", engine_id="q0", le="100"): 2.0 * i,
            _key(fam + "_bucket", engine_id="q0", le="+Inf"): 2.0 * i,
        })
    # window of 10 scrapes: 10 obs <=10ms, 10 more <=100ms. PromQL
    # interpolation: q50 rank sits exactly at the first bucket's
    # upper bound; q75 interpolates half-way into (10, 100]
    out = store.query_range(fam, start=T0 + 30, end=T0 + 30, step=1,
                            window=10, fn="quantile", q=50, now=T0 + 30)
    [row] = out["series"]
    assert row["labels"] == {"engine_id": "q0"}
    assert row["points"][-1][1] == pytest.approx(10.0)
    out = store.query_range(fam, start=T0 + 30, end=T0 + 30, step=1,
                            window=10, fn="quantile", q=75, now=T0 + 30)
    assert out["series"][0]["points"][-1][1] == pytest.approx(55.0)


def test_value_staleness_marks_gaps_null():
    store = hist_mod.HistoryStore(dirpath="", retain_s=7200)
    key = _key("mxnet_tpu_serving_queue_depth", engine_id="stale0")
    store.append(T0, {key: 3.0})
    out = store.query_range("mxnet_tpu_serving_queue_depth",
                            start=T0, end=T0 + 600, step=60,
                            fn="value", now=T0 + 600)
    pts = out["series"][0]["points"]
    assert pts[0][1] == 3.0
    assert pts[-1][1] is None     # 600s past the last sample: stale


# ---------------------------------------------------------------------------
# store: disk persistence, rotation, crash reload
# ---------------------------------------------------------------------------

def test_disk_segments_rotate_reload_and_skip_torn_line(tmp_path):
    d = str(tmp_path / "hist")
    store = hist_mod.HistoryStore(dirpath=d, retain_s=7200,
                                  max_mb=64, segment_mb=0.000001)
    key = _key("mxnet_tpu_serving_requests_total",
               engine_id="disk0", event="completed")
    gkey = _key("mxnet_tpu_serving_queue_depth", engine_id="disk0")
    n = 400
    for i in range(n):
        store.append(T0 + i, {key: 2.0 * i, gkey: float(i % 7)})
    store.close()
    fam_dir = os.path.join(d, "mxnet_tpu_serving_requests_total")
    segs = [f for f in os.listdir(fam_dir) if f.startswith("raw-")]
    assert len(segs) >= 2, "tiny segment_mb must have rotated"

    # hard-kill simulation: tear the newest raw segment mid multi-byte
    # UTF-8 sequence, plus a corrupt-JSON line
    newest = os.path.join(fam_dir, sorted(segs)[-1])
    with open(newest, "ab") as fh:
        fh.write(b'{"t": 17, "s": {"x\xe2\x82')
    reloaded = hist_mod.HistoryStore(dirpath=d, retain_s=7200,
                                     max_mb=64, now=T0 + n)
    assert reloaded.load_skipped >= 1
    pts = reloaded.points(key)
    assert pts and pts[-1] == (T0 + n - 1, 2.0 * (n - 1))
    # reloaded store answers range queries identically to the live one
    out = reloaded.query_range("mxnet_tpu_serving_requests_total",
                               start=T0 + n - 1, end=T0 + n - 1,
                               step=1, window=10, fn="rate",
                               now=T0 + n - 1)
    assert out["series"][0]["points"][-1][1] == pytest.approx(2.0)
    reloaded.close()


def test_disk_budget_drops_oldest_sealed_segments(tmp_path):
    d = str(tmp_path / "hist")
    store = hist_mod.HistoryStore(dirpath=d, retain_s=7200,
                                  max_mb=0.008, segment_mb=0.000001)
    key = _key("mxnet_tpu_serving_requests_total",
               engine_id="budget0", event="completed")
    for i in range(1200):
        store.append(T0 + i, {key: float(i)})
    fam_dir = os.path.join(d, "mxnet_tpu_serving_requests_total")
    assert not os.path.exists(os.path.join(fam_dir, "raw-00000001.seg"))
    sealed = sum(os.path.getsize(os.path.join(fam_dir, f))
                 for f in os.listdir(fam_dir))
    # bounded: budget plus at most the open segments' slack
    assert sealed <= 0.008 * 1024 * 1024 + 3 * store.segment_bytes
    store.close()


# ---------------------------------------------------------------------------
# scrape -> store -> query golden (the acceptance cycle)
# ---------------------------------------------------------------------------

def test_scrape_store_query_golden_rate_and_quantile():
    reg = MetricsRegistry()
    req = reg.counter("mxnet_tpu_serving_requests_total",
                      "doc", ("engine_id", "event"))
    lat = reg.histogram("mxnet_tpu_serving_latency_ms", "doc",
                        ("engine_id", "stage"), buckets=(10.0, 100.0))
    # a family NO recording rule names must not be stored
    other = reg.counter("mxnet_tpu_serving_batches_total", "doc",
                        ("engine_id",))
    scraper = hist_mod.HistoryScraper("golden0", registry=reg,
                                      interval_s=999)
    c = req.labels(engine_id="g0", event="completed")
    h = lat.labels(engine_id="g0", stage="total")
    o = other.labels(engine_id="g0")
    for i in range(31):
        c.inc(2)
        h.observe(5.0)
        h.observe(50.0)
        o.inc()
        kept = scraper.scrape_once(now=T0 + i)
        assert kept > 0
    assert scraper.scrapes == 31
    store = scraper.store
    assert not any("batches" in k for k in store.keys())

    out = store.query_range("mxnet_tpu_serving_requests_total",
                            start=T0 + 10, end=T0 + 30, step=5,
                            window=10, fn="rate", now=T0 + 30,
                            match={"engine_id": "g0"})
    [row] = out["series"]
    for _, v in row["points"]:
        assert v == pytest.approx(2.0)      # +2 per 1s scrape

    # per scrape: one obs in (0,10], one in (10,100] — the windowed
    # histogram is the quantile golden from the pure-store test
    out = store.query_range("mxnet_tpu_serving_latency_ms",
                            start=T0 + 30, end=T0 + 30, step=1,
                            window=10, fn="quantile", q=75,
                            now=T0 + 30)
    [row] = out["series"]
    assert row["labels"]["engine_id"] == "g0"
    assert row["points"][-1][1] == pytest.approx(55.0)

    body = store.series()
    assert body["count"] == len(store.keys())
    names = {r["family"] for r in body["series"]}
    assert names == {"mxnet_tpu_serving_requests_total",
                     "mxnet_tpu_serving_latency_ms"}


def test_merged_tenant_exemplars_survive_into_history(monkeypatch):
    """Satellite: two engines' exemplar-bearing tenant-labeled
    histograms merge (worst trace per series survives), and the
    merged text feeds a history scrape-store-query cycle."""
    regs = [MetricsRegistry(), MetricsRegistry()]
    children = []
    for i, reg in enumerate(regs):
        fam = reg.histogram(
            "mxnet_tpu_serving_tenant_latency_ms", "doc",
            ("engine_id", "tenant", "tenant_class", "model"),
            buckets=(10.0, 100.0))
        children.append(fam.labels(engine_id=f"mx{i}", tenant="acme",
                                   tenant_class="std", model="m1"))

    def merged():
        return merge_prometheus_texts(
            [r.render_prometheus() for r in regs])

    scraper = hist_mod.HistoryScraper("merge0", text_fn=merged,
                                      interval_s=999)
    for i in range(21):
        children[0].observe(5.0, exemplar=f"tr-fast-{i}")
        children[1].observe(80.0, exemplar="tr-slow")
        scraper.scrape_once(now=T0 + i)

    ex = {}
    parsed = parse_prometheus_text(merged(), exemplars=ex)
    traces = {e["trace_id"] for e in ex.values()}
    assert "tr-slow" in traces          # the merge kept the worst trace
    inf_keys = [k for k in parsed
                if k.startswith("mxnet_tpu_serving_tenant_latency_ms_"
                                "bucket") and 'le="+Inf"' in k]
    assert len(inf_keys) == 2           # engine-labeled: disjoint series

    # the tenant axis queries straight out of history: one row per
    # engine, both entirely under the 100ms bucket
    out = scraper.store.query_range(
        "mxnet_tpu_serving_tenant_latency_ms",
        start=T0 + 20, end=T0 + 20, step=1, window=10,
        fn="quantile", q=99, now=T0 + 20, match={"tenant": "acme"})
    rows = {r["labels"]["engine_id"]: r["points"][-1][1]
            for r in out["series"]}
    assert set(rows) == {"mx0", "mx1"}
    assert 0.0 < rows["mx0"] <= 10.0             # all obs in (0,10]
    assert 10.0 < rows["mx1"] <= 100.0


# ---------------------------------------------------------------------------
# exposition endpoints + the mxtop consumer
# ---------------------------------------------------------------------------

def test_engine_history_endpoints_and_mxtop(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    eng = ServingEngine(StubModel(), bucket_lens=(16,), max_rows=2,
                        engine_id="hist-ep0")
    with eng:
        srv = eng.expose()
        eng.warmup()
        assert eng._history is not None, \
            "MXNET_TPU_HISTORY defaults on: engine start runs a scraper"
        for _ in range(4):
            eng.infer([1, 2, 3], timeout=30)
        eng._history.scrape_once()
        for _ in range(4):
            eng.infer([1, 2, 3], timeout=30)
        time.sleep(0.02)
        eng._history.scrape_once()

        series = _get_json(srv.url("/series"))
        assert series["count"] > 0
        fams = {r["family"] for r in series["series"]}
        assert "mxnet_tpu_serving_requests_total" in fams

        out = _get_json(srv.url(
            "/query_range?family=mxnet_tpu_serving_requests_total"
            "&fn=increase&window=3600&engine_id=hist-ep0"))
        assert out["fn"] == "increase"
        # increase anchors at the FIRST stored sample (4 completed at
        # scrape one, 8 at scrape two): the window saw +4
        last = {r["labels"].get("event"): r["points"][-1][1]
                for r in out["series"]}
        assert last.get("completed", 0) >= 4

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url("/query_range?fn=rate"))    # no family
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url("/query_range?family=x&fn=bogus"))
        assert ei.value.code == 400

        # the terminal console renders one frame off the same store
        import mxtop
        buf = io.StringIO()
        firing = mxtop.render(srv.url("").rstrip("/"), 300.0, out=buf)
        frame = buf.getvalue()
        assert "mxtop" in frame and "alerts" in frame
        assert isinstance(firing, int)
    assert eng._history._thread is None      # stop() joined the scraper


def test_mxtop_sparkline_and_format():
    import mxtop
    assert mxtop.sparkline([]) == "····"
    line = mxtop.sparkline([(0, 0.0), (1, None), (2, 1.0), (3, 2.0)])
    assert len(line) == 3
    assert line[0] == mxtop.SPARK[0] and line[-1] == mxtop.SPARK[-1]
    assert mxtop._fmt(None) == "  -"
    assert mxtop._fmt(2_500_000).strip().startswith("2.5M")


# ---------------------------------------------------------------------------
# incident forensics + retro replay (the acceptance drill)
# ---------------------------------------------------------------------------

def _synthetic_burn_drill(owner, on_page=None, register=False):
    """Drive an availability SLO + fast-burn rule + history scraper
    over a synthetic wall timeline ending NOW: 20s of good traffic,
    then everything fails — the page fires mid-timeline. With
    ``register`` the scraper is started first (registered with the
    incident hook and the flight recorder; its thread idles at the
    999s interval). Returns (daemon, scraper, evaluator,
    timestamps)."""
    reg = MetricsRegistry()
    req = reg.counter("mxnet_tpu_serving_requests_total", "doc",
                      ("engine_id", "event"))
    evaluator = slo_mod.SloEvaluator(owner, registry=reg, scale=0.01)
    evaluator.add(slo_mod.AvailabilitySLO(
        "hist_avail", target=0.99, match={"engine_id": owner},
        registry=reg))
    daemon = alerts_mod.AlertDaemon(evaluator, eval_s=999,
                                    registry=reg, on_page=on_page)
    daemon.add_rule(alerts_mod.BurnRateRule(
        "hist_avail_fast_burn", "hist_avail", long_window="1h",
        short_window="5m", factor=14.4,
        severity=alerts_mod.PAGE, for_s=60.0))
    clock = {"t": 0.0}
    scraper = hist_mod.HistoryScraper(
        owner, registry=reg, interval_s=999,
        slo_fn=lambda: evaluator.snapshot(now=clock["t"], tick=False),
        alerts_fn=daemon.snapshot)
    if register:
        scraper.start()
    end = time.time()
    ts = [end - 60.0 + i for i in range(61)]
    good = req.labels(engine_id=owner, event="completed")
    bad = req.labels(engine_id=owner, event="failed")
    for i, t in enumerate(ts):
        (good if i < 20 else bad).inc(5)
        clock["t"] = t
        daemon.evaluate_once(now=t)
        scraper.scrape_once(now=t)
    return daemon, scraper, evaluator, ts


def test_replay_history_reproduces_the_firing_decision():
    daemon, scraper, _, ts = _synthetic_burn_drill("replay0")
    assert daemon.state("hist_avail_fast_burn") == "firing"

    freeze = scraper.freeze("inc-replay-test")
    assert freeze["series"], "freeze must carry the series window"
    assert freeze["alerts"]["rules"][0]["state"] == "firing"

    rep = slo_mod.replay_history(freeze)
    assert rep["reproduces"] is True
    [rule] = rep["rules"]
    assert rule["alert"] == "hist_avail_fast_burn"
    assert rule["active"] is True and rule["live_state"] == "firing"
    assert rule["detail"]["burn_short"] > 14.4
    obj = rep["objectives"]["hist_avail"]
    assert obj["sli"] is not None and obj["sli"] < 0.99
    assert rep["ticks"] > 0 and rep["scale"] == pytest.approx(0.01)

    # the audit has teeth: judged at a HEALTHY instant the replay
    # must NOT reproduce a firing decision
    rep2 = slo_mod.replay_history(freeze, at=ts[15])
    assert rep2["reproduces"] is False
    assert rep2["rules"][0]["active"] in (False, None)


def test_incident_open_freezes_preceding_window_into_bundle(
        monkeypatch, tmp_path):
    """The chaos-drill acceptance path, synthetically induced: the
    fast-burn page opens an incident, the incident freezes every live
    scraper's PRECEDING window, and the page's flight bundle carries
    ``history_<owner>.json`` — from which replay reproduces the
    decision."""
    flight_dir = str(tmp_path / "flight")
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", flight_dir)
    rec = flight.RECORDER
    rec._last_bundle = None
    rec._last_dump.clear()
    incidents_mod.TRACKER.reset()
    incidents_mod.install()
    scraper = None
    try:
        daemon, scraper, _, ts = _synthetic_burn_drill(
            "pagehist0", register=True)
        assert scraper in hist_mod.scrapers()
        assert hist_mod.default_store() is scraper.store
        assert daemon.state("hist_avail_fast_burn") == "firing"
        open_inc = incidents_mod.TRACKER.open_incidents()
        assert len(open_inc) == 1
        inc_id = open_inc[0]["id"]

        with scraper._lock:
            freezes = list(scraper._freezes)
        assert freezes and freezes[-1]["incident_id"] == inc_id
        # the window precedes the incident: coverage starts back in
        # the healthy phase, not at the moment the page fired
        first_t = min(p[0] for pts in freezes[-1]["series"].values()
                      for p in pts)
        assert first_t <= ts[5]

        bundles = [p for p in glob.glob(os.path.join(flight_dir, "*"))
                   if "alert_hist_avail_fast_burn" in p]
        assert len(bundles) == 1
        section_path = os.path.join(bundles[0],
                                    "history_pagehist0.json")
        assert os.path.exists(section_path)
        with open(section_path, encoding="utf-8") as fh:
            section = json.load(fh)
        assert section["owner"] == "pagehist0"

        # replay straight off the BUNDLE section, exactly as a
        # postmortem would (a bundle section replays its newest
        # freeze), judged at the newest stored sample — the synthetic
        # timeline lags the wall clock the freeze is stamped with
        frozen = section["freezes"][-1]
        last_t = max(p[0] for pts in frozen["series"].values()
                     for p in pts)
        rep = slo_mod.replay_history(section, at=last_t)
        assert rep["reproduces"] is True
        with open(os.path.join(bundles[0], "meta.json"),
                  encoding="utf-8") as fh:
            meta = json.load(fh)
        assert meta["incident_id"] == inc_id
    finally:
        if scraper is not None:
            scraper.stop()
        incidents_mod.TRACKER.reset()
        rec._last_bundle = None
        rec._last_dump.clear()


# ---------------------------------------------------------------------------
# events: torn-tail hardening
# ---------------------------------------------------------------------------

def test_read_events_skips_and_counts_torn_tail(tmp_path):
    p = tmp_path / "events.jsonl"
    with open(p, "w", encoding="utf-8") as fh:
        fh.write(json.dumps({"event": "a", "n": 1}) + "\n")
        fh.write("[1, 2, 3]\n")                  # parseable, not a dict
        fh.write(json.dumps({"event": "b", "n": 2}) + "\n")
    with open(p, "ab") as fh:
        # hard kill mid-write, cut INSIDE a multi-byte UTF-8 sequence:
        # a strict decode would raise mid-postmortem
        fh.write(b'{"event": "c", "msg": "\xf0\x9f')
    skipped = {}
    recs = events_mod.read_events(str(p), skipped=skipped)
    assert [r["event"] for r in recs] == ["a", "b"]
    assert skipped == {str(p): 2}
    # filter still applies; a caller that doesn't ask doesn't pay
    assert [r["n"] for r in
            events_mod.read_events(str(p), event="b")] == [2]
