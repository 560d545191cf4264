"""Serving subsystem (mxnet_tpu/serving): queue admission control,
continuous packing batcher, engine correctness under concurrency, and
clean shutdown. Marker-clean — this IS the tier-1 CPU serving smoke.

The acceptance golden (closed-loop, >= 8 concurrent clients, every
response bit-matched against a solo forward within fp tolerance, zero
lost responses, distinct errors for deadline/shedding) lives in
``test_concurrent_clients_parity_and_stats``.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.serving import (ContinuousBatcher, DeadlineExceededError,
                               EngineStoppedError, LatencySummary,
                               NoEngineAvailableError, QueueFullError,
                               Request, RequestQueue, RequestTooLongError,
                               ServingEngine, ServingRouter)
from mxnet_tpu.serving.queue import InferenceFuture


class StubModel:
    """Contract-shaped stand-in: out[b, s, 0] == ids[b, s], so a
    correctly-unpacked response equals the request's own tokens —
    any placement/slicing bug shows up as a value mismatch."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.started = threading.Event()
        self.shapes = []

    def __call__(self, ids, token_types, valid_length, segment_ids,
                 positions):
        self.started.set()
        if self.delay:
            time.sleep(self.delay)
        self.shapes.append(tuple(ids.shape))
        return nd.array(ids.asnumpy().astype(np.float32)[..., None])


def _tiny_bert():
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel

    mx.random.seed(11)
    net = BERTModel(vocab_size=64, units=16, hidden_size=32, num_layers=1,
                    num_heads=2, max_length=16, dropout=0.0,
                    attention_dropout=0.0, use_pooler=False)
    net.initialize(init=mx.initializer.Normal(0.02))
    return net


# ---------------------------------------------------------------------------
# queue / future / metrics units
# ---------------------------------------------------------------------------

def test_request_queue_admission_and_poll():
    q = RequestQueue(max_depth=2)
    r1, r2 = Request([1, 2]), Request([3])
    q.put(r1)
    q.put(r2)
    with pytest.raises(QueueFullError):
        q.put(Request([4]))
    # poll drains what's there without waiting for more
    t0 = time.monotonic()
    got = q.poll(max_items=8, timeout=5.0)
    assert [g.id for g in got] == [r1.id, r2.id]
    assert time.monotonic() - t0 < 1.0
    assert all(g.t_drain is not None for g in got)
    # empty queue: poll waits at most timeout
    assert q.poll(4, timeout=0.05) == []
    q.close()
    with pytest.raises(EngineStoppedError):
        q.put(Request([5]))


def test_request_deadline_and_validation():
    r = Request([1, 2, 3], deadline_ms=1.0)
    time.sleep(0.01)
    assert r.expired()
    assert not Request([1]).expired()
    with pytest.raises(ValueError):
        Request([])
    with pytest.raises(ValueError):
        Request([1, 2], token_types=[0])


def test_future_result_and_exception():
    f = InferenceFuture()
    with pytest.raises(TimeoutError):
        f.result(timeout=0.01)
    f.set_result(42)
    assert f.done() and f.result() == 42 and f.exception() is None
    g = InferenceFuture()
    g.set_exception(DeadlineExceededError("late"))
    with pytest.raises(DeadlineExceededError):
        g.result()


def test_latency_summary_percentiles():
    s = LatencySummary(capacity=100)
    for v in range(1, 101):
        s.observe(float(v))
    snap = s.snapshot()
    assert snap["count"] == 100
    assert snap["p50_ms"] == 50.0
    assert snap["p99_ms"] == 99.0
    assert snap["max_ms"] == 100.0
    assert LatencySummary().snapshot() == {"count": 0}


# ---------------------------------------------------------------------------
# batcher units
# ---------------------------------------------------------------------------

def test_batcher_buckets_quantization_and_leftovers():
    b = ContinuousBatcher(bucket_lens=(8, 16), max_rows=4)
    # bucket: longest request picks the row length
    plan, left = b.plan([Request([1] * 3), Request([2] * 10)])
    assert plan.row_len == 16 and not left
    # row count quantizes to powers of two with 1-token dummy rows
    plan, _ = b.plan([Request([1] * 7), Request([2] * 7), Request([3] * 7)])
    assert plan.rows == 4 and plan.pad_rows == 1
    assert plan.valid_length[-1] == 1 and plan.segment_ids[-1, 0] == 1
    assert plan.valid_tokens == 21
    # overflow: requests beyond max_rows rows come back as leftovers
    reqs = [Request([9] * 8) for _ in range(6)]
    plan, left = b.plan(reqs)
    assert len(plan.entries) == 4 and len(left) == 2
    assert [r.id for r in left] == [reqs[4].id, reqs[5].id]
    # the compile budget is closed and small
    assert set(plan.data.shape for plan in [plan]) <= set(b.shape_universe())
    assert len(b.shape_universe()) == 6  # {1,2,4} rows x {8,16} lens


def test_batcher_packs_multiple_requests_per_row():
    b = ContinuousBatcher(bucket_lens=(16,), max_rows=2)
    reqs = [Request(np.arange(1, n + 1)) for n in (6, 5, 4, 9)]
    plan, left = b.plan(reqs)
    assert not left
    assert plan.rows == 2
    # every request's tokens are where its placement says
    for req, pl in plan.entries:
        got = plan.data[pl.row, pl.offset:pl.offset + pl.length]
        assert np.array_equal(got, req.tokens)
        seg = plan.segment_ids[pl.row, pl.offset:pl.offset + pl.length]
        assert (seg == pl.segment).all()
        pos = plan.positions[pl.row, pl.offset:pl.offset + pl.length]
        assert np.array_equal(pos, np.arange(pl.length))
    assert plan.packing_efficiency == 24 / 32.0


# ---------------------------------------------------------------------------
# engine behavior (stub model: no compiles, pure threading semantics)
# ---------------------------------------------------------------------------

def test_engine_roundtrip_and_placement_mapping():
    stub = StubModel()
    eng = ServingEngine(stub, bucket_lens=(16,), max_rows=2,
                        max_queue_depth=32)
    rs = np.random.RandomState(3)
    with eng:
        toks = [rs.randint(1, 60, n).astype(np.int32)
                for n in (3, 7, 12, 5, 9, 4)]
        outs = [eng.submit(t).result(timeout=30) for t in toks]
    for t, o in zip(toks, outs):
        assert o.shape == (len(t), 1)
        assert np.array_equal(o[:, 0].astype(np.int32), t)
    snap = eng.snapshot()
    assert snap["counters"]["completed"] == len(toks)
    assert snap["counters"]["submitted"] == len(toks)
    # every dispatched shape came from the batcher's closed universe
    universe = set(ContinuousBatcher((16,), 2).shape_universe())
    assert set(stub.shapes) <= universe


def test_engine_deadline_expiry_is_distinct_error():
    stub = StubModel(delay=0.3)
    eng = ServingEngine(stub, bucket_lens=(16,), max_rows=1,
                        max_queue_depth=8)
    with eng:
        f1 = eng.submit([1, 2, 3])          # occupies the worker
        assert stub.started.wait(10)
        f2 = eng.submit([4, 5], deadline_ms=10)  # expires in queue
        assert f1.result(timeout=30).shape == (3, 1)
        with pytest.raises(DeadlineExceededError):
            f2.result(timeout=30)
    assert eng.stats.count("expired") == 1
    assert eng.stats.count("completed") == 1


def test_engine_queue_full_sheds_with_backpressure():
    stub = StubModel(delay=0.4)
    eng = ServingEngine(stub, bucket_lens=(16,), max_rows=1,
                        max_queue_depth=2)
    with eng:
        first = eng.submit([1])             # drained into the worker
        assert stub.started.wait(10)
        ok = [eng.submit([2]), eng.submit([3])]   # fill the queue
        with pytest.raises(QueueFullError):
            eng.submit([4])
        assert eng.stats.count("rejected_queue_full") == 1
        for f in [first] + ok:
            f.result(timeout=30)            # nothing below the limit lost


def test_engine_rejects_oversize_requests():
    eng = ServingEngine(StubModel(), bucket_lens=(8, 16), max_rows=2)
    with eng:
        with pytest.raises(RequestTooLongError):
            eng.submit(list(range(17)))
    assert eng.stats.count("rejected_too_long") == 1


def test_engine_clean_shutdown_drains_in_flight():
    stub = StubModel(delay=0.05)
    eng = ServingEngine(stub, bucket_lens=(16,), max_rows=1,
                        max_queue_depth=64)
    eng.start()
    futs = [eng.submit([i + 1]) for i in range(10)]
    eng.stop(drain=True, timeout=60)        # returns only when drained
    for i, f in enumerate(futs):
        assert f.result(timeout=0.1)[0, 0] == i + 1
    assert eng.stats.count("completed") == 10
    assert not eng.running
    with pytest.raises(EngineStoppedError):
        eng.submit([1])


def test_engine_abort_fails_pending_loudly():
    stub = StubModel(delay=0.3)
    eng = ServingEngine(stub, bucket_lens=(16,), max_rows=1,
                        max_queue_depth=8)
    eng.start()
    f1 = eng.submit([1, 2])
    assert stub.started.wait(10)
    pending = [eng.submit([3]), eng.submit([4])]
    eng.stop(drain=False, timeout=60)
    assert f1.result(timeout=30).shape == (2, 1)  # in-flight finishes
    for f in pending:
        with pytest.raises(EngineStoppedError):
            f.result(timeout=5)
    assert eng.stats.count("cancelled") == 2


def test_engine_survives_model_failure():
    calls = {"n": 0}

    class Flaky(StubModel):
        def __call__(self, *args):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return super().__call__(*args)

    eng = ServingEngine(Flaky(), bucket_lens=(16,), max_rows=1)
    with eng:
        bad = eng.submit([1, 2, 3])
        with pytest.raises(RuntimeError):
            bad.result(timeout=30)
        ok = eng.submit([4, 5]).result(timeout=30)
        assert ok.shape == (2, 1)
    assert eng.stats.count("failed") == 1
    assert eng.stats.count("completed") == 1


def test_engine_model_failure_spares_carry():
    """A poison BATCH fails only its own requests: leftovers carried
    to the next iteration (never dispatched in the failed batch) must
    still be served."""
    calls = {"n": 0}

    class Flaky(StubModel):
        def __call__(self, *args):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("boom")
            return super().__call__(*args)

    stub = Flaky(delay=0.2)
    eng = ServingEngine(stub, bucket_lens=(16,), max_rows=1,
                        max_queue_depth=8)
    with eng:
        f1 = eng.submit([1] * 10)
        assert stub.started.wait(10)
        f2 = eng.submit([2] * 10)       # 10+10 > 16: r3 becomes carry
        f3 = eng.submit([3] * 10)
        assert f1.result(timeout=30).shape == (10, 1)
        with pytest.raises(RuntimeError):
            f2.result(timeout=30)
        assert f3.result(timeout=30)[0, 0] == 3.0
    assert eng.stats.count("failed") == 1
    assert eng.stats.count("completed") == 2


def test_future_first_write_wins():
    f = InferenceFuture()
    f.set_result(7)
    f.set_exception(RuntimeError("late sweep"))
    assert f.result() == 7              # the sweep must not clobber it


def test_future_callbacks_run_outside_lock_and_report_errors():
    """Done-callbacks are snapshot under the future's lock and invoked
    OUTSIDE it (the mxlint lock-callback contract): a callback that
    reenters the future — or raises — must neither deadlock nor lose
    the result, and a raising observer leaves a
    ``future_callback_error`` event."""
    from mxnet_tpu.telemetry import events as _events

    records = []
    _events.add_tap(records.append)
    try:
        f = InferenceFuture()
        f.trace_id = "req-reentrant"
        seen = []

        def reentrant(fut):
            # reentry: registering ANOTHER callback from inside a
            # callback takes the future's lock again — deadlocks if
            # callbacks ran under it
            fut.add_done_callback(lambda g: seen.append(g.result()))

        def broken(fut):
            raise RuntimeError("broken observer")

        f.add_done_callback(reentrant)
        f.add_done_callback(broken)
        f.set_result(41)
        assert f.result(timeout=1) == 41
        assert seen == [41]
        errs = [r for r in records if r["event"] == "future_callback_error"]
        assert errs and "broken observer" in errs[0]["error"]
        assert errs[0]["trace_id"] == "req-reentrant"
    finally:
        _events.remove_tap(records.append)


def test_reentrant_done_callback_cannot_deadlock_submit():
    """ISSUE-6 satellite regression: a done-callback that REENTERS
    ``engine.submit`` runs on the engine worker thread the moment it
    fulfils the future — if shed/expiry/completion notifications ran
    under the queue lock, this would deadlock the worker against its
    own admission path. Must complete well inside the timeout."""
    eng = ServingEngine(StubModel(), bucket_lens=(8,), max_rows=2)
    with eng:
        chained = []
        done = threading.Event()

        def resubmit(fut):
            # executes on the worker thread, mid-completion sweep
            chained.append(eng.submit([7, 8, 9]))
            done.set()

        first = eng.submit([1, 2, 3, 4])
        first.add_done_callback(resubmit)
        np.testing.assert_allclose(
            np.asarray(first.result(timeout=30)).reshape(-1)[:4],
            [1, 2, 3, 4])
        assert done.wait(30)
        np.testing.assert_allclose(
            np.asarray(chained[0].result(timeout=30)).reshape(-1)[:3],
            [7, 8, 9])
    assert eng.stats.count("completed") == 2


def test_engine_reset_stats_separates_windows():
    eng = ServingEngine(StubModel(), bucket_lens=(16,), max_rows=1)
    with eng:
        eng.infer([1, 2], timeout=30)
        assert eng.stats.count("completed") == 1
        eng.reset_stats()
        assert eng.stats.count("completed") == 0
        eng.infer([3], timeout=30)
        assert eng.stats.count("completed") == 1
        assert eng.snapshot()["queue_depth"] == 0


# ---------------------------------------------------------------------------
# the acceptance golden: real model, 8 concurrent clients, solo parity
# ---------------------------------------------------------------------------

def test_concurrent_clients_parity_and_stats():
    from mxnet_tpu.gluon.model_zoo.bert import bert_serving_entry

    net = _tiny_bert()
    eng = ServingEngine(bert_serving_entry(net), bucket_lens=(16,),
                        max_rows=4, max_queue_depth=128)
    rs = np.random.RandomState(7)
    lens = [3, 5, 8, 11, 13, 15]            # few distinct solo shapes
    n_clients, per_client = 8, 4
    results = {}
    errors = []

    def client(cid):
        rc = np.random.RandomState(100 + cid)
        try:
            for j in range(per_client):
                toks = rc.randint(1, 60, lens[(cid + j) % len(lens)]) \
                    .astype(np.int32)
                out = eng.infer(toks, timeout=300)
                results[(cid, j)] = (toks, out)
        except Exception as e:  # surfaced below — a lost response fails
            errors.append((cid, e))

    with eng:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
    assert not errors, errors
    assert len(results) == n_clients * per_client   # zero lost responses

    # per-request parity vs the same tokens run SOLO through the model
    solo_cache = {}
    for (cid, j), (toks, out) in sorted(results.items()):
        key = toks.tobytes()
        if key not in solo_cache:
            one = nd.array(toks[None, :], dtype="int32")
            tt = nd.zeros((1, len(toks)), dtype="int32")
            with mx.autograd.predict_mode():
                solo_cache[key] = net(one, tt).asnumpy()[0]
        np.testing.assert_allclose(out, solo_cache[key], rtol=2e-4,
                                   atol=2e-4,
                                   err_msg=f"client {cid} req {j}")

    snap = eng.snapshot()
    c = snap["counters"]
    assert c["completed"] == n_clients * per_client
    assert c["submitted"] == c["completed"]  # nothing shed in this run
    lat = snap["latency"]["total"]
    assert lat["count"] == c["completed"]
    assert 0 < lat["p50_ms"] <= lat["p99_ms"] <= lat["max_ms"]
    assert snap["packing_efficiency"] is not None
    assert snap["queue_depth"] == 0
    assert c["batches"] >= 1 and c["compiles"] >= 1


def test_loaded_traffic_packs_densely():
    """The packing acceptance number: under sustained load (the queue
    holds work while a batch computes — the continuous-batching steady
    state) the synthetic variable-length mix packs > 0.8 of dispatched
    slots with real tokens."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from serve_loadgen import run_load

    stub = StubModel(delay=0.02)   # compute window lets the queue fill
    eng = ServingEngine(stub, bucket_lens=(64,), max_rows=4,
                        max_queue_depth=256)
    with eng:
        report = run_load(eng, n_clients=12, requests_per_client=8,
                          min_len=16, max_len=64, vocab=60)
    assert report["completed"] == 96
    assert report["errors"] == 0 and report["shed"] == 0
    snap = report["engine"]
    assert snap["packing_efficiency"] > 0.8, snap
    lat = report["p50_ms"], report["p99_ms"]
    assert 0 < lat[0] <= lat[1]
    assert snap["latency"]["queue"]["count"] == 96


# ---------------------------------------------------------------------------
# engine-labeled metric families (ROADMAP per-chip router metrics)
# ---------------------------------------------------------------------------

def test_engine_metric_families_disjoint_per_engine():
    """REGRESSION for the shared-family collision: two engines in one
    process used to double-count one unlabeled family set; with
    engine_id labels each engine's counters stay disjoint and each
    equals that engine's own window counts exactly."""
    from mxnet_tpu.telemetry import REGISTRY

    a = ServingEngine(StubModel(), bucket_lens=(16,), max_rows=1,
                      engine_id="disjoint-a")
    b = ServingEngine(StubModel(), bucket_lens=(16,), max_rows=1,
                      engine_id="disjoint-b")
    with a, b:
        for _ in range(3):
            a.infer([1, 2], timeout=30)
        for _ in range(5):
            b.infer([3], timeout=30)
    req_total = REGISTRY.counter("mxnet_tpu_serving_requests_total", "",
                                 ("engine_id", "event"))
    for eng, n in ((a, 3), (b, 5)):
        for event in ("submitted", "completed"):
            child = req_total.labels(engine_id=eng.engine_id, event=event)
            assert child.value == n, (eng.engine_id, event, child.value)
    lat = REGISTRY.get("mxnet_tpu_serving_latency_ms")
    assert lat.labels(engine_id="disjoint-a", stage="total").count == 3
    assert lat.labels(engine_id="disjoint-b", stage="total").count == 5
    # the rendered exposition carries both engines' labeled children
    text = REGISTRY.render_prometheus()
    assert ('mxnet_tpu_serving_requests_total{engine_id="disjoint-a",'
            'event="completed"} 3') in text
    assert ('mxnet_tpu_serving_requests_total{engine_id="disjoint-b",'
            'event="completed"} 5') in text


# ---------------------------------------------------------------------------
# multi-engine router: routing, failover, shed, scoreboard
# ---------------------------------------------------------------------------

def _stub_engine(engine_id, delay=0.0, **kw):
    kw.setdefault("bucket_lens", (16,))
    kw.setdefault("max_rows", 2)
    return ServingEngine(StubModel(delay=delay), engine_id=engine_id, **kw)


def test_router_roundtrip_distribution_and_snapshot():
    a = _stub_engine("rt-a")
    b = _stub_engine("rt-b")
    router = ServingRouter(engines=[a, b], poll_interval_s=0.2)
    rs = np.random.RandomState(5)
    with a, b, router:
        toks = [rs.randint(1, 60, n).astype(np.int32)
                for n in (3, 7, 5, 9, 2, 6, 4, 8)]
        outs = [router.submit(t).result(timeout=30) for t in toks]
        for t, o in zip(toks, outs):
            assert np.array_equal(o[:, 0].astype(np.int32), t)
        snap = router.snapshot()
    c = snap["counters"]
    assert c["completed"] == len(toks) == c["submitted"]
    dispatched = {eid: row["dispatched"]
                  for eid, row in snap["engines"].items()}
    assert sum(dispatched.values()) == len(toks)
    # least-outstanding over sequential submits: both engines serve
    assert all(n > 0 for n in dispatched.values()), dispatched
    assert snap["engines_up"] == 2
    assert snap["latency"]["total"]["count"] == len(toks)


def test_router_failover_requeues_to_sibling():
    """An engine dying mid-load (stop drain=False) fails its
    admitted-but-undispatched requests with EngineStoppedError; the
    router re-queues them to the sibling — zero client-visible
    failures, failover counted per failed engine."""
    from mxnet_tpu.telemetry import REGISTRY

    live = _stub_engine("fo-live", max_rows=1)
    dying = _stub_engine("fo-dying", max_rows=1)
    live.start()
    dying.start()
    # poll slow enough that DISPATCH discovers the death, not the poll
    router = ServingRouter(engines=[live, dying], poll_interval_s=30.0)
    router.start()
    try:
        dying.stop(drain=False)
        futs = [router.submit([7, 8]) for _ in range(8)]
        outs = [f.result(timeout=30) for f in futs]
        assert all(o[0, 0] == 7.0 for o in outs)      # nothing lost
        snap = router.snapshot()
        assert snap["counters"]["completed"] == 8
        assert snap["counters"]["requeued"] >= 1
        assert snap["engines"]["fo-dying"]["routable"] is False
        fo = REGISTRY.counter("mxnet_tpu_router_failover_total", "",
                              ("engine_id",))
        assert fo.labels(engine_id="fo-dying").value >= 1
    finally:
        router.stop()
        live.stop()


def test_router_sheds_when_all_engines_down():
    """Fleet down => submit sheds with a DISTINCT error (and the shed
    trace is force-kept, same contract as engine sheds)."""
    from mxnet_tpu.telemetry import spans

    eng = _stub_engine("down-1")
    eng.start()
    router = ServingRouter(engines=[eng], poll_interval_s=0.1,
                           health_fail_after=1)
    router.start()
    try:
        assert router.infer([1, 2], timeout=30)[0, 0] == 1.0
        eng.stop(drain=True)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if not router.snapshot()["engines"]["down-1"]["routable"]:
                break
            time.sleep(0.05)
        snap = router.snapshot()
        assert snap["engines_up"] == 0, snap["engines"]
        with pytest.raises(NoEngineAvailableError):
            router.submit([3, 4])
        assert router.count("shed_no_engine") == 1
        kept = spans.traces_summary()["kept"]
        shed = [k for k in kept if k["root"] == "router/request"
                and k["status"] == "error"]
        assert shed, kept
    finally:
        router.stop()


def test_router_engine_overflow_fails_over_then_sheds():
    """A saturated engine (its own queue at bound) is an ENGINE
    failure from the router's view: the request retries a sibling;
    with no sibling left it sheds LOUDLY — and a stopped router
    refuses new work with a distinct error."""
    slow = ServingEngine(StubModel(delay=0.3), bucket_lens=(16,),
                         max_rows=1, max_queue_depth=1,
                         engine_id="ovf-slow")
    roomy = _stub_engine("ovf-roomy", max_rows=1)
    router = ServingRouter(engines=[slow, roomy], poll_interval_s=30.0)
    with slow, roomy, router:
        # saturate: one in flight + one queued at the slow engine, the
        # rest overflow — every overflow must land on the sibling
        futs = [router.submit([9, 9]) for _ in range(10)]
        outs = [f.result(timeout=60) for f in futs]
        assert all(o[0, 0] == 9.0 for o in outs)       # nothing lost
        snap = router.snapshot()
        assert snap["counters"]["completed"] == 10

    # single saturated engine, no sibling: the shed is explicit
    slow2 = ServingEngine(StubModel(delay=0.3), bucket_lens=(16,),
                          max_rows=1, max_queue_depth=1,
                          engine_id="ovf-solo")
    router2 = ServingRouter(engines=[slow2], poll_interval_s=30.0)
    with slow2, router2:
        futs, shed = [], 0
        for _ in range(8):
            futs.append(router2.submit([3]))
        for f in futs:
            try:
                f.result(timeout=60)
            except NoEngineAvailableError:
                shed += 1
        assert shed >= 1                 # overflow shed, not silent
        assert shed == router2.count("shed_no_engine")
        assert router2.count("completed") == len(futs) - shed
    with pytest.raises(EngineStoppedError):
        router2.submit([5])
    assert router2.count("rejected_stopped") == 1


def test_router_scoreboard_events_and_recovery(tmp_path):
    """up→down→up transitions emit router_engine_state events and the
    scoreboard gauges follow."""
    from mxnet_tpu.telemetry import REGISTRY, events

    events.configure(str(tmp_path / "router.jsonl"))
    try:
        eng = _stub_engine("sb-1")
        eng.start()
        srv = eng.expose()
        router = ServingRouter(poll_interval_s=0.1, health_fail_after=1)
        # remote seat against the engine's own exposition endpoint
        router.add_engine("sb-remote", f"http://127.0.0.1:{srv.port}")
        router.start()
        try:
            out = router.infer([5, 6], timeout=30)
            assert out.shape == (2, 1)
            eng.stop(drain=True)         # endpoint goes away
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                row = router.snapshot()["engines"]["sb-remote"]
                if not row["routable"]:
                    break
                time.sleep(0.05)
            assert not router.snapshot()["engines"]["sb-remote"][
                "routable"]
            up = REGISTRY.gauge("mxnet_tpu_router_engine_up", "",
                                ("engine_id",))
            assert up.labels(engine_id="sb-remote").value == 0
        finally:
            router.stop()
        log_path = events.get_log().path
    finally:
        events.configure(None)
    states = events.read_events(log_path, event="router_engine_state")
    assert any(e["engine_id"] == "sb-remote" and e["state"] == "down"
               for e in states), states


def test_engine_pool_modes():
    stub = StubModel()
    outs = {}
    for pool in ("tokens", "mean", "cls"):
        eng = ServingEngine(stub, bucket_lens=(16,), max_rows=1, pool=pool)
        with eng:
            outs[pool] = eng.infer([2, 4, 6], timeout=30)
    assert outs["tokens"].shape == (3, 1)
    assert outs["mean"].shape == (1,) and outs["mean"][0] == 4.0
    assert outs["cls"].shape == (1,) and outs["cls"][0] == 2.0
