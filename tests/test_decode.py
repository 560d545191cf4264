"""Autoregressive decode serving: paged KV cache, decode kernel,
iteration-level continuous batching, streamed tokens.

Covers the ISSUE-15 acceptance surface:

- decode-kernel goldens vs the dense reference (causal chunk, paged
  Sq=1, multi-page, ragged kv_len, zero-length rows);
- page-pool alloc/free/occupancy round trip, allocation atomicity,
  per-page owner attribution (isolation invariants), scratch-padded
  scatter coordinates;
- join/leave-mid-iteration SOLO-PARITY golden: sequences decoded in a
  churning batch are byte-identical to solo runs, and streamed ==
  non-streamed;
- KV-page backpressure: an exhausted pool DEFERS joins (nothing
  fails), pages recycle, everything completes;
- buffer donation: steady-state decode performs no per-step
  cache-sized allocation (RSS watermark bound);
- streamed RESULT frames over the wire incl. a LEGACY one-RESULT peer
  and a killed connection mid-stream (partial tokens are not replayed
  as new client-visible work: zero lost, zero duplicated);
- the HTTP chunked /submit fallback;
- decode observability: inter-token/TTFT families, the
  decode_inter_token SLO rule, the scheduler-state flight-bundle
  section, telemetry_dump's fleet decode split.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu  # noqa: F401  (configures jax for the CPU mesh)


def _mk_model(**kw):
    from mxnet_tpu.serving import PagedCausalLM

    args = dict(vocab=64, units=32, layers=2, heads=4, max_len=128,
                seed=7)
    args.update(kw)
    return PagedCausalLM(**args)


def _mk_engine(model=None, **kw):
    from mxnet_tpu.serving import DecodeEngine

    args = dict(prefill_bucket_lens=(8, 16), max_rows=4, page_size=8,
                n_pages=24, max_new_tokens=6)
    args.update(kw)
    return DecodeEngine(model if model is not None else _mk_model(),
                        **args)


# ---------------------------------------------------------------------------
# decode kernel vs dense reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sq,kvls", [
    (1, (5, 17, 24)),          # steady-state decode, ragged lengths
    (4, (9, 20, 4)),           # chunked prefill, causal within chunk
    (8, (8, 24, 16)),          # chunk spanning whole pages
])
def test_paged_kernel_golden(monkeypatch, sq, kvls):
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    from mxnet_tpu.ops.pallas.flash_attention import (
        paged_attention_reference, paged_flash_attention)

    rng = np.random.RandomState(0)
    p, h, psize, d = 10, 4, 8, 16
    b, npg = 3, 3
    k_pages = rng.randn(p, h, psize, d).astype(np.float32)
    v_pages = rng.randn(p, h, psize, d).astype(np.float32)
    # non-contiguous PHYSICAL pages: the gather must go through the
    # table, not assume adjacency
    table = rng.permutation(p)[:b * npg].reshape(b, npg).astype(np.int32)
    q = rng.randn(b, h, sq, d).astype(np.float32)
    kvl = np.asarray(kvls, np.int32)
    out = paged_flash_attention(q, k_pages, v_pages, table, kvl,
                                interpret=True)
    ref = paged_attention_reference(q, k_pages, v_pages, table, kvl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # ... and against a from-scratch dense softmax over the gathered,
    # causally-masked history (independent of the reference helper)
    for r in range(b):
        hist = np.concatenate([k_pages[table[r, j]]
                               for j in range(npg)], axis=1)  # (h,S,d)
        vhist = np.concatenate([v_pages[table[r, j]]
                                for j in range(npg)], axis=1)
        for qi in range(sq):
            limit = kvl[r] - sq + qi + 1     # exclusive
            if limit <= 0:
                continue
            s = np.einsum("hd,hkd->hk", q[r, :, qi] / np.sqrt(d),
                          hist[:, :limit])
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            o = np.einsum("hk,hkd->hd", w, vhist[:, :limit])
            np.testing.assert_allclose(np.asarray(out)[r, :, qi], o,
                                       atol=2e-5, rtol=2e-5)


def test_paged_kernel_zero_and_pad_rows(monkeypatch):
    """kv_len 0 rows emit exact zeros; table-pad slots past the row's
    pages never contribute (widening the table changes nothing)."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    from mxnet_tpu.ops.pallas.flash_attention import paged_flash_attention

    rng = np.random.RandomState(1)
    p, h, psize, d = 6, 2, 8, 16
    k_pages = rng.randn(p, h, psize, d).astype(np.float32)
    v_pages = rng.randn(p, h, psize, d).astype(np.float32)
    q = rng.randn(2, h, 1, d).astype(np.float32)
    kvl = np.asarray([0, 5], np.int32)
    narrow = np.asarray([[1, 0], [2, 0]], np.int32)
    wide = np.asarray([[1, 3, 4, 5], [2, 3, 4, 5]], np.int32)
    o1 = np.asarray(paged_flash_attention(q, k_pages, v_pages, narrow,
                                          kvl, interpret=True))
    o2 = np.asarray(paged_flash_attention(q, k_pages, v_pages, wide,
                                          kvl, interpret=True))
    assert np.all(o1[0] == 0.0)
    np.testing.assert_array_equal(o1, o2)


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------
def test_pool_alloc_free_round_trip():
    from mxnet_tpu.serving import KVPagesExhaustedError, PagedKVPool

    pool = PagedKVPool(2, 4, 16, page_size=8, n_pages=6,
                       engine_id="pool_t0")
    assert pool.pages_for(1) == 1 and pool.pages_for(8) == 1
    assert pool.pages_for(9) == 2
    t_a = pool.ensure("a", 20)          # 3 pages
    assert len(t_a) == 3 and pool.table("a") == t_a
    pool.ensure("b", 8)
    assert pool.occupancy()["pages_used"] == 4
    for page in t_a:
        assert pool.owner_of(page) == "a"
    pool.check_isolated()
    # growth extends IN PLACE (same leading pages)
    t_a2 = pool.ensure("a", 25)
    assert t_a2[:3] == t_a and len(t_a2) == 4
    # atomic refusal: "c" needs 2, only 1 free — nothing allocated
    with pytest.raises(KVPagesExhaustedError):
        pool.ensure("c", 16)
    assert pool.table("c") == []
    assert pool.occupancy()["pages_used"] == 5
    # release recycles everything, idempotently
    assert pool.release("a") == 4
    assert pool.release("a") == 0
    pool.release("b")
    occ = pool.occupancy()
    assert occ["pages_used"] == 0 and occ["pages_free"] == 6
    pool.check_isolated()
    # no fragmentation by construction: interleaved churn at full
    # capacity keeps succeeding (every page is the same size)
    for i in range(20):
        pool.ensure(f"x{i}", 48)        # the whole pool
        pool.release(f"x{i}")
    assert pool.occupancy()["pages_free"] == 6


def test_pool_scatter_and_padded_tables():
    from mxnet_tpu.serving import PagedKVPool

    pool = PagedKVPool(1, 2, 8, page_size=4, n_pages=8,
                       engine_id="pool_t1")
    pool.ensure("a", 6)                 # 2 pages
    phys, off = pool.scatter_indices("a", 6, padded=12)
    table = pool.table("a")
    assert list(phys[:4]) == [table[0]] * 4
    assert list(phys[4:6]) == [table[1]] * 2
    # padded tail lands on the scratch page, never a live one
    assert all(p == pool.scratch_page for p in phys[6:])
    assert list(off) == [0, 1, 2, 3] * 3
    tables = pool.padded_tables(["a", "nobody"], 4)
    assert tables.shape == (2, 4)
    assert list(tables[0, :2]) == table
    assert all(v == pool.scratch_page for v in tables[0, 2:])
    assert all(v == pool.scratch_page for v in tables[1])


# ---------------------------------------------------------------------------
# solo parity + streaming semantics
# ---------------------------------------------------------------------------
def test_join_leave_solo_parity_golden():
    """Sequences joining/leaving a churning decode batch produce
    byte-identical tokens to solo runs — and the streamed parts are
    byte-identical to the final (non-streamed) result."""
    model = _mk_model()
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [4], [11, 12],
               [3, 1, 4, 1, 5, 9, 2, 6], [13, 10, 7]]
    news = [8, 3, 6, 10, 4, 7]
    # solo goldens: one request at a time
    solo = []
    with _mk_engine(model) as eng:
        for prompt, n_new in zip(prompts, news):
            solo.append(eng.infer(prompt, max_new_tokens=n_new).tolist())
        assert sorted({len(s) for s in solo}) == sorted(set(news))
    # churning batch: staggered joins from client threads, mixed
    # lengths so leaves happen mid-flight while others keep decoding
    model2 = _mk_model()                # fresh pool/caches, same seed
    with _mk_engine(model2) as eng:
        futs = [None] * len(prompts)

        def submit(i):
            time.sleep(0.003 * i)       # join at different iterations
            futs[i] = eng.submit(prompts[i], max_new_tokens=news[i],
                                 stream=True)

        threads = [threading.Thread(target=submit, args=(i,),
                                    name=f"parity_{i}", daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, fut in enumerate(futs):
            parts = [p["token"] for p in fut.stream(timeout=60)]
            out = fut.result(timeout=0).tolist()
            assert out == solo[i], (i, out, solo[i])
            assert parts == out, (i, parts, out)
            assert [p["index"] for p in fut.parts()] \
                == list(range(len(out)))
        snap = eng.snapshot()
        assert snap["decode"]["joins"] >= 2
        assert snap["decode"]["leaves"] >= 2
        eng.pool.check_isolated()
        assert eng.pool.occupancy()["pages_used"] == 0


def test_eos_and_max_tokens_leave():
    model = _mk_model()
    with _mk_engine(model) as eng:
        full = eng.infer([1, 2, 3, 4, 5], max_new_tokens=8).tolist()
    # pin eos to the 3rd generated token: greedy decode is
    # deterministic, so the truncated run must equal the prefix
    model2 = _mk_model()
    with _mk_engine(model2, eos_id=full[2]) as eng:
        out = eng.infer([1, 2, 3, 4, 5], max_new_tokens=8).tolist()
    assert out == full[:3]
    # a generation that ends AT PREFILL (max_new_tokens=1) still lands
    # in the ledger's requests column — the sum(bills) == ledger
    # reconciliation contract covers the never-joined path too
    with _mk_engine(_mk_model()) as eng:
        before = eng.costs.totals()["requests"]
        out = eng.infer([1, 2, 3], max_new_tokens=1)
        assert len(out) == 1
        assert eng.costs.totals()["requests"] == before + 1


def test_page_exhaustion_defers_not_fails():
    """A pool too small for the whole burst DEFERS joins: requests
    wait for pages to recycle and every one completes."""
    # worst case per request: pages_for(5 + 6) = 2 pages; 4 pages
    # total => at most 2 sequences live at once
    with _mk_engine(_mk_model(), page_size=8, n_pages=4,
                    max_rows=4) as eng:
        futs = [eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
                for _ in range(6)]
        outs = [f.result(timeout=120).tolist() for f in futs]
    assert len({tuple(o) for o in outs}) == 1      # same prompt, same out
    assert all(len(o) == 6 for o in outs)
    assert eng.stats.count("completed") == 6
    assert eng.pool.occupancy()["pages_used"] == 0


def test_static_mode_cohorts():
    """iteration_level=False (the bench A/B baseline) still completes
    everything, but never exceeds one cohort's membership: no join
    while a batch is live."""
    with _mk_engine(_mk_model(), iteration_level=False) as eng:
        futs = [eng.submit([i + 1, i + 2], max_new_tokens=3 + i)
                for i in range(5)]
        for f in futs:
            f.result(timeout=120)
    snap = eng.snapshot()
    assert snap["counters"]["completed"] == 5
    assert snap["iteration_level"] is False


def test_donation_no_per_step_cache_allocation():
    """Steady-state decode must not allocate a cache-sized buffer per
    step: the pool rides the jitted steps as donated arguments. The
    RSS watermark over many iterations stays under one cache size."""
    from mxnet_tpu.telemetry import resources

    model = _mk_model(units=128, heads=4, layers=2)
    with _mk_engine(model, page_size=8, n_pages=192, max_rows=2,
                    prefill_bucket_lens=(8,), max_new_tokens=40) as eng:
        cache_bytes = eng.pool.bytes_total
        assert cache_bytes > 1 << 20    # the bound must mean something
        # warm the steady-state path, then measure
        eng.infer([1, 2, 3], max_new_tokens=40)
        resources.sample()
        rss0 = resources.rss_bytes()
        steps = 0
        for _ in range(3):
            eng.infer([1, 2, 3], max_new_tokens=40)
            steps += 40
        resources.sample()
        grown = resources.rss_bytes() - rss0
    # without in-place updates this loop would have cycled
    # steps * cache_bytes (~0.3 GB) through the allocator; the
    # watermark bound tolerates one extra cache copy + noise
    assert grown < steps * cache_bytes / 8, (grown, steps, cache_bytes)


def test_weights_are_step_arguments_not_baked_constants():
    """Each compiled step takes the weights as an argument. Closed over,
    they were baked into EVERY (rows, width) executable as constants —
    invisible at toy size, 760 MB per bucket at 12x768 on the chip
    (persistent cache refused the entries, the host ran out of memory
    compiling the warm-up)."""
    import jax

    model = _mk_model(units=64, heads=4, layers=2)
    weight_bytes = sum(a.nbytes for a in model.params.values())
    with _mk_engine(model, page_size=8, n_pages=16, max_rows=2,
                    prefill_bucket_lens=(8,)) as eng:
        caches = eng.pool.caches
    i32 = np.int32
    zeros = np.zeros
    traces = [
        model._decode.trace(model.params, caches, zeros(2, i32),
                            zeros(2, i32), zeros((2, 2), i32),
                            zeros(2, np.float32), zeros(2, i32),
                            np.ones(2, np.float32), zeros(2, i32)),
        model._chunk.trace(model.params, caches, zeros(8, i32), i32(0),
                           i32(8), zeros(2, i32), np.float32(0), i32(0),
                           np.float32(1), i32(0)),
        model._prefill.trace(model.params, caches, zeros(8, i32), i32(8),
                             zeros(8, i32), zeros(8, i32), np.float32(0),
                             i32(0), np.float32(1), i32(0)),
    ]
    assert weight_bytes > 100_000       # the bound must mean something
    for t in traces:
        baked = sum(getattr(c, "nbytes", 0) for c in t.jaxpr.consts)
        assert baked < weight_bytes / 100, (baked, weight_bytes)
    assert all(isinstance(a, jax.Array) for a in model.params.values())


# ---------------------------------------------------------------------------
# streamed dispatch: wire + HTTP chunked + router
# ---------------------------------------------------------------------------
def _wire_client(eng):
    from mxnet_tpu.serving.wire import WireClient

    wc = WireClient("127.0.0.1", eng._wire.port, client_id="t",
                    expect_engine_id=eng.engine_id)
    wc.ensure()
    return wc


def test_wire_streamed_and_legacy_one_result(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WIRE", "1")
    with _mk_engine(_mk_model()) as eng:
        eng.expose()
        solo = eng.infer([1, 2, 3, 4, 5], max_new_tokens=8).tolist()
        wc = _wire_client(eng)
        try:
            # streamed: partial RESULT frames then an authoritative
            # final carrying full sequence + final/seq markers
            parts, box, done = [], {}, threading.Event()
            wc.dispatch({"tokens": np.asarray([1, 2, 3, 4, 5], np.int32),
                         "max_new_tokens": 8, "stream": True},
                        lambda exc, body: (box.update(exc=exc,
                                                      body=body),
                                           done.set()),
                        30.0, on_part=lambda b: parts.append(b))
            assert done.wait(60)
            assert box["exc"] is None
            body = box["body"]
            assert body.get("final") is True and body.get("seq") == 8
            assert np.asarray(body["result"]).tolist() == solo
            assert [p["token"] for p in parts] == solo
            assert [p["seq"] for p in parts] == list(range(8))
            # LEGACY peer: no "stream" in the payload -> exactly one
            # RESULT frame with no "final" key (the pre-streaming
            # protocol, byte-compatible for old routers)
            box2, done2 = {}, threading.Event()
            wc.dispatch({"tokens": np.asarray([1, 2, 3, 4, 5], np.int32),
                         "max_new_tokens": 8},
                        lambda exc, body: (box2.update(exc=exc,
                                                       body=body),
                                           done2.set()), 30.0)
            assert done2.wait(60)
            assert box2["exc"] is None
            assert "final" not in box2["body"]
            assert np.asarray(box2["body"]["result"]).tolist() == solo
        finally:
            wc.close()


def test_http_chunked_submit_stream():
    with _mk_engine(_mk_model()) as eng:
        srv = eng.expose()
        solo = eng.infer([1, 2, 3], max_new_tokens=6).tolist()
        req = urllib.request.Request(
            srv.url("/submit"),
            data=json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 6,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        parts, final = [], None
        with urllib.request.urlopen(req, timeout=60) as r:
            for line in r:
                if not line.strip():
                    continue
                obj = json.loads(line.decode())
                if obj.get("final", True):
                    final = obj
                    break
                parts.append(obj)
        assert final["ok"] and final["result"] == solo
        assert [p["token"] for p in parts] == solo
        assert final["seq"] == len(parts)
        assert final["cost"]["generated_tokens"] == 6


class _SlowStep:
    """Model wrapper stretching each decode iteration so a test can
    act mid-stream (kill a connection between tokens)."""

    def __init__(self, model, delay_s=0.02):
        self._m = model
        self._delay = delay_s
        self.spec = model.spec

    def prefill(self, *a, **k):
        return self._m.prefill(*a, **k)

    def prefill_chunk(self, *a, **k):
        return self._m.prefill_chunk(*a, **k)

    def decode_step(self, *a, **k):
        time.sleep(self._delay)
        return self._m.decode_step(*a, **k)


def test_kill_connection_mid_stream_zero_lost_zero_dup(monkeypatch):
    """Kill the wire connection while tokens are streaming through a
    router: the failover re-run must not replay already-delivered
    partial tokens as new client-visible work — the client stream
    stays strictly ordered with no gaps and no duplicates, and the
    final result is the complete sequence."""
    monkeypatch.setenv("MXNET_TPU_WIRE", "1")
    from mxnet_tpu.serving import ServingRouter

    # two seats with IDENTICAL weights: greedy decode is deterministic,
    # so the failover re-run regenerates the same sequence and the
    # router's index dedupe hides the replayed prefix
    engines = [_mk_engine(_SlowStep(_mk_model()), max_new_tokens=12,
                          engine_id=f"kill{i}") for i in range(2)]
    with engines[0], engines[1]:
        for eng in engines:
            eng.expose()
        solo = engines[0].infer([1, 2, 3], max_new_tokens=12).tolist()
        urls = {eng.engine_id: f"http://127.0.0.1:{eng._expo.port}"
                for eng in engines}
        with ServingRouter(urls, poll_interval_s=0.1) as router:
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and not all(
                    row.get("transport") == "wire"
                    for row in router.scoreboard().values()):
                time.sleep(0.05)
            assert all(row.get("transport") == "wire"
                       for row in router.scoreboard().values()), \
                router.scoreboard()
            fut = router.submit([1, 2, 3], max_new_tokens=12,
                                stream=True)
            seen = []
            killed = {"done": False}
            for part in fut.stream(timeout=60):
                seen.append(part)
                if len(seen) == 3 and not killed["done"]:
                    killed["done"] = True
                    # sever the dispatch connections of the seat
                    # CARRYING the stream (its partials stop mid-
                    # flight); the router must fail the dispatch over
                    # to the healthy sibling
                    busy = {eid for eid, row
                            in router.scoreboard().items()
                            if row.get("outstanding")}
                    assert busy, router.scoreboard()
                    for eng in engines:
                        if eng.engine_id in busy:
                            eng._wire.kill_connections()
            out = fut.result(timeout=0).tolist()
        assert killed["done"]
        assert out == solo
        idxs = [p["index"] for p in seen]
        toks = [p["token"] for p in seen]
        # zero duplicated: indices strictly increasing; zero lost:
        # every index present and every token the right one
        assert idxs == list(range(len(seen))), idxs
        assert toks == solo[:len(seen)], (toks, solo)
        assert len(seen) == len(solo)
        # the engines saw the request twice (original + failover re-
        # run) — but the CLIENT saw every token exactly once
        assert sum(e.stats.count("submitted") for e in engines) >= 2


def test_router_local_stream_and_parity():
    """Router-fronted in-process decode seat: streamed parts relay
    through, byte-identical to a direct engine run; non-streamed
    router result matches too."""
    from mxnet_tpu.serving import ServingRouter

    with _mk_engine(_mk_model()) as eng:
        solo = eng.infer([5, 4, 3], max_new_tokens=7).tolist()
        with ServingRouter(engines=[eng]) as router:
            fut = router.submit([5, 4, 3], max_new_tokens=7,
                                stream=True)
            parts = [p["token"] for p in fut.stream(timeout=60)]
            assert parts == solo
            assert fut.result(timeout=0).tolist() == solo
            plain = router.submit([5, 4, 3], max_new_tokens=7) \
                .result(timeout=60)
            assert np.asarray(plain).tolist() == solo


# ---------------------------------------------------------------------------
# observability: SLO rule, metrics, bundle section, fleet dump
# ---------------------------------------------------------------------------
def test_decode_observability_surface(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SLO", "1")
    import io
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from telemetry_dump import decode_split

    from mxnet_tpu.telemetry import recorder as _recorder
    from mxnet_tpu.telemetry.registry import REGISTRY

    eid = "obs_decode"
    with _mk_engine(_mk_model(), engine_id=eid) as eng:
        eng.warmup()
        # the decode_inter_token LatencySLO is declared by default
        assert eng.alerts is not None
        assert eng.alerts.evaluator.get("decode_inter_token") is not None
        # the scheduler-state flight-bundle section is live
        fn = _recorder.RECORDER.get_section(f"decode_scheduler_{eid}")
        assert fn is not None
        eng.infer([1, 2, 3, 4], max_new_tokens=5)
        state = fn()
        assert state["engine_id"] == eid
        assert "kv" in state and "prefill_queue_depth" in state
        # inter-token + ttft histograms moved under this engine's label
        fam = REGISTRY.get("mxnet_tpu_serving_inter_token_latency_ms")
        child = fam.labels(engine_id=eid)
        assert child.count >= 4          # 5 tokens -> >= 4 gaps
        assert REGISTRY.get("mxnet_tpu_serving_ttft_ms") \
            .labels(engine_id=eid).count == 1
        snap = eng.snapshot()
        assert snap["decode"]["tokens"] == 5
        assert snap["kv"]["pages_total"] == 24
        # telemetry_dump's fleet decode split reads the same families
        text = REGISTRY.render_prometheus()
        split = decode_split(text)
        assert split[eid]["tokens"] >= 5
        assert split[eid]["occupancy"] == 0.0   # drained
        assert split[eid]["join"] >= 1 and split[eid]["leave"] >= 1
    # section retired with the engine
    assert _recorder.RECORDER.get_section(f"decode_scheduler_{eid}") \
        is None


def test_warmup_manifest_round_trip():
    """Decode shape keys ((0, prefill_len) / (rows, width)) ride the
    fleet manifest machinery unchanged; an encoder-shaped replay
    skips them instead of crashing."""
    from mxnet_tpu import compile_cache

    with _mk_engine(_mk_model()) as eng:
        eng.warmup()
        manifest = eng.warmup_manifest()
    shapes = compile_cache.manifest_shapes(manifest)
    assert (0, 8) in shapes and (0, 16) in shapes
    assert any(r >= 1 for r, _w in shapes)
    # replay into a FRESH engine: every manifest shape is compatible,
    # so the warmup covers exactly the visited set
    with _mk_engine(_mk_model()) as eng2:
        eng2.warmup(manifest=manifest)
        assert set(compile_cache.manifest_shapes(
            eng2.warmup_manifest())) == set(shapes)


def test_stop_abort_fails_streams_loudly():
    """stop(drain=False) ends live streams with the engine-stopped
    failure after the received parts — the stream contract."""
    from mxnet_tpu.serving import EngineStoppedError

    eng = _mk_engine(_SlowStep(_mk_model(), delay_s=0.05),
                     max_new_tokens=50)
    eng.start()
    fut = eng.submit([1, 2, 3], max_new_tokens=50, stream=True)
    got = []
    with pytest.raises(EngineStoppedError):
        for part in fut.stream(timeout=30):
            got.append(part)
            if len(got) == 2:
                threading.Thread(target=eng.stop,
                                 kwargs={"drain": False},
                                 name="abort", daemon=True).start()
    assert len(got) >= 2
    assert eng.pool.occupancy()["pages_used"] == 0
