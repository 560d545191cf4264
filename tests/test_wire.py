"""Binary dispatch wire (mxnet_tpu/serving/wire.py): the persistent
multiplexed router↔engine transport.

Mirrors ``test_kvstore_wire.py`` for the SERVING dispatch port — the
codec is shared, so this file owns what's new: hostile frames against
a live dispatch listener (truncated frames, length bombs, unknown
frame types, garbage correlation ids must error the frame or the
connection, never the process), the end-to-end 2-remote-engine parity
golden with ZERO threads spawned per request on the wire path,
kill-the-connection-mid-request failover (requeue loses nothing), the
JSON-only-engine fallback regression, and the bounded HTTP waiter
pool that replaced the legacy thread-per-in-flight-request shape.
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu.serving import ServingEngine, ServingRouter
from mxnet_tpu.serving import wire as wiremod
from mxnet_tpu.serving.router import _FallbackPool
from mxnet_tpu.serving.wire import (FrameTooLargeError, WireClient,
                                    WireError, recv_frame, send_frame,
                                    wire_decode, wire_encode)


def model(ids, token_types, valid_length, segment_ids, positions):
    """out[b, s, 0] == ids[b, s]: responses bit-match their request."""
    return nd.array(ids.asnumpy().astype(np.float32)[..., None])


class SlowModel:
    def __init__(self, delay):
        self.delay = delay
        self.started = threading.Event()

    def __call__(self, ids, token_types, valid_length, segment_ids,
                 positions):
        self.started.set()
        time.sleep(self.delay)
        return nd.array(ids.asnumpy().astype(np.float32)[..., None])


def _engine(engine_id, m=model, **kw):
    kw.setdefault("bucket_lens", (32,))
    kw.setdefault("max_rows", 2)
    return ServingEngine(m, engine_id=engine_id, **kw)


def _wait_transport(router, transport, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        board = router.scoreboard()
        if board and all(r.get("transport") == transport
                         for r in board.values()):
            return board
        time.sleep(0.05)
    raise AssertionError(
        f"fleet never reached transport={transport}: "
        f"{router.scoreboard()}")


# ---------------------------------------------------------------------------
# codec: shared with kvstore (one wire encoding in the repo)
# ---------------------------------------------------------------------------

def test_codec_is_the_kvstore_codec():
    import mxnet_tpu.kvstore as kvmod
    msg = ("SUBMIT", 7, {"tokens": np.arange(9, dtype=np.int32),
                         "trace_id": "req-x", "deadline_ms": None})
    raw = wire_encode(msg)
    assert kvmod._wire_encode(msg) == raw
    got = wire_decode(raw)
    assert got[0] == "SUBMIT" and got[1] == 7
    assert got[2]["tokens"].dtype == np.int32
    assert np.array_equal(got[2]["tokens"], np.arange(9))


def test_frame_cap_refused_before_allocation():
    a, b = socket.socketpair()
    try:
        with pytest.raises(FrameTooLargeError):
            send_frame(a, b"x" * 2048, max_frame=1024)
        # a hostile LENGTH PREFIX is refused off the header alone
        a.sendall(struct.pack("<Q", 1 << 40))
        with pytest.raises(FrameTooLargeError) as ei:
            recv_frame(b, max_frame=1024)
        # both historical refusal hierarchies hold
        from mxnet_tpu.base import MXNetError
        assert isinstance(ei.value, (MXNetError,))
        assert isinstance(ei.value, ValueError)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# hostile frames against a live dispatch listener
# ---------------------------------------------------------------------------

def test_dispatch_port_refuses_hostile_frames():
    """Undecodable/oversized frames drop THE CONNECTION; unknown frame
    types and garbage correlation ids error THE FRAME; the engine
    process survives all of it and keeps serving."""
    eng = _engine("hostile")
    with eng:
        srv = eng.expose(port=0)
        port = eng._wire.port
        addr = ("127.0.0.1", port)

        # (a) raw garbage after the length prefix: connection dropped
        s = socket.create_connection(addr, timeout=5.0)
        s.sendall(struct.pack("<Q", 5) + b"zjunk")
        assert s.recv(1) == b""          # peer closed, no reply
        s.close()

        # (b) length bomb: refused off the 8-byte header, dropped
        s = socket.create_connection(addr, timeout=5.0)
        s.sendall(struct.pack("<Q", 1 << 62))
        assert s.recv(1) == b""
        s.close()

        # (c) truncated frame then close: never kills the process
        s = socket.create_connection(addr, timeout=5.0)
        payload = wire_encode(("SUBMIT", 1, {"tokens": np.arange(4)}))
        s.sendall(struct.pack("<Q", len(payload)) + payload[:7])
        s.close()

        # (d) well-formed frame of an UNKNOWN type: ERROR frame back,
        # connection stays up for the next frame
        s = socket.create_connection(addr, timeout=5.0)
        send_frame(s, ("DECODE", 3, {"x": 1}))
        frame, _ = recv_frame(s)
        assert frame[0] == "ERROR" and frame[1] == 3
        assert "unknown frame type" in frame[2]["error"]

        # (e) garbage correlation id on a SUBMIT: frame errored (the
        # reply can't be matched, so corr rides back as None)
        send_frame(s, ("SUBMIT", "not-a-corr-id",
                       {"tokens": np.arange(4, dtype=np.int32)}))
        frame, _ = recv_frame(s)
        assert frame[0] == "ERROR" and frame[1] is None
        assert "correlation id" in frame[2]["error"]

        # (f) SUBMIT payload of the wrong shape: errored, not fatal
        send_frame(s, ("SUBMIT", 9, "tokens"))
        frame, _ = recv_frame(s)
        assert frame[0] == "ERROR" and frame[1] == 9
        s.close()

        # the engine survived everything above: a REAL wire round trip
        # and the in-process path both still serve
        client = WireClient("127.0.0.1", port, client_id="t",
                            expect_engine_id="hostile", conns=1)
        try:
            assert client.ensure() == 1
            assert client.ping(timeout_s=5.0)
            got = {}
            evt = threading.Event()

            def on_done(exc, body):
                got["exc"], got["body"] = exc, body
                evt.set()

            toks = np.arange(1, 11, dtype=np.int32)
            client.dispatch({"tokens": toks}, on_done, timeout_s=30.0)
            assert evt.wait(30.0)
            assert got["exc"] is None, got
            assert np.array_equal(
                np.asarray(got["body"]["result"]).ravel()[:10],
                toks.astype(np.float32))
        finally:
            client.close()
        out = eng.submit(np.arange(1, 5, dtype=np.int32)).result(30.0)
        assert np.array_equal(np.asarray(out).ravel()[:4],
                              np.arange(1, 5, dtype=np.float32))
        srv  # keepalive


def test_wire_client_refuses_wrong_engine_and_non_wire_port():
    """The handshake rejects a port answering as a DIFFERENT engine
    (stale/recycled port) and a port speaking another protocol."""
    eng = _engine("who")
    with eng:
        eng.expose(port=0)
        c = WireClient("127.0.0.1", eng._wire.port, client_id="t",
                       expect_engine_id="somebody-else", conns=1)
        assert c.ensure() == 0
        assert not c.has_live()
        c.close()
        # the HTTP exposition port does not speak the wire protocol
        c2 = WireClient("127.0.0.1", eng._expo.port, client_id="t",
                        conns=1, timeout_s=2.0)
        assert c2.ensure() == 0
        c2.close()


# ---------------------------------------------------------------------------
# end-to-end: router over 2 remote engines on the binary wire
# ---------------------------------------------------------------------------

def test_router_wire_parity_zero_threads_per_request(monkeypatch):
    """The acceptance golden: 2 remote engines behind a wire router —
    results bit-match the request tokens under 8 concurrent clients,
    both engines serve, and the steady-state thread set does NOT grow
    with in-flight requests (the wire path spawns per CONNECTION, the
    legacy path spawned per REQUEST). The canary prober is pinned off:
    its own per-seat wire connections come up asynchronously (once the
    health poll advertises the port) and would shift the steady-state
    thread snapshot this test pins."""
    monkeypatch.setenv("MXNET_TPU_CANARY", "0")
    with _engine("w0") as e0, _engine("w1") as e1:
        u0, u1 = e0.expose(port=0), e1.expose(port=0)
        router = ServingRouter(poll_interval_s=0.1)
        router.add_engine("w0", f"http://127.0.0.1:{u0.port}")
        router.add_engine("w1", f"http://127.0.0.1:{u1.port}")
        with router:
            _wait_transport(router, "wire")
            # prime: one request through, then snapshot the wire/pool
            # thread population
            router.submit(np.arange(1, 5, dtype=np.int32)).result(30.0)

            def dispatch_threads():
                return sorted(
                    t.name for t in threading.enumerate()
                    if t.name.startswith(("mxnet_tpu_wire_",
                                          "mxnet_tpu_router_http_",
                                          "mxnet_tpu_router_rpc_")))

            before = dispatch_threads()
            assert not [n for n in before
                        if n.startswith("mxnet_tpu_router_")], before

            results = {}
            errors = []

            def client(cid):
                rs = np.random.RandomState(cid)
                for k in range(6):
                    toks = rs.randint(
                        1, 1000, rs.randint(4, 30)).astype(np.int32)
                    try:
                        out = router.submit(toks).result(timeout=60.0)
                    except Exception as e:       # pragma: no cover
                        errors.append(repr(e))
                        return
                    results[(cid, k)] = (
                        toks, np.asarray(out).ravel()[:toks.size])

            threads = [threading.Thread(target=client, args=(c,),
                                        name=f"t_wire_client_{c}",
                                        daemon=True) for c in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
            assert not errors, errors
            assert len(results) == 48
            for toks, out in results.values():
                assert np.array_equal(out, toks.astype(np.float32))
            # zero threads per request: the dispatch-thread population
            # is exactly what it was before the 48-request burst
            assert dispatch_threads() == before
            # both seats actually served over the wire
            board = router.scoreboard()
            assert all(r["transport"] == "wire" for r in board.values())
            assert all(r["dispatched"] > 0 for r in board.values())
            snap = router.snapshot()
            assert snap["counters"]["completed"] >= 48
            over = snap["dispatch_overhead"]
            assert over.get("wire", {}).get("count", 0) >= 48
            assert "json" not in over or over["json"]["count"] == 0


def test_kill_connection_mid_request_loses_nothing():
    """Severing every wire connection to one engine mid-request fails
    its in-flight dispatches with WireError → the router requeues them
    to the sibling: every submitted request completes."""
    slow = SlowModel(0.4)
    with _engine("k0", m=slow, max_rows=1) as e0, \
            _engine("k1", max_rows=2) as e1:
        u0, u1 = e0.expose(port=0), e1.expose(port=0)
        router = ServingRouter(poll_interval_s=0.1)
        router.add_engine("k0", f"http://127.0.0.1:{u0.port}")
        router.add_engine("k1", f"http://127.0.0.1:{u1.port}")
        with router:
            _wait_transport(router, "wire")
            futs = [router.submit(np.arange(1, 9, dtype=np.int32))
                    for _ in range(6)]
            assert slow.started.wait(10.0)   # k0 is mid-forward
            seat = router._seats["k0"]
            wire = seat._wire
            assert wire is not None and wire.has_live()
            for conn in list(wire._slots):   # kill the CONNECTIONS,
                if conn is not None:         # not the engine
                    conn.sock.shutdown(socket.SHUT_RDWR)
            outs = [np.asarray(f.result(timeout=120.0)) for f in futs]
            for out in outs:
                assert np.array_equal(out.ravel()[:8],
                                      np.arange(1, 9, dtype=np.float32))
            # the kill was observed as failover, not silent loss
            assert router.count("requeued") >= 1
            assert router.count("completed") == 6


# ---------------------------------------------------------------------------
# fallback: JSON-only engines keep working behind a wire router
# ---------------------------------------------------------------------------

def test_json_only_engine_behind_wire_router(monkeypatch):
    """An old engine with no wire listener (MXNET_TPU_WIRE=0 at
    expose) behind a wire-capable router: dispatch falls back to the
    HTTP/JSON long-poll, counted on the fallback counter."""
    from mxnet_tpu.serving.metrics import wire_fallback_counter

    monkeypatch.setenv("MXNET_TPU_WIRE", "0")
    with _engine("legacy") as eng:
        srv = eng.expose(port=0)
        assert eng._wire is None     # no listener started
        monkeypatch.setenv("MXNET_TPU_WIRE", "1")
        router = ServingRouter(poll_interval_s=0.1)
        router.add_engine("legacy", f"http://127.0.0.1:{srv.port}")
        fall = wire_fallback_counter().labels(engine_id="legacy")
        f0 = fall.value
        with router:
            time.sleep(0.3)          # a couple of polls: no wire port
            toks = np.arange(1, 13, dtype=np.int32)
            out = np.asarray(router.submit(toks).result(timeout=60.0))
            assert np.array_equal(out.ravel()[:12],
                                  toks.astype(np.float32))
            assert router.scoreboard()["legacy"]["transport"] == "json"
            assert router.scoreboard()["legacy"]["wire_port"] is None
            assert fall.value == f0 + 1
            # the JSON leg feeds the same overhead axis
            over = router.snapshot()["dispatch_overhead"]
            assert over.get("json", {}).get("count", 0) >= 1


def test_wire_disabled_router_stays_on_json(monkeypatch):
    """ServingRouter(wire=False) never upgrades even when the engine
    advertises a wire port (the bench A/B pin)."""
    with _engine("pin") as eng:
        srv = eng.expose(port=0)
        assert eng._wire is not None
        router = ServingRouter(wire=False, poll_interval_s=0.1)
        router.add_engine("pin", f"http://127.0.0.1:{srv.port}")
        with router:
            time.sleep(0.3)
            out = np.asarray(router.submit(
                np.arange(1, 5, dtype=np.int32)).result(timeout=60.0))
            assert out.ravel()[0] == 1.0
            assert router.scoreboard()["pin"]["transport"] == "json"
            assert router._seats["pin"]._wire is None


# ---------------------------------------------------------------------------
# bounded HTTP fallback pool (the legacy thread-bomb fix)
# ---------------------------------------------------------------------------

def test_fallback_pool_bounds_waiter_threads(monkeypatch):
    """8 concurrent HTTP dispatches against a slow engine run on at
    most MXNET_TPU_WIRE_HTTP_POOL waiter threads — the legacy shape
    spawned 8. Canary pinned off: its probe would trip the model's
    started-event before the 8 dispatches are even queued."""
    monkeypatch.setenv("MXNET_TPU_CANARY", "0")
    monkeypatch.setenv("MXNET_TPU_WIRE_HTTP_POOL", "2")
    slow = SlowModel(0.2)
    with _engine("pool", m=slow, max_rows=2,
                 max_queue_depth=64) as eng:
        srv = eng.expose(port=0)
        router = ServingRouter(wire=False, poll_interval_s=0.2)
        router.add_engine("pool", f"http://127.0.0.1:{srv.port}")
        with router:
            futs = [router.submit(np.arange(1, 6, dtype=np.int32))
                    for _ in range(8)]
            assert slow.started.wait(10.0)
            waiters = [t.name for t in threading.enumerate()
                       if t.name.startswith("mxnet_tpu_router_http_pool")]
            assert 1 <= len(waiters) <= 2, waiters
            for f in futs:
                out = np.asarray(f.result(timeout=120.0))
                assert out.ravel()[0] == 1.0


def test_fallback_pool_unit():
    """Pool mechanics in isolation: lazy spawn up to the bound, FIFO
    drain, close() refuses new jobs but drains queued ones."""
    pool = _FallbackPool("unit", 2)
    gate = threading.Event()
    ran = []

    def job(i):
        gate.wait(10.0)
        ran.append(i)

    import functools
    for i in range(6):
        assert pool.submit(functools.partial(job, i))
    time.sleep(0.1)
    assert pool._threads <= 2
    pool.close()
    assert not pool.submit(lambda: ran.append("late"))
    gate.set()
    deadline = time.monotonic() + 10.0
    while len(ran) < 6 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sorted(ran) == list(range(6))    # queued jobs drained


# ---------------------------------------------------------------------------
# remote-router client failover (tools/serve_loadgen.py --router-url)
# ---------------------------------------------------------------------------

def test_loadgen_router_client_failover():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from serve_loadgen import RouterClient

    with _engine("rc0") as eng:
        router = ServingRouter(engines=[eng], poll_interval_s=0.2)
        with router:
            srv = router.expose(port=0)
            live = f"http://127.0.0.1:{srv.port}"
            # a dead url first: the client fails over and goes sticky
            client = RouterClient(["http://127.0.0.1:1", live],
                                  timeout_s=60.0)
            toks = np.arange(1, 9, dtype=np.int32)
            out = client.submit(toks).result(timeout=60.0)
            assert np.array_equal(out.ravel()[:8],
                                  toks.astype(np.float32))
            assert client.failovers == 1
            fut = client.submit(toks)
            fut.result(timeout=60.0)
            assert client.failovers == 1     # sticky: no re-probe
            assert fut.trace_id and fut.cost
            assert client.scoreboard()       # run_load's router surface
        # router stopped: every url now refuses
        from mxnet_tpu.serving import NoEngineAvailableError
        with pytest.raises(NoEngineAvailableError):
            RouterClient(["http://127.0.0.1:1"]).submit(toks) \
                .result(timeout=5.0)


# ---------------------------------------------------------------------------
# wire-safety: the new module is inside mxlint's enforced scope
# ---------------------------------------------------------------------------

def test_mxlint_wire_safety_covers_wire_module():
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from tools.mxlint.core import Project
    from tools.mxlint.passes.wire_safety import WireSafetyPass

    # the dispatch wire is inside the enforced scope...
    assert WireSafetyPass().applies("mxnet_tpu/serving/wire.py")
    # ...and the shipped module is clean under the REAL pass
    proj = Project(root=root, passes=[WireSafetyPass()])
    findings = proj.lint_path(
        os.path.join(root, "mxnet_tpu", "serving", "wire.py"))
    assert findings == [], findings
    # negative control: an executable decoder in this module WOULD fire
    evil = ("import pickle\n"
            "def decode(raw):\n"
            "    return pickle.loads(raw)\n")
    bad = Project(root=root, passes=[WireSafetyPass()]).lint_source(
        evil, "mxnet_tpu/serving/wire.py")
    assert any(f.rule == "wire-unsafe" for f in bad)
