"""Multi-engine serving front door with a fleet-wide observability plane.

``ServingRouter`` fronts N :class:`~.engine.ServingEngine` seats — the
"one engine per chip, one front door" scale-out shape — and routes
each request to the routable engine with the fewest router-observed
outstanding requests (least-outstanding, the standard L7 balancing
policy for long-tailed request costs). Seats come in two kinds:

- **in-process** engines, registered by handle (``add_engine(id,
  engine)``) and dispatched via ``engine.submit`` directly;
- **remote** engines, registered by the base URL of their
  ``engine.expose()`` endpoint, with per-engine health/stats/metrics/
  traces scraped off that endpoint. Dispatch prefers the BINARY WIRE
  (:mod:`.wire`): when the engine's ``/healthz`` advertises a
  ``wire_port``, the seat keeps a small pool of persistent
  multiplexed connections whose single reader thread per connection
  demuxes replies by correlation id — zero connections, threads or
  ``tokens.tolist()`` round-trips per request. A peer with no wire
  port (an old engine, or ``MXNET_TPU_WIRE=0``) — or a seat whose
  wire connections are momentarily down — falls back to the
  ``POST /submit`` HTTP/JSON long-poll, now driven by a BOUNDED
  per-seat waiter pool instead of a thread per in-flight request.

The observability plane is the point:

1. **Engine-labeled metrics** — every serving family carries an
   ``engine_id`` label (see :mod:`.metrics`); the router's own
   ``/metrics`` serves an AGGREGATED exposition: the local process
   registry unioned with every remote engine's scrape
   (:func:`~mxnet_tpu.telemetry.expo.merge_prometheus_texts`), so one
   Prometheus target sees the whole fleet.
2. **Cross-engine trace aggregation** — ``submit`` opens a
   ``router/request`` root span and propagates ``(trace_id,
   span_id)`` to the chosen engine (directly in-process, as dispatch
   payload fields for remote seats — the same frame-carried crossing
   the dist_async wire uses), so the engine-side
   ``serving/request → queue → pack → forward → complete`` tree
   parents under the router root across processes. The router's
   ``/traces`` and ``/traces/<id>`` merge the per-engine tail-sampled
   rings into one fleet view / one span tree, each span tagged with
   the engine that served it.
3. **Per-engine health scoreboard** — a poll thread folds engine
   heartbeats (``running``/``/healthz``, queue depth, worker-beat
   age, p95, qps) into per-engine gauges and a scoreboard dict; a
   stalled or unreachable engine is marked unroutable (new traffic
   avoids it; its failed dispatches re-queue to siblings), every
   transition emits a ``router_engine_state`` event, and a watchdog
   probe plus a ``router_scoreboard.json`` flight-recorder bundle
   section make a wedged engine self-diagnosing.

4. **Warm restarts** — the poll thread collects each engine's
   visited-shape **warmup manifest** (``/warmup`` /
   ``warmup_manifest()``), keeps the fleet union, and persists it at
   ``MXNET_TPU_WARMUP_MANIFEST`` whenever it grows; a replacement
   engine started with ``warmup(manifest=router.warmup_manifest())``
   (plus the persistent compilation cache,
   :mod:`mxnet_tpu.compile_cache`) replays the fleet's working set
   before ``add_engine`` admits it traffic — rolling restarts serve
   their first real request warm. ``remove_engine`` completes the
   drill.

5. **Fleet cost accounting** — ``/costs`` merges every engine's
   per-bucket cost ledger (device/compile seconds, requests, valid
   tokens; :class:`~.metrics.CostLedger`) into one fleet table with
   per-request / per-1k-token rates, and completed requests carry
   their engine-computed amortized ``future.cost`` through the router
   untouched.

6. **Fleet objectives** — the router runs its own SLO engine
   (:mod:`mxnet_tpu.telemetry.slo` / :mod:`~mxnet_tpu.telemetry.
   alerts`, gate ``MXNET_TPU_SLO``): availability ACROSS failover
   (router outcome counters — a failed-over request that completed on
   a sibling burns no budget), the fleet latency quantile over the
   router-observed end-to-end histogram (with trace-id exemplars on
   slow requests), and the routable-engine fraction off the
   scoreboard. ``/slo`` and ``/alerts`` serve the fleet view: the
   router's own objectives plus every seat's seat-level snapshot
   (local handles read directly, remote seats are scraped), so one
   endpoint answers both "is the fleet healthy" and "which engine is
   burning its budget".

Failover: a dispatch that dies of an ENGINE-SHAPED failure (engine
stopped, queue full, remote transport error) re-queues the request at
the front of the line for a sibling — requests are only lost to
explicit sheds (:class:`NoEngineAvailableError` when every candidate
is down/tried) or their own deadlines, never silently. Model errors
and deadline misses propagate to the caller untouched: retrying a
deterministic failure on every engine would just multiply it.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
import urllib.error
import urllib.request
from collections import OrderedDict, deque
from urllib.parse import urlsplit

import numpy as np

from .. import compile_cache, envvars
from ..retrying import Reconnector
from ..telemetry import attribution as _attribution
from ..telemetry import events as _events
from ..telemetry import incidents as _incidents
from ..telemetry import profiling as _profiling
from ..telemetry import recorder as _recorder
from ..telemetry import spans as _spans
from ..telemetry.registry import REGISTRY as _REGISTRY
from ..telemetry.trace import new_trace_id
from . import tenancy
from .engine import _SUBMIT_ERROR_STATUS, ServingEngine
from .metrics import (DispatchOverhead, LatencySummary, exemplar_gate,
                      merge_cost_buckets, slow_exemplar,
                      wire_bytes_counter, wire_fallback_counter)
from .queue import (DeadlineExceededError, EngineStoppedError,
                    InferenceFuture, QueueFullError, ServingError,
                    UnknownModelError, validate_sampling,
                    validate_tokens)
from .wire import WireClient, WireError

__all__ = ["ServingRouter", "NoEngineAvailableError", "RemoteEngineError"]

_router_seq = itertools.count()
_seat_seq = itertools.count()

# SLO-aware routing-weight hysteresis: a seat enters the DEGRADED
# state (weight tracks its health target) when the target falls to
# _W_ENTER, and returns to full weight only after _W_OK_POLLS
# consecutive polls with the target back above _W_EXIT — weights shed
# smoothly and never flap on a noisy boundary signal.
_W_ENTER = 0.7
_W_EXIT = 0.95
_W_OK_POLLS = 3


class NoEngineAvailableError(ServingError):
    """Shed: no routable engine (fleet down, or failover exhausted
    every candidate for this request)."""


class RemoteEngineError(ServingError):
    """A remote engine endpoint failed at the transport level
    (unreachable, timeout, non-JSON reply)."""


# engine-shaped failures: the request did not fail, the ENGINE did —
# eligible for failover to a sibling
_FAILOVER_ERRORS = (EngineStoppedError, QueueFullError, RemoteEngineError)

# remote /submit error_type -> local exception class (anything unknown
# lands on ServingError so callers still catch the serving error family)
_ERROR_CLASSES = {
    "QueueFullError": QueueFullError,
    "DeadlineExceededError": DeadlineExceededError,
    "EngineStoppedError": EngineStoppedError,
    "UnknownModelError": UnknownModelError,
}


class RouterRequest:
    """One admitted request and its router-side breadcrumbs: the
    minted trace id, the ``router/request`` root span every engine-side
    span ultimately parents under, the engines already tried (failover
    must not ping-pong), and the absolute deadline (failover burns
    wall-clock; the remaining budget shrinks with each attempt)."""

    __slots__ = ("tokens", "token_types", "deadline", "future",
                 "trace_id", "span", "t_submit", "tried", "engine_id",
                 "requeues", "cid", "adopted", "decode", "stream",
                 "parts_seen", "relay_lock", "model_id", "tenant",
                 "tenant_class", "stages", "t_activity")

    def __init__(self, tokens, token_types=None, deadline_ms=None,
                 decode=None, stream=False, model_id=None, tenant=None,
                 tenant_class=None):
        self.tokens, self.token_types = validate_tokens(tokens,
                                                        token_types)
        # tenancy attribution: validated HERE (an unknown class is a
        # ValueError before any counter/journal), carried verbatim on
        # every dispatch payload + the HA journal entry so the serving
        # seat — first pick, failover sibling, peer adoption — bills
        # and WFQ-classes the request identically
        self.model_id = str(model_id) if model_id is not None else None
        self.tenant = str(tenant) if tenant is not None else None
        self.tenant_class = tenancy.normalize_class(tenant_class)
        self.trace_id = new_trace_id("req")
        self.t_submit = time.monotonic()
        self.deadline = (self.t_submit + deadline_ms / 1e3
                         if deadline_ms is not None else None)
        self.span = _spans.start_span(
            "router/request", trace_id=self.trace_id,
            attrs={"tokens": int(self.tokens.size)}, local_root=True)
        self.future = InferenceFuture()
        self.future.trace_id = self.trace_id
        # tried holds seat GENERATION tokens, not engine ids: a
        # replacement seat registered under a reused id is a FRESH
        # failover candidate, not forever poisoned by its predecessor
        self.tried = set()
        self.engine_id = None
        self.requeues = 0
        # HA correlation id: client-provided (resubmit dedupe across
        # routers) or minted from the trace id when journaling
        self.cid = None
        self.adopted = False
        # decode pass-through: generation params riding the dispatch
        # payload unchanged, and the streamed-parts relay state.
        # parts_seen is the next part index the CLIENT has not yet
        # seen: a failover re-run of a (deterministic) decode request
        # replays indices the client already has — the relay drops
        # them, so a killed connection mid-stream loses and duplicates
        # NOTHING
        self.decode = dict(decode) if decode else None
        self.stream = bool(stream)
        self.parts_seen = 0
        self.relay_lock = threading.Lock()
        # router-side stage stamps (dispatch transit, HA-journal ack):
        # the ENGINE's decomposition rides the reply; these feed the
        # router's own /whyslow aggregator
        self.stages = [] if _attribution.enabled() else None
        self.t_activity = None

    def remaining_ms(self, now=None):
        if self.deadline is None:
            return None
        return (self.deadline - (now if now is not None
                                 else time.monotonic())) * 1e3

    def relay_part(self, index, token):
        """Deliver one streamed token to the caller's future, deduped
        by part index (see ``parts_seen`` above). Seats call this from
        their transport threads; a request rides one transport at a
        time, but a FAILOVER's first relays can race a late in-flight
        partial from the old transport's reader — the lock makes the
        dedupe check-and-push atomic so no index delivers twice."""
        if index is None:
            return
        index = int(index)
        with self.relay_lock:
            if index < self.parts_seen:
                return
            self.parts_seen = index + 1
            self.future.push_part({"index": index, "token": token,
                                   "final": False})

    def expired(self, now=None):
        return (self.deadline is not None
                and (now if now is not None else time.monotonic())
                > self.deadline)


class _FallbackPool:
    """Bounded waiter pool for the HTTP/JSON fallback dispatch path.

    The legacy shape spawned one unbounded daemon thread per in-flight
    remote request — a load spike against a slow engine thread-bombed
    the router. Jobs queue here instead; at most
    ``MXNET_TPU_WIRE_HTTP_POOL`` waiters per seat run them, spawned
    lazily only when every existing waiter is busy. ``close()`` lets
    the waiters drain what's queued and exit."""

    def __init__(self, name, size):
        self._name = str(name)
        self._size = max(1, int(size))
        self._dq = deque()
        self._cv = threading.Condition()
        self._threads = 0
        self._idle = 0
        self._closed = False
        self._seq = itertools.count()

    def submit(self, fn):
        """Queue one job; False when the pool is closed (the seat is
        being torn down — the caller resolves the request itself)."""
        with self._cv:
            if self._closed:
                return False
            self._dq.append(fn)
            if self._idle == 0 and self._threads < self._size:
                self._threads += 1
                threading.Thread(
                    target=self._run, daemon=True,
                    name=f"mxnet_tpu_router_http_{self._name}"
                         f"_{next(self._seq)}").start()
            else:
                self._cv.notify()
        return True

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def _run(self):
        while True:
            with self._cv:
                while not self._dq and not self._closed:
                    self._idle += 1
                    self._cv.wait(0.5)
                    self._idle -= 1
                if not self._dq:
                    self._threads -= 1
                    return          # closed and drained
                fn = self._dq.popleft()
            try:
                fn()
            except Exception as e:
                # a job resolves its own request via done(); an escape
                # here is a bug worth a trace, never a dead waiter pool
                _events.emit("router_http_pool_error",
                             pool=self._name, error=repr(e))


class _Seat:
    """One engine behind the router: routing state + scoreboard row."""

    kind = "?"

    def __init__(self, engine_id):
        self.engine_id = str(engine_id)
        # generation token: unique per seat OBJECT, so failover
        # bookkeeping survives a replacement under a reused id
        self.token = f"{self.engine_id}#{next(_seat_seq)}"
        self.outstanding = 0        # router-observed in flight
        self.dispatched = 0
        self.up = True              # optimistic until the first poll
        self.routable = True
        self.closed = False         # removed from the fleet
        self.consecutive_failures = 0
        self.last_change = time.time()
        self.queue_depth = None
        self.p95_ms = None
        self.qps = 0.0
        # hosted models (model_id -> version) learned off the health
        # poll; None = unknown (an old peer that advertises nothing) —
        # treated as hosting anything so mixed fleets keep routing
        self.models = None
        self.last_error = None
        self.last_picked = 0        # round-robin tie-break stamp
        self._prev_completed = None
        self._prev_poll = None
        self._manifest_count = None  # visited shapes at last collect
        # SLO-aware routing weight: 1.0 = full share; a seat burning
        # its error budget / drifting on cost / slow to canaries sheds
        # weight smoothly (poll-thread owned, dispatcher read-only)
        self.weight = 1.0
        self.hys = "healthy"        # healthy | degraded (hysteresis)
        self.ok_polls = 0
        self.burn = None            # last short-window burn rate
        self.cost_rate = None       # EMA windowed device_s/1k tokens
        self._prev_cost = None      # (request_s, valid_tokens)
        self._cost_age = 0          # polls since the EMA last updated
        self._sig_tick = 0          # throttles remote /slo fetches

    def cost_table(self):
        return None

    def row(self):
        return {"kind": self.kind, "up": self.up,
                "routable": self.routable,
                "outstanding": self.outstanding,
                "dispatched": self.dispatched,
                "queue_depth": self.queue_depth,
                "p95_ms": self.p95_ms, "qps": self.qps,
                "models": self.models,
                "weight": round(self.weight, 3),
                "burn": (round(self.burn, 3)
                         if self.burn is not None else None),
                "cost_rate": (round(self.cost_rate, 6)
                              if self.cost_rate is not None else None),
                "manifest_shapes": self._manifest_count,
                "consecutive_failures": self.consecutive_failures,
                "last_change": round(self.last_change, 3),
                "last_error": self.last_error}

    def hosts(self, model_id):
        """True when this seat can serve ``model_id`` (None names the
        seat's default model; a seat whose hosted set is unknown — an
        old peer — routes optimistically and 404s would fail over)."""
        return (model_id is None or self.models is None
                or model_id in self.models)

    def warmup_manifest(self):
        return None

    def slo_snapshot(self):
        """This seat's /slo body (None when the engine has no SLO
        evaluator — MXNET_TPU_SLO=0, or an old peer)."""
        return None

    def alerts_snapshot(self):
        return None

    def whyslow(self):
        """This seat's /whyslow body (None when the engine has no
        stage attribution — MXNET_TPU_ATTRIBUTION=0, or an old
        peer)."""
        return None

    def capture_summary(self):
        """This seat's /capture body (None when the engine has no
        capture store — MXNET_TPU_CAPTURE=0, or an old peer)."""
        return None

    def maintain(self):
        """Poll-thread housekeeping (wire connection upkeep)."""

    def close(self):
        """Release seat-owned transport resources (router stop /
        ``remove_engine``). Sets ``closed`` so a dispatch (or a poll
        ``maintain``) racing the removal fails over instead of driving
        a dead seat — subclasses must call ``super().close()``."""
        self.closed = True


class _LocalSeat(_Seat):
    kind = "local"

    def __init__(self, engine_id, engine):
        super().__init__(engine_id)
        self._engine = engine

    def dispatch(self, req, timeout_s, done):
        if self.closed:
            # picked just as remove_engine() raced in: engine-shaped —
            # the failover requeue hands the request to a sibling
            done(self, req, EngineStoppedError(
                f"engine {self.engine_id} seat was removed"), None)
            return
        submit_payload = getattr(self._engine, "submit_payload", None)
        if submit_payload is not None and (req.decode or req.stream):
            # decode engine: generation params + streaming ride the
            # payload dict (the same shape the wire/HTTP dispatch uses)
            fut, _streamed = submit_payload(dict(
                req.decode or {}, tokens=req.tokens,
                deadline_ms=req.remaining_ms(), stream=req.stream,
                trace_id=req.trace_id, span_id=req.span.span_id,
                model_id=req.model_id, tenant=req.tenant,
                tenant_class=req.tenant_class))
        else:
            fut = self._engine.submit(req.tokens, req.token_types,
                                      deadline_ms=req.remaining_ms(),
                                      trace_id=req.trace_id,
                                      parent_span_id=req.span.span_id,
                                      model_id=req.model_id,
                                      tenant=req.tenant,
                                      tenant_class=req.tenant_class)
        if req.stream:
            fut.add_part_callback(
                lambda _f, part: req.relay_part(part.get("index"),
                                                part.get("token")))

        def _cb(f):
            exc = f.exception(timeout=0)
            done(self, req, exc,
                 None if exc is not None else f.result(timeout=0),
                 cost=f.cost,
                 breakdown=getattr(f, "breakdown", None))

        fut.add_done_callback(_cb)

    def health(self):
        snap = self._engine.snapshot()
        return bool(snap.get("running")), snap

    def warmup_manifest(self):
        try:
            return self._engine.warmup_manifest()
        except Exception:
            return None

    def cost_table(self):
        try:
            return self._engine.cost_table()
        except Exception:
            return None

    def slo_snapshot(self):
        try:
            if self._engine.alerts is None:
                return None
            return self._engine.slo_snapshot()
        except Exception:
            return None

    def alerts_snapshot(self):
        try:
            if self._engine.alerts is None:
                return None
            return self._engine.alerts_snapshot()
        except Exception:
            return None

    def whyslow(self):
        try:
            return self._engine.whyslow()
        except Exception:
            return None

    def capture_summary(self):
        try:
            return self._engine.capture_summary()
        except Exception:
            return None


class _RemoteSeat(_Seat):
    kind = "remote"

    def __init__(self, engine_id, base_url, http_timeout_s=5.0,
                 overhead=None, wire_enabled=None, client_id=None):
        super().__init__(engine_id)
        self.base_url = base_url.rstrip("/")
        self._timeout = http_timeout_s
        self._last_costs = None     # last fetched /costs (see cost_table)
        self._overhead = overhead   # router-shared DispatchOverhead
        self._wire_enabled = (bool(wire_enabled) if wire_enabled
                              is not None
                              else bool(envvars.get("MXNET_TPU_WIRE")))
        self._client_id = str(client_id or f"router-{os.getpid():x}")
        self._wire = None           # WireClient once a port is known
        self._wire_peer = None      # engine id the pool was built for
        self._advertised = (None, None)   # (wire_port, engine_id) @ poll
        self._pool = _FallbackPool(
            self.engine_id, envvars.get("MXNET_TPU_WIRE_HTTP_POOL"))
        byt = wire_bytes_counter()
        self._b_out_json = byt.labels(side="router", transport="json",
                                      direction="out")
        self._b_in_json = byt.labels(side="router", transport="json",
                                     direction="in")
        self._c_fallback = wire_fallback_counter() \
            .labels(engine_id=self.engine_id)

    def _get(self, path, timeout=None):
        with urllib.request.urlopen(
                self.base_url + path,
                timeout=timeout if timeout is not None
                else self._timeout) as r:
            return r.read().decode()

    def row(self):
        out = super().row()
        wire = self._wire
        out["transport"] = ("wire" if wire is not None
                            and wire.has_live() else "json")
        out["wire_port"] = self._advertised[0]
        return out

    # -- binary wire path ---------------------------------------------------
    def maintain(self):
        """Poll-thread housekeeping for the wire transport: (re)open
        persistent connections toward the advertised dispatch port,
        time out unanswered in-flight requests. All blocking connect/
        handshake work lives HERE — the dispatch path only ever queues
        frames on already-live connections."""
        if not self._wire_enabled or self.closed:
            # a poll racing remove_engine() must not resurrect the
            # closed seat's wire pool (a pure leak: the seat can never
            # be picked again)
            return
        port, peer_eid = self._advertised
        wire = self._wire
        if wire is not None and (
                port is None or wire.port != int(port)
                or (peer_eid is not None
                    and self._wire_peer not in (None, peer_eid))):
            # peer downgraded (restarted with MXNET_TPU_WIRE=0), came
            # back on a different port, or a REPLACEMENT engine took
            # the same port under a new id (the old client would pin
            # a stale expect and refuse it forever): rebuild the pool
            self._wire = None
            wire.close()
            wire = None
        if port is None:
            return
        if wire is None:
            host = urlsplit(self.base_url).hostname or "127.0.0.1"
            wire = WireClient(host, int(port),
                              client_id=self._client_id,
                              expect_engine_id=peer_eid)
            self._wire = wire
            self._wire_peer = peer_eid
        wire.ensure()
        wire.sweep()

    def _dispatch_wire(self, wire, req, timeout_s, done):
        # raw typed ndarrays — no tolist()/JSON round trip; trace and
        # span ids ride the frame so the engine-side span tree parents
        # under the router root exactly as it did over HTTP
        payload = {"tokens": req.tokens,
                   "token_types": req.token_types,
                   "deadline_ms": req.remaining_ms(),
                   "trace_id": req.trace_id,
                   "span_id": req.span.span_id,
                   "model_id": req.model_id,
                   "tenant": req.tenant,
                   "tenant_class": req.tenant_class}
        if req.decode:
            payload.update(req.decode)
        if req.stream:
            payload["stream"] = True
        t0 = time.perf_counter()
        t0m = time.monotonic()

        def _on_part(body):
            req.relay_part(body.get("seq"), body.get("token"))

        def _on_wire(exc, body):
            rt_ms = (time.perf_counter() - t0) * 1e3
            if exc is not None:
                # connection died or reply timed out: engine-shaped —
                # the router's failover requeues the request
                done(self, req, RemoteEngineError(
                    f"engine {self.engine_id} wire dispatch failed: "
                    f"{exc}"), None)
                return
            err_type = body.get("error_type")
            if err_type is None:
                engine_ms = body.get("engine_ms")
                if self._overhead is not None and engine_ms is not None:
                    self._overhead.observe("wire",
                                           rt_ms - float(engine_ms))
                # dispatch transit: the whole round trip as one span —
                # the engine's own stage/* children start later, so the
                # innermost-wins extractor bills them their slices and
                # the remainder (serialize + queue + socket) to
                # ``dispatch``
                _attribution.stamp(
                    req, "dispatch", t0m, time.monotonic(),
                    attrs={"transport": "wire",
                           "engine_id": self.engine_id,
                           "engine_ms": engine_ms})
                done(self, req, None, np.asarray(body.get("result")),
                     cost=body.get("cost"),
                     breakdown=body.get("breakdown"))
                return
            if err_type == "WireError":
                # protocol-level refusal from the listener (bad frame
                # shape we somehow sent): transport-shaped
                exc2 = RemoteEngineError(
                    body.get("error")
                    or f"engine {self.engine_id} wire error")
            else:
                cls = _ERROR_CLASSES.get(err_type, ServingError)
                exc2 = cls(body.get("error")
                           or f"engine {self.engine_id} error")
            done(self, req, exc2, None)

        wire.dispatch(payload, _on_wire, timeout_s,
                      on_part=_on_part if req.stream else None)

    # -- dispatch (wire preferred, bounded HTTP/JSON fallback) --------------
    def dispatch(self, req, timeout_s, done):
        if self.closed:
            # removal raced the pick: fail over immediately instead of
            # paying an HTTP timeout against a seat already torn down
            done(self, req, RemoteEngineError(
                f"engine {self.engine_id} seat was removed"), None)
            return
        wire = self._wire
        if wire is not None:
            try:
                self._dispatch_wire(wire, req, timeout_s, done)
                return
            except WireError:
                pass    # no live connection right now: HTTP still works
        if self._wire_enabled:
            # a wire-capable router dispatching over HTTP: the peer has
            # no wire port, or its connections are down — visible so an
            # operator can tell "fast path" from "limping"
            self._c_fallback.inc()
        payload = {"tokens": req.tokens.tolist(),
                   "token_types": (req.token_types.tolist()
                                   if req.token_types is not None
                                   else None),
                   "deadline_ms": req.remaining_ms(),
                   "trace_id": req.trace_id,
                   "span_id": req.span.span_id,
                   "model_id": req.model_id,
                   "tenant": req.tenant,
                   "tenant_class": req.tenant_class,
                   "timeout_s": timeout_s}
        if req.decode:
            payload.update(req.decode)
        if req.stream:
            payload["stream"] = True
        t0 = time.perf_counter()

        # the /submit long-poll blocks for the whole request; a BOUNDED
        # waiter pool keeps the router's dispatch loop free without the
        # legacy thread-per-in-flight-request bomb (in-process seats
        # resolve via callbacks)
        def _run():
            exc = value = cost = breakdown = None
            body = None
            t0m = time.monotonic()
            try:
                data = json.dumps(payload).encode()
                self._b_out_json.inc(len(data))
                http_req = urllib.request.Request(
                    self.base_url + "/submit", data=data,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(
                        http_req, timeout=timeout_s + self._timeout) as r:
                    if req.stream:
                        # chunked JSON lines: one per generated token,
                        # final body last (the decode engine's HTTP
                        # fallback for wire-less routers)
                        body = None
                        for line in r:
                            self._b_in_json.inc(len(line))
                            if not line.strip():
                                continue
                            part = json.loads(line.decode())
                            if part.get("final", True):
                                body = part
                                break
                            req.relay_part(part.get("seq"),
                                           part.get("token"))
                        if body is None:
                            raise RemoteEngineError(
                                f"engine {self.engine_id} stream ended "
                                "without a final body")
                    else:
                        raw = r.read()
                        self._b_in_json.inc(len(raw))
                        body = json.loads(raw.decode())
            except urllib.error.HTTPError as e:
                try:
                    body = json.loads(e.read().decode())
                except Exception:
                    exc = RemoteEngineError(
                        f"engine {self.engine_id}: HTTP {e.code}")
            except Exception as e:
                exc = RemoteEngineError(
                    f"engine {self.engine_id} unreachable: {e!r}")
            if exc is None:
                if body.get("ok"):
                    # decode results are token ids (the engine tags
                    # its reply, covering requests that rode engine
                    # defaults); the encoder path keeps its historical
                    # float JSON round trip
                    value = np.asarray(body["result"],
                                       np.int32 if (req.decode
                                                    or req.stream
                                                    or body.get(
                                                        "decode"))
                                       else np.float32)
                    cost = body.get("cost")
                    breakdown = body.get("breakdown")
                    engine_ms = body.get("engine_ms")
                    if self._overhead is not None \
                            and engine_ms is not None:
                        self._overhead.observe(
                            "json", (time.perf_counter() - t0) * 1e3
                            - float(engine_ms))
                    _attribution.stamp(
                        req, "dispatch", t0m, time.monotonic(),
                        attrs={"transport": "json",
                               "engine_id": self.engine_id,
                               "engine_ms": engine_ms})
                else:
                    cls = _ERROR_CLASSES.get(body.get("error_type"),
                                             ServingError)
                    exc = cls(body.get("error")
                              or f"engine {self.engine_id} error")
            done(self, req, exc, value, cost=cost, breakdown=breakdown)

        if not self._pool.submit(_run):
            done(self, req, RemoteEngineError(
                f"engine {self.engine_id} seat is closed"), None)

    def close(self):
        super().close()
        wire, self._wire = self._wire, None
        if wire is not None:
            wire.close()
        self._pool.close()

    def health(self):
        try:
            hz = json.loads(self._get("/healthz"))
            ok = bool(hz.get("ok"))
            # the advertised dispatch port (and the engine's REAL id —
            # the seat may be registered under an operator alias) feed
            # maintain()'s connection upkeep on this same poll thread
            self._advertised = (hz.get("wire_port"),
                                hz.get("engine_id"))
        except urllib.error.HTTPError as e:
            try:
                hz = json.loads(e.read().decode())
            except Exception:
                hz = {"error": f"HTTP {e.code}"}
            ok = False
        except Exception as e:
            return False, {"error": repr(e)}
        snap = {}
        if ok:
            try:
                snap = json.loads(self._get("/stats"))
            except Exception as e:
                return False, {"error": repr(e)}
        snap.setdefault("queue_depth", hz.get("queue_depth"))
        snap.setdefault("seconds_since_beat", hz.get("seconds_since_beat"))
        return ok, snap

    def metrics_text(self):
        return self._get("/metrics")

    def traces_summary(self):
        try:
            return json.loads(self._get("/traces"))
        except Exception:
            return None

    def get_trace(self, trace_id):
        from urllib.parse import quote
        try:
            return json.loads(
                self._get("/traces/" + quote(trace_id, safe="")))
        except Exception:
            return None

    def warmup_manifest(self):
        try:
            return json.loads(self._get("/warmup"))
        except Exception:
            return None

    def cost_table(self):
        # books are cumulative: a seat that stops answering (died,
        # restarting) must not DROP its billed history from the fleet
        # table, so the last fetched ledger stands in for it
        try:
            self._last_costs = json.loads(self._get("/costs"))
        except Exception:
            return self._last_costs
        return self._last_costs

    def slo_snapshot(self):
        # a 404 body ({"error": "no SLO evaluator"}) parses but is not
        # a snapshot: only objective-bearing replies count
        try:
            snap = json.loads(self._get("/slo"))
        except Exception:
            return None
        return snap if "objectives" in snap else None

    def alerts_snapshot(self):
        try:
            snap = json.loads(self._get("/alerts"))
        except Exception:
            return None
        return snap if "rules" in snap else None

    def incidents_snapshot(self):
        try:
            snap = json.loads(self._get("/incidents"))
        except Exception:
            return None
        return snap if "open" in snap else None

    def whyslow(self):
        # a 404 body ({"error": "no stage attribution"}) parses but is
        # not a snapshot: only stage-bearing replies count
        try:
            snap = json.loads(self._get("/whyslow"))
        except Exception:
            return None
        return snap if "stages" in snap else None

    def capture_summary(self):
        # a 404 body ({"error": "traffic capture disabled"}) parses
        # but is not a summary: only record-bearing replies count
        try:
            snap = json.loads(self._get("/capture"))
        except Exception:
            return None
        return snap if "records_written" in snap else None


class ServingRouter:
    """Least-outstanding front door over N serving engines.

    Parameters
    ----------
    engines : optional initial fleet — a ``{engine_id: target}`` dict
        or an iterable of :class:`ServingEngine` (their own
        ``engine_id`` names the seat); a ``target`` is an engine
        handle (in-process) or an ``http://host:port`` exposition base
        URL (remote).
    max_queue_depth : router admission bound (like the engine's —
        backpressure, never unbounded growth).
    poll_interval_s : health-scoreboard poll period.
    health_fail_after : consecutive failed polls before an engine is
        marked down (dispatch-observed stop/transport errors mark it
        down immediately).
    dispatch_timeout_s : per-attempt cap a remote long-poll waits for
        one engine before the transport gives up.
    """

    COUNTERS = ("submitted", "completed", "failed", "expired",
                "cancelled", "requeued", "shed_queue_full",
                "shed_no_engine", "rejected_stopped", "adopted")

    def __init__(self, engines=None, max_queue_depth=1024,
                 poll_interval_s=1.0, health_fail_after=1,
                 default_deadline_ms=None, dispatch_timeout_s=600.0,
                 router_id=None, wire=None, peer=None):
        self.router_id = (str(router_id) if router_id is not None
                          else f"router-{os.getpid():x}-"
                               f"{next(_router_seq)}")
        # wire=None follows MXNET_TPU_WIRE; False pins every remote
        # seat to the HTTP/JSON path (the bench A/B and the fallback
        # regression test need a JSON-only router on demand)
        self._wire_flag = (bool(wire) if wire is not None
                           else bool(envvars.get("MXNET_TPU_WIRE")))
        # router-observed remote dispatch overhead (round trip minus
        # engine-observed wall) by transport — THE wire-vs-JSON number
        self.dispatch_overhead = DispatchOverhead()
        self._seats = OrderedDict()
        # cost ledgers of seats removed by remove_engine: the fleet
        # /costs books are cumulative, so a rolling-restart drill must
        # not drop the dead engine's billed requests from the table
        self._retired_costs = OrderedDict()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue = deque()
        self._max_queue_depth = int(max_queue_depth)
        self._poll_interval_s = float(poll_interval_s)
        self._fail_after = max(1, int(health_fail_after))
        self._default_deadline_ms = default_deadline_ms
        self._dispatch_timeout_s = float(dispatch_timeout_s)
        self._pending = 0           # admitted, not yet resolved
        self._closed = False
        self._abort = False
        self._started = False
        self._dispatcher = None
        self._poller = None
        self._stop_evt = threading.Event()
        self._expo = None
        self._probe_name = f"serving_router_{id(self):x}"
        # fleet SLO engine (MXNET_TPU_SLO): built in start(), serves
        # /slo + /alerts; exemplar gate shared with the engine via
        # metrics.exemplar_gate/slow_exemplar
        self._slo = None
        # memoized fleet top-stage attribution for alert payloads
        # (ts, rows) — see _whyslow_top
        self._whyslow_top_cache = None
        # black-box canary prober (MXNET_TPU_CANARY): built in
        # start(), probes every seat from outside over wire + HTTP and
        # feeds the per-seat canary-absence page rules
        self._canary = None
        # history scraper (MXNET_TPU_HISTORY): samples the fleet-merged
        # exposition into the retrospective store — built in start()
        self._history = None
        # shadow-diff mirror (MXNET_TPU_SHADOW): mirrors a fraction of
        # completed live traffic at a candidate seat and keeps the
        # /shadow verdict the swap gate consults — built in start();
        # None means no mirror branch in _on_done at all
        self._shadow = None
        self._exemplars = exemplar_gate()
        self._pick_seq = itertools.count(1)
        # SLO-aware routing weights (MXNET_TPU_ROUTER_WEIGHTS): the
        # poll thread folds per-seat burn rate, windowed cost drift
        # and canary latency into a smoothed weight the picker divides
        # outstanding load by — off, every weight stays 1.0 and the
        # pick order is exactly the classic least-outstanding
        self._weights_on = bool(envvars.get("MXNET_TPU_ROUTER_WEIGHTS"))
        self._w_floor = max(1e-3, float(
            envvars.get("MXNET_TPU_ROUTER_WEIGHT_FLOOR")))
        self._w_gain = min(1.0, max(0.01, float(
            envvars.get("MXNET_TPU_ROUTER_WEIGHT_GAIN"))))
        # a seat's burn signal costs a full SLO evaluation (an HTTP
        # /slo GET for remote seats, an evaluator tick+evaluate for
        # local handles): fetch it at most every ~2 s per seat
        # (reusing the last value in between) so default-on weights
        # don't multiply the poll thread's per-tick work
        self._slo_every = max(1, int(round(2.0 / max(
            0.05, float(poll_interval_s)))))
        self._g_weight = _REGISTRY.gauge(
            "mxnet_tpu_router_engine_weight",
            "SLO-aware routing weight per seat (1 = full share; a "
            "seat burning its error budget, drifting on cost or slow "
            "to canaries sheds smoothly)", ("engine_id",))
        # -- router active/active HA ------------------------------------
        # each admitted SUBMIT is journaled (cid + payload) to the
        # peer over the wire; when this router dies, the survivor
        # adopts the orphaned in-flight requests front-of-queue and a
        # client resubmitting the same cid attaches instead of
        # duplicating work
        self._peer_url = (str(peer).rstrip("/") if peer
                          else envvars.get("MXNET_TPU_ROUTER_HA_PEER"))
        if self._peer_url:
            self._peer_url = self._peer_url.rstrip("/")
        self._ha_on = bool(envvars.get("MXNET_TPU_ROUTER_HA"))
        self._ha = None             # inbound journal listener
        self._peer = None           # outbound WireClient to the peer
        self._peer_rid = None
        self._peer_ha_port = None
        self._peer_alive = None     # None unknown / True / False dead
        self._peer_fails = 0
        # backoff gate for the peer /healthz dial: a blackholed peer
        # must not cost the seat-health poll thread a full connect
        # timeout on EVERY tick (same policy the wire reconnects use)
        self._peer_recon = Reconnector()
        self._journal = OrderedDict()    # peer's in-flight: cid->entry
        self._journal_cap = int(envvars.get("MXNET_TPU_ROUTER_HA_JOURNAL"))
        self._ha_ack_s = float(envvars.get("MXNET_TPU_ROUTER_HA_ACK_S"))
        self._live_cids = OrderedDict()  # our in-flight cids -> future
        self._adopted = OrderedDict()    # adopted orphans: cid->future
        self._adopted_cap = 4096
        self._c_ha = None
        self._died = False
        if self._ha_on and self._peer_url:
            self._ha_setup()
        # trace -> engines that served it (bounded): lets the merged
        # /traces summary attribute LOCAL-engine traces too (remote
        # attribution comes from which ring a span was scraped off)
        self._trace_engines = OrderedDict()
        self._trace_engines_cap = 1024

        self._c = {name: 0 for name in self.COUNTERS}
        req_total = _REGISTRY.counter(
            "mxnet_tpu_router_requests_total",
            "router requests by admission/completion outcome", ("event",))
        self._reg_c = {name: req_total.labels(event=name)
                       for name in self.COUNTERS}
        self._c_dispatch = _REGISTRY.counter(
            "mxnet_tpu_router_dispatch_total",
            "requests dispatched, per engine", ("engine_id",))
        self._c_failover = _REGISTRY.counter(
            "mxnet_tpu_router_failover_total",
            "failover requeues, per FAILED engine", ("engine_id",))
        self._g_up = _REGISTRY.gauge(
            "mxnet_tpu_router_engine_up",
            "1 when the engine is routable, else 0", ("engine_id",))
        self._g_queue_depth = _REGISTRY.gauge(
            "mxnet_tpu_router_engine_queue_depth",
            "engine-reported admission-queue depth at last poll",
            ("engine_id",))
        self._g_inflight = _REGISTRY.gauge(
            "mxnet_tpu_router_engine_inflight",
            "router-observed in-flight requests, per engine",
            ("engine_id",))
        self._g_fleet = _REGISTRY.gauge(
            "mxnet_tpu_router_engines_up", "routable engines")
        self._c_scrape_err = _REGISTRY.counter(
            "mxnet_tpu_router_scrape_errors_total",
            "remote-engine scrape failures at the aggregated /metrics",
            ("engine_id",))
        # fleet-union warmup manifest: the poll thread folds every
        # live engine's visited-shape manifest in here and persists
        # the union at MXNET_TPU_WARMUP_MANIFEST so a restarting
        # engine can replay the fleet's working set (warm restart)
        self._fleet_manifest = None
        self._g_manifest = _REGISTRY.gauge(
            "mxnet_tpu_router_warmup_manifest_shapes",
            "shape buckets in the fleet-union warmup manifest")
        self.total_ms = LatencySummary(
            4096, _REGISTRY.histogram(
                "mxnet_tpu_router_latency_ms",
                "router-observed end-to-end latency", ("stage",))
            .labels(stage="total"))

        if engines:
            items = (engines.items() if isinstance(engines, dict)
                     else ((getattr(e, "engine_id", None), e)
                           for e in engines))
            for eid, target in items:
                self.add_engine(eid, target)

    # -- fleet membership --------------------------------------------------
    def add_engine(self, engine_id, target):
        """Register one engine seat: an in-process
        :class:`ServingEngine` handle, or the base URL string of a
        remote engine's ``expose()`` endpoint."""
        if isinstance(target, str):
            seat = _RemoteSeat(engine_id or target, target,
                               overhead=self.dispatch_overhead,
                               wire_enabled=self._wire_flag,
                               client_id=self.router_id)
        elif isinstance(target, ServingEngine) or hasattr(target, "submit"):
            seat = _LocalSeat(
                engine_id if engine_id is not None
                else getattr(target, "engine_id", None), target)
        else:
            raise TypeError(f"engine target {target!r} is neither a "
                            "ServingEngine nor an exposition URL")
        with self._lock:
            if seat.engine_id in self._seats:
                raise ValueError(
                    f"engine id {seat.engine_id!r} already registered")
            self._seats[seat.engine_id] = seat
            self._g_up.labels(engine_id=seat.engine_id).set(1)
            self._g_weight.labels(engine_id=seat.engine_id).set(1.0)
            self._g_inflight.labels(engine_id=seat.engine_id) \
                .set_function(lambda s=seat: s.outstanding)
        _events.emit("router_engine_added", router_id=self.router_id,
                     engine_id=seat.engine_id, kind=seat.kind)
        return self

    def remove_engine(self, engine_id):
        """Deregister one seat (the rolling-restart drill: remove the
        dead engine, then ``add_engine`` its warmed replacement under
        the same id). In-flight dispatches to it resolve through the
        normal failover path; new traffic stops immediately."""
        engine_id = str(engine_id)
        with self._lock:
            seat = self._seats.pop(engine_id, None)
            if seat is None:
                raise KeyError(f"engine id {engine_id!r} not registered")
            # closed is visible to a dispatcher that picked this seat
            # BEFORE the pop: its dispatch fails over immediately (and
            # the poll thread's maintain() stops touching the seat)
            # instead of erroring the request against a dead target
            seat.closed = True
            self._g_up.labels(engine_id=engine_id).set(0)
            self._g_weight.labels(engine_id=engine_id).set(0)
            self._g_inflight.labels(engine_id=engine_id).set(0)
            self._g_queue_depth.labels(engine_id=engine_id).set(0)
        # snapshot the departing seat's cumulative cost ledger OUTSIDE
        # the lock (remote seats scrape /costs) so the fleet books keep
        # every request it ever billed
        table = seat.cost_table()
        if table is not None:
            with self._lock:
                self._retired_costs[engine_id] = table
        # then drop its transport: closing the wire pool fails its
        # in-flight dispatches with WireError → failover requeues them
        # to siblings (the rolling-restart drill's zero-loss contract)
        seat.close()
        _events.emit("router_engine_removed", router_id=self.router_id,
                     engine_id=engine_id, kind=seat.kind)
        # release any incident hold on this seat: a seat that LEFT the
        # fleet must not pin an incident open forever (its replacement
        # starts up without a down→up transition) — same contract as
        # AlertDaemon.remove_rule's final resolved
        _events.emit("router_engine_state", router_id=self.router_id,
                     engine_id=engine_id, state="removed",
                     reason="remove_engine")
        return self

    def engine_ids(self):
        with self._lock:
            return list(self._seats)

    def engine_handle(self, engine_id):
        """The in-process engine behind a seat (None for remote seats
        or unknown ids) — the autoscaler uses it to stop a replaced
        incarnation it didn't spawn itself."""
        with self._lock:
            seat = self._seats.get(str(engine_id))
        return seat._engine if isinstance(seat, _LocalSeat) else None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        with self._lock:
            if self._started:
                return self
            if self._closed:
                raise EngineStoppedError("router cannot be restarted")
            if not self._seats:
                raise ValueError("router has no engines; add_engine first")
            self._started = True
            self._stop_evt.clear()
            self._dispatcher = threading.Thread(
                target=self._run_dispatch, daemon=True,
                name="mxnet_tpu_router_dispatch")
            self._poller = threading.Thread(
                target=self._run_poll, daemon=True,
                name="mxnet_tpu_router_health")
        # the router is a serving front door: it explains its own
        # death the same way an engine does (probe + bundle section),
        # and its bundle carries the FLEET scoreboard
        _recorder.install()
        _recorder.register_probe(self._probe_name, self._watchdog_probe)
        _recorder.add_bundle_section("router_scoreboard", self.snapshot)
        _profiling.ensure_started()
        _incidents.install()
        # fleet objectives: availability across failover, fleet
        # latency quantile, routable-engine fraction — judged by the
        # same burn-rate machinery every engine runs on itself
        if envvars.get("MXNET_TPU_SLO"):
            from ..telemetry.alerts import (AlertDaemon, default_burn_rules,
                                            default_router_objectives)
            from ..telemetry.slo import SloEvaluator
            evaluator = SloEvaluator(self.router_id)
            names = default_router_objectives(evaluator, self)
            self._slo = AlertDaemon(evaluator)
            # fleet "why slow" on the fleet page: the router's own
            # aggregator only sees dispatch/ha_ack, so a firing
            # fleet_latency payload attaches the MERGED top stages
            # (short TTL cache — /alerts renders every rule's payload
            # and must not re-scrape every seat per rule)
            self._slo.attribution_fn = self._whyslow_top
            default_burn_rules(self._slo, names)
            self._slo.start()
        # black-box monitoring: the canary prober serves the product
        # path from OUTSIDE each seat (wire + HTTP round-robined) and
        # declares the per-seat canary-absence page rule on the fleet
        # daemon — a wedged engine pages even with a green /healthz
        if envvars.get("MXNET_TPU_CANARY"):
            from ..telemetry.canary import CanaryProber
            self._canary = CanaryProber(self._canary_targets,
                                        owner_id=self.router_id,
                                        alerts=self._slo)
            self._canary.start()
        # retrospective history: the router's scraper samples the
        # fleet-MERGED exposition (this registry + every routable
        # remote seat), so one /query_range answers for the fleet
        if envvars.get("MXNET_TPU_HISTORY"):
            from ..telemetry.history import HistoryScraper
            self._history = HistoryScraper(
                self.router_id, text_fn=self.metrics_text,
                slo_fn=(self.slo_snapshot if self._slo is not None
                        else None),
                alerts_fn=(self.alerts_snapshot
                           if self._slo is not None else None)).start()
        # shadow-diff validation (MXNET_TPU_SHADOW): the mirror is
        # built DISARMED — set_shadow_target() arms it at a candidate.
        # Off (the default) this is one env read: no mirror branch in
        # the completion path, no mxnet_tpu_shadow_* families
        if envvars.get("MXNET_TPU_SHADOW"):
            from .shadow import ShadowMirror
            self._shadow = ShadowMirror(self.router_id)
        # chaos harness (MXNET_TPU_CHAOS): register as a fault target
        # (kill_router / kill_wire) — one env read when off
        if envvars.get("MXNET_TPU_CHAOS"):
            from .chaos import register_router as _chaos_register
            _chaos_register(self)
        self._poll_once()           # scoreboard fresh before traffic
        self._dispatcher.start()
        self._poller.start()
        _events.emit("router_start", router_id=self.router_id,
                     engines=self.engine_ids())
        return self

    def stop(self, drain=True, timeout=None):
        """Shut the router down (engines are NOT stopped — the router
        fronts them, it doesn't own them). ``drain=True`` waits for
        every admitted request to resolve; ``drain=False`` fails
        undispatched requests with :class:`EngineStoppedError`."""
        if self._died:
            return      # die() already tore everything down abruptly
        _events.emit("router_stop", router_id=self.router_id, drain=drain)
        with self._cond:
            already = self._closed
            self._closed = True
            if not drain:
                self._abort = True
            stranded = []
            if not drain:
                stranded = list(self._queue)
                self._queue.clear()
            self._cond.notify_all()
        for req in stranded:
            self._finish(req, EngineStoppedError(
                "router stopped before request was dispatched"),
                "cancelled")
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        timed_out = False
        if drain:
            with self._cond:
                while self._pending > 0:
                    remaining = (None if deadline is None
                                 else deadline - time.monotonic())
                    if remaining is not None and remaining <= 0:
                        timed_out = True
                        break
                    self._cond.wait(0.2 if remaining is None
                                    else min(0.2, remaining))
        self._stop_evt.set()
        for t in (self._dispatcher, self._poller):
            if t is not None:
                t.join(timeout=5.0)
        if not already:
            _recorder.unregister_probe(self._probe_name)
            _recorder.remove_bundle_section("router_scoreboard")
            if self._canary is not None:
                self._canary.stop()
            if self._slo is not None:
                self._slo.stop()
            if self._history is not None:
                self._history.stop()
            if self._shadow is not None:
                self._shadow.close()
        with self._lock:
            expo, self._expo = self._expo, None
            ha, self._ha = self._ha, None
            peer, self._peer = self._peer, None
            seats = list(self._seats.values())
        if expo is not None:
            expo.close()
        if ha is not None:
            ha.close()
        if peer is not None:
            peer.close()
        # transports are router-owned even though the engines aren't:
        # drop the persistent wire pools and HTTP waiter pools
        for seat in seats:
            seat.close()
        if timed_out:
            raise ServingError("router did not drain in time")

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=True)
        return False

    @property
    def running(self):
        with self._lock:
            return (self._started and not self._closed
                    and self._dispatcher is not None
                    and self._dispatcher.is_alive())

    # -- client surface ----------------------------------------------------
    def submit(self, tokens, token_types=None, deadline_ms=None,
               cid=None, max_new_tokens=None, eos_id=None,
               stream=False, temperature=None, top_k=None, top_p=None,
               seed=None, model_id=None, tenant=None,
               tenant_class=None):
        """Admit one request; returns an :class:`InferenceFuture`
        whose ``trace_id`` names the request fleet-wide. Sheds loudly:
        :class:`QueueFullError` (router queue at bound),
        :class:`NoEngineAvailableError` (no routable engine),
        :class:`EngineStoppedError` (router not running).

        ``cid`` is the HA correlation id: a client resubmitting the
        same cid (after its first router died mid-request) ATTACHES to
        the already-adopted/live request instead of duplicating work.
        With an HA peer configured, every admitted request is
        journaled (cid + payload) to the peer before it becomes
        dispatchable, so a router death orphans nothing.

        ``max_new_tokens``/``eos_id``/``stream`` are the DECODE
        pass-through (seats fronting a :class:`~.decode.DecodeEngine`):
        generation params ride the dispatch payload unchanged, and
        with ``stream=True`` the returned future's :meth:`~.queue.
        InferenceFuture.stream` yields each generated token as the
        engine produces it — over the wire as partial RESULT frames,
        over HTTP as chunked JSON lines, in-process as direct part
        relays, deduped by index across failover.

        ``temperature``/``top_k``/``top_p``/``seed`` select seeded
        sampling on the serving seat (validated HERE, the typed
        :class:`~.queue.InvalidSamplingError` before any journaling or
        dispatch). A sampled request with no seed gets one MINTED at
        admission — the seed then rides the dispatch payload and the
        HA journal entry, so a failover re-dispatch (this router's
        retry or the peer's adoption) resamples the identical tokens
        and the stream dedupe stays byte-exact.

        ``model_id`` routes the request to a seat advertising that
        hosted model (None = each seat's default); ``tenant``/
        ``tenant_class`` attribute it to an owner and its WFQ
        admission class on the serving seat. All three ride every
        dispatch payload and the HA journal, so failover and peer
        adoption preserve the attribution."""
        if deadline_ms is None:
            deadline_ms = self._default_deadline_ms
        if cid is not None and self._c_ha is not None:
            existing = self._ha_lookup(str(cid))
            if existing is not None:
                return existing
        temperature, top_k, top_p, seed = validate_sampling(
            temperature, top_k, top_p, seed)
        decode = {}
        if max_new_tokens is not None:
            decode["max_new_tokens"] = int(max_new_tokens)
        if eos_id is not None:
            decode["eos_id"] = int(eos_id)
        if temperature is not None:
            decode["temperature"] = temperature
            if seed is None and temperature > 0:
                # mint the replay seed at the ROUTER so every
                # dispatch of this request — first try, retry on a
                # dead seat, HA-peer adoption — samples identically
                seed = int.from_bytes(os.urandom(4),
                                      "little") & 0x7FFFFFFF
        if top_k is not None:
            decode["top_k"] = top_k
        if top_p is not None:
            decode["top_p"] = top_p
        if seed is not None:
            decode["seed"] = seed
        # validate FIRST (same invariant as the engine: submitted ==
        # sum of outcome counters, malformed requests touch nothing)
        req = RouterRequest(tokens, token_types, deadline_ms,
                            decode=decode or None, stream=stream,
                            model_id=model_id, tenant=tenant,
                            tenant_class=tenant_class)
        self._bump("submitted")
        # journal only requests that LOOK admittable: shedding must
        # stay cheap under overload (no peer round trip per refusal).
        # The authoritative admission check re-runs after journaling;
        # if the queue drained in between (pre-check refused, final
        # check would admit an UNJOURNALED request), go around once
        # more so every admitted request really is journaled — the
        # second lap journals unconditionally.
        for lap in range(2):
            if (self._c_ha is not None and req.cid is None
                    and (lap > 0
                         or self._refusal_peek() is None)):
                req.cid = str(cid) if cid is not None else req.trace_id
                # journal BEFORE the request can be dispatched: the
                # ack wait (bounded) is the durability cost of the
                # zero-loss contract; a missing/slow peer degrades to
                # unjournaled
                self._ha_journal(req)
            # decide under the lock, account/raise OUTSIDE it
            # (self._cond shares self._lock, which _bump needs —
            # non-reentrant)
            with self._cond:
                refusal = self._refusal_locked()
                if (refusal is None and self._c_ha is not None
                        and req.cid is None):
                    continue        # drained mid-flight: journal first
                if refusal is None:
                    self._queue.append(req)
                    self._pending += 1
                    if req.cid is not None:
                        self._live_cids[req.cid] = req.future
                        while len(self._live_cids) > self._adopted_cap:
                            self._live_cids.popitem(last=False)
                    self._cond.notify()
            break
        if refusal is None:
            return req.future
        # refused after journaling: release, or the peer would adopt
        # (and execute) a request this router never accepted
        self._ha_release(req)
        if refusal == "stopped":
            self._bump("rejected_stopped")
            req.span.end(error="rejected: router not running")
            raise EngineStoppedError("serving router is not running")
        _events.emit("router_shed", reason=refusal,
                     router_id=self.router_id, trace_id=req.trace_id)
        # shed traces are tail-sampling KEEPs by contract, same as the
        # engine's: the operator debugging overload wants exactly these
        req.span.set_attr(shed=refusal).force_keep() \
           .end(error=f"shed: {refusal}")
        if refusal == "no_engine":
            self._bump("shed_no_engine")
            raise NoEngineAvailableError("no routable engine (fleet down)")
        self._bump("shed_queue_full")
        raise QueueFullError(
            f"router queue full (depth {self._max_queue_depth})")

    def _refusal_locked(self):
        """The admission decision (caller holds ``_lock``): None =
        admittable, else the refusal reason."""
        if not self._started or self._closed:
            return "stopped"
        if not any(s.routable for s in self._seats.values()):
            return "no_engine"
        if len(self._queue) >= self._max_queue_depth:
            return "queue_full"
        return None

    def _refusal_peek(self):
        """Advisory admission look (takes and releases the lock) —
        the cheap pre-check that keeps sheds from paying peer I/O."""
        with self._lock:
            return self._refusal_locked()

    def infer(self, tokens, token_types=None, deadline_ms=None,
              timeout=None):
        return self.submit(tokens, token_types, deadline_ms).result(timeout)

    # -- dispatch ----------------------------------------------------------
    def _run_dispatch(self):
        while True:
            with self._cond:
                while not self._queue and not self._exit_locked():
                    self._cond.wait(0.2)
                if not self._queue:
                    if self._exit_locked():
                        return
                    continue
                req = self._queue.popleft()
                seat = None
                if not req.expired():
                    seat = self._pick_locked(req.tried, req.model_id)
                    if seat is not None:
                        seat.outstanding += 1
                        seat.dispatched += 1
            if seat is None:
                if req.expired():
                    self._finish(req, DeadlineExceededError(
                        f"request {req.trace_id} deadline exceeded "
                        "before dispatch"), "expired")
                else:
                    # failover exhausted or fleet down: an explicit
                    # shed, never a silent drop
                    self._bump_shed_no_engine(req)
                continue
            # a deadline that lapsed since the in-lock check still
            # dispatches: the engine re-checks at drain, and the
            # picked seat's outstanding count must balance its _on_done
            req.engine_id = seat.engine_id
            self._c_dispatch.labels(engine_id=seat.engine_id).inc()
            self._note_trace_engine(req.trace_id, seat.engine_id)
            try:
                seat.dispatch(req, self._dispatch_timeout_s,
                              self._on_done)
            except Exception as e:  # sync admission failure (queue
                # full, stopped) funnels through the same completion
                # path so failover/accounting stay uniform
                self._on_done(seat, req, e, None)

    def _exit_locked(self):
        return self._closed and (self._abort or self._pending == 0)

    def _pick_locked(self, exclude, model_id=None):
        # WEIGHTED least outstanding: score = (outstanding + 1) /
        # weight, ties break round-robin (least recently picked). With
        # every weight at 1.0 (weights off, or a healthy fleet) the
        # order is exactly the classic least-outstanding; a seat shed
        # to weight w gets ~w of a full share under load and only
        # overflow traffic when idle. A request naming a model only
        # considers seats advertising it (unknown hosted sets route
        # optimistically — a 404 there is typed and propagates).
        best = best_score = None
        for seat in self._seats.values():
            if not seat.routable or seat.token in exclude \
                    or not seat.hosts(model_id):
                continue
            score = ((seat.outstanding + 1.0)
                     / max(seat.weight, self._w_floor))
            if best is None or (score, seat.last_picked) \
                    < (best_score, best.last_picked):
                best, best_score = seat, score
        if best is not None:
            best.last_picked = next(self._pick_seq)
        return best

    def _bump_shed_no_engine(self, req):
        self._bump("shed_no_engine")
        _events.emit("router_shed", reason="no_engine",
                     router_id=self.router_id, trace_id=req.trace_id,
                     tried=sorted(req.tried))
        req.span.set_attr(shed="no_engine")
        self._finish(req, NoEngineAvailableError(
            "no routable engine"
            + (f" (tried {sorted(req.tried)})" if req.tried else "")),
            None, force_keep=True)

    def _on_done(self, seat, req, exc, value, cost=None,
                 breakdown=None):
        with self._lock:
            seat.outstanding = max(0, seat.outstanding - 1)
        if exc is None:
            self._bump("completed")
            total_ms = (time.monotonic() - req.t_submit) * 1e3
            # exemplar on the fleet latency histogram: links a firing
            # fleet_latency alert to a retrievable cross-engine trace
            self.total_ms.observe(
                total_ms, exemplar=slow_exemplar(
                    req.trace_id, total_ms, self._exemplars))
            req.span.set_attr(engine=req.engine_id,
                              requeues=req.requeues).end()
            if cost is not None:
                # the engine's amortized bill rides through to the
                # router's caller (remote seats carry it in the
                # /submit body) so cost attribution survives fronting
                req.future.cost = cost
            if breakdown is not None:
                # the ENGINE's critical-path decomposition, relayed
                # verbatim (wire and HTTP seats carry it in the reply
                # body, local seats on the future) — the caller sees
                # the same breakdown it would have engine-direct
                req.future.breakdown = breakdown
            self._observe_router_stages(req, total_ms)
            req.future.set_result(value)
            # shadow-diff mirror: strictly AFTER the live future has
            # resolved — fire-and-forget at the candidate seat; the
            # live caller never waits on (or sees) the shadow leg
            if self._shadow is not None:
                try:
                    self._shadow.mirror(req, value, total_ms)
                except Exception as e:
                    _events.emit("shadow_mirror_error",
                                 router_id=self.router_id,
                                 trace_id=req.trace_id, error=repr(e))
            self._ha_release(req)
            self._resolve()
            return
        if isinstance(exc, _FAILOVER_ERRORS) and not req.expired():
            # the ENGINE failed, not the request: unroutable-on-death
            # + re-queue at the front for a sibling. The queue insert
            # and the abort check share one critical section — an
            # abort stop() racing in here must not strand the request
            # in a queue whose dispatcher already exited.
            if isinstance(exc, (EngineStoppedError, RemoteEngineError)) \
                    and not seat.closed:
                # a REMOVED seat's failures must not touch the gauges
                # of a replacement registered under the same id
                self._mark(seat, up=False,
                           reason=f"dispatch: {type(exc).__name__}")
                seat.last_error = repr(exc)
            with self._cond:
                requeued = not self._abort
                if requeued:
                    # tried must grow BEFORE the dispatcher can re-pop
                    # the request, or it may re-pick this same seat
                    # (generation tokens: a same-id REPLACEMENT seat
                    # stays a fresh candidate)
                    req.requeues += 1
                    req.tried.add(seat.token)
                    self._queue.appendleft(req)
                    self._cond.notify()
            if requeued:
                self._bump("requeued")
                self._c_failover.labels(engine_id=seat.engine_id).inc()
                _events.emit("router_failover", router_id=self.router_id,
                             trace_id=req.trace_id,
                             from_engine=seat.engine_id,
                             error=repr(exc), requeues=req.requeues)
                return
        if isinstance(exc, DeadlineExceededError):
            counter = "expired"
        elif isinstance(exc, EngineStoppedError):
            counter = "cancelled"
        else:
            counter = "failed"
        self._finish(req, exc, counter)

    def _finish(self, req, exc, counter, force_keep=False):
        if counter is not None:
            self._bump(counter)
        if force_keep:
            req.span.force_keep()
        req.span.end(error=repr(exc))
        req.future.set_exception(exc)
        self._ha_release(req)
        self._resolve()

    def _observe_router_stages(self, req, total_ms):
        """Feed the ROUTER-owned stages (dispatch transit, HA-journal
        ack) into this router's /whyslow aggregator. Only the stages
        the router itself timed are billed here — the engine's own
        decomposition aggregates engine-side and reaches the fleet
        view through the /whyslow merge, so nothing double-counts."""
        if not req.stages:
            return
        per = {}
        for name, a, b in req.stages:
            if name in ("dispatch", "ha_ack"):
                per[name] = per.get(name, 0.0) + (b - a)
        if not per:
            return
        rb = {"wall_ms": total_ms, "trace_id": req.trace_id,
              "stages": [{"stage": s, "ms": round(v * 1e3, 3),
                          "share": (round(v * 1e3 / total_ms, 4)
                                    if total_ms > 0 else 0.0)}
                         for s, v in per.items()],
              "unattributed_ms": 0.0}
        _attribution.aggregator(self.router_id).observe(
            rb, tenant_class=req.tenant_class, model=req.model_id,
            trace_id=req.trace_id)

    def _resolve(self):
        with self._cond:
            self._pending -= 1
            self._cond.notify_all()

    def _bump(self, name, n=1):
        with self._lock:
            self._c[name] += n
        self._reg_c[name].inc(n)

    def count(self, name):
        with self._lock:
            return self._c[name]

    def _note_trace_engine(self, trace_id, engine_id):
        with self._lock:
            ids = self._trace_engines.setdefault(trace_id, [])
            if engine_id not in ids:
                ids.append(engine_id)
            self._trace_engines.move_to_end(trace_id)
            while len(self._trace_engines) > self._trace_engines_cap:
                self._trace_engines.popitem(last=False)

    # -- health scoreboard -------------------------------------------------
    def _run_poll(self):
        while not self._stop_evt.wait(self._poll_interval_s):
            try:
                self._poll_once()
            except Exception as e:
                # a poll failure must not kill routing, but a silent
                # one hides a scoreboard gone stale — leave a trace
                _events.emit("router_poll_error",
                             router_id=self.router_id, error=repr(e))

    def _poll_once(self):
        now = time.monotonic()
        with self._lock:
            seats = list(self._seats.values())
        up_count = 0
        signals = {}
        for seat in seats:
            try:
                ok, snap = seat.health()
            except Exception as e:
                ok, snap = False, {"error": repr(e)}
            beat_age = snap.get("seconds_since_beat")
            allowed = _recorder.stall_seconds()
            if snap.get("compiling"):
                # an open first-visit compile window widens the
                # allowance by the SAME finite grace as the engine's
                # own watchdog — tens-of-seconds compiles are
                # progress, but a compile outliving even the grace is
                # a wedge and must not stay routable forever
                allowed += envvars.get(
                    "MXNET_TPU_WATCHDOG_COMPILE_GRACE_S")
            if ok and beat_age is not None and beat_age > allowed \
                    and (snap.get("queue_depth") or 0) > 0:
                # alive but WEDGED: the worker loop stopped beating
                # with work queued — unroutable, same as unreachable
                ok = False
                snap = dict(snap, error=f"stalled: worker beat "
                            f"{beat_age:.1f}s old with queued work")
            if ok:
                mcount = snap.get("manifest_shapes")
                if mcount is not None \
                        and mcount != seat._manifest_count:
                    # visited-shape set changed since the last collect:
                    # pull the engine's manifest and fold it into the
                    # fleet union (persisted for warm restarts). A
                    # failing collect must not abort the poll round —
                    # the remaining seats still need health updates.
                    try:
                        m = seat.warmup_manifest()
                        if m is not None:
                            seat._manifest_count = mcount
                            self._fold_manifest(m)
                    except Exception as e:
                        _events.emit("router_manifest_error",
                                     router_id=self.router_id,
                                     engine_id=seat.engine_id,
                                     error=repr(e))
            if ok:
                seat.consecutive_failures = 0
                seat.queue_depth = snap.get("queue_depth")
                models = snap.get("models")
                if isinstance(models, dict):
                    # the hosted-model advertisement: feeds the
                    # model-aware pick and the canary's version
                    # fingerprint (a hot-swap re-TOFUs the golden)
                    seat.models = dict(models)
                lat = (snap.get("latency") or {}).get("total") or {}
                seat.p95_ms = lat.get("p95_ms")
                completed = (snap.get("counters") or {}).get("completed")
                if (completed is not None
                        and seat._prev_completed is not None
                        and seat._prev_poll is not None
                        and now > seat._prev_poll):
                    seat.qps = max(0.0, round(
                        (completed - seat._prev_completed)
                        / (now - seat._prev_poll), 2))
                seat._prev_completed = completed
                seat._prev_poll = now
                self._mark(seat, up=True)
                if self._weights_on:
                    signals[seat] = self._seat_signals(seat, snap)
            else:
                seat.consecutive_failures += 1
                seat.last_error = snap.get("error") or "health check failed"
                if seat.consecutive_failures >= self._fail_after:
                    self._mark(seat, up=False, reason=seat.last_error)
            self._g_queue_depth.labels(engine_id=seat.engine_id) \
                .set(seat.queue_depth or 0)
            if seat.routable:
                up_count += 1
            try:
                # wire upkeep rides the same poll cadence: blocking
                # connect/handshake + in-flight timeout sweep happen
                # HERE so the dispatch path never blocks on either
                seat.maintain()
            except Exception as e:
                _events.emit("router_wire_maintain_error",
                             router_id=self.router_id,
                             engine_id=seat.engine_id, error=repr(e))
        if self._weights_on:
            self._update_weights(signals)
        self._g_fleet.set(up_count)
        # the shadow mirror's wire connection rides the same poll
        # cadence as the seats' — blocking connect work stays here,
        # never on the dispatch or completion paths
        if self._shadow is not None:
            try:
                self._shadow.maintain()
            except Exception as e:
                _events.emit("shadow_maintain_error",
                             router_id=self.router_id, error=repr(e))
        self._maintain_peer()

    # -- SLO-aware routing weights (poll thread) ---------------------------
    def _seat_signals(self, seat, snap):
        """One seat's health signals for the weight fold: the max
        short-window burn rate over its ratio objectives (``/slo``),
        the poll-windowed device_s/1k-tokens EMA off the ``/stats``
        cost totals, and the canary probe latency EMA. Poll thread
        only."""
        fetch = seat._sig_tick % self._slo_every == 0
        seat._sig_tick += 1
        if fetch:
            from ..telemetry.slo import max_short_burn
            try:
                slo = seat.slo_snapshot()
            except Exception:
                slo = None
            seat.burn = burn = max_short_burn(slo)
        else:
            burn = seat.burn        # throttled: reuse the last fetch
        costs = snap.get("costs") or {}
        cur = (costs.get("request_s"), costs.get("valid_tokens"))
        prev = seat._prev_cost
        seat._prev_cost = cur
        if (prev is not None and None not in cur
                and None not in prev and cur[1] - prev[1] > 0):
            inst = (cur[0] - prev[0]) * 1e3 / (cur[1] - prev[1])
            if inst >= 0:
                seat.cost_rate = (inst if seat.cost_rate is None
                                  else 0.5 * seat.cost_rate
                                  + 0.5 * inst)
                seat._cost_age = 0
        else:
            # no fresh tokens this poll: the EMA is aging. A shed
            # seat stops receiving traffic, so a stale-high cost
            # reading must EXPIRE or it would pin the penalty (and
            # the floor weight) forever — no data is no signal,
            # exactly like a burn rate over an empty window
            seat._cost_age += 1
        cost = seat.cost_rate if seat._cost_age <= 5 else None
        canary = self._canary
        lat = (canary.latency_ms(seat.engine_id)
               if canary is not None else None)
        return {"burn": burn, "cost": cost, "canary": lat}

    def _update_weights(self, signals):
        """Fold each healthy seat's signals into its routing weight.
        Burn rate is judged absolutely (1x is sustainable, the page
        factor 14.4x is a full shed); cost and canary latency are
        judged RELATIVE to the median of the other seats (a uniform
        slowdown is capacity, not a hot-spot)."""
        def _others_median(key, me):
            xs = sorted(v[key] for s, v in signals.items()
                        if s is not me and v.get(key) is not None)
            return xs[len(xs) // 2] if xs else None

        for seat, v in signals.items():
            penalty = 0.0
            burn = v.get("burn")
            if burn is not None and burn > 1.0:
                penalty = max(penalty, min(1.0, (burn - 1.0) / 13.4))
            for key in ("cost", "canary"):
                mine = v.get(key)
                ref = _others_median(key, seat)
                if mine is None or ref is None or ref <= 0:
                    continue
                ratio = mine / ref
                if ratio > 1.25:
                    # 25% over the fleet is noise; 3x is a full shed
                    penalty = max(penalty,
                                  min(1.0, (ratio - 1.25) / 1.75))
            self._step_weight(seat,
                              max(self._w_floor, 1.0 - penalty))

    def _step_weight(self, seat, target):
        """One hysteresis + smoothing step: healthy seats pin 1.0;
        a target at/below the enter bound flips the seat DEGRADED
        (weight then tracks the target with gain alpha); recovery
        needs the target back above the exit bound for
        ``_W_OK_POLLS`` consecutive polls — no flapping on a noisy
        boundary signal."""
        prev_hys = seat.hys
        if seat.hys == "healthy":
            if target <= _W_ENTER:
                seat.hys = "degraded"
                seat.ok_polls = 0
        elif target >= _W_EXIT:
            seat.ok_polls += 1
            if seat.ok_polls >= _W_OK_POLLS:
                seat.hys = "healthy"
        else:
            seat.ok_polls = 0
        if seat.hys == "degraded":
            seat.weight += self._w_gain * (target - seat.weight)
            seat.weight = max(self._w_floor, min(1.0, seat.weight))
        else:
            seat.weight = 1.0
        self._g_weight.labels(engine_id=seat.engine_id) \
            .set(round(seat.weight, 4))
        if seat.hys != prev_hys:
            _events.emit("router_engine_weight",
                         router_id=self.router_id,
                         engine_id=seat.engine_id, state=seat.hys,
                         weight=round(seat.weight, 4),
                         target=round(target, 4))

    def _fold_manifest(self, manifest):
        """Union one engine's manifest into the fleet manifest; when
        the union GROWS, persist it (MXNET_TPU_WARMUP_MANIFEST) so a
        restarting engine finds the fleet's whole working set on disk
        even after every live engine is gone. The in-memory union is
        seeded from the persisted file, and an empty shape set is
        never written: a freshly restarted fleet reporting zero
        visited shapes must not clobber the previous run's manifest
        (which is exactly what the next warm restart needs)."""
        with self._lock:
            need_seed = self._fleet_manifest is None
        seed = compile_cache.load_manifest() if need_seed else None
        with self._lock:
            prev = self._fleet_manifest
            if prev is None:
                prev = seed
            merged = compile_cache.merge_manifests([prev, manifest])
            if merged is None:
                return
            grew = (prev is None
                    or len(merged["shapes"]) > len(prev["shapes"])
                    or set(merged["engines"]) != set(prev["engines"]))
            self._fleet_manifest = merged
        self._g_manifest.set(len(merged["shapes"]))
        if grew and merged["shapes"]:
            path = compile_cache.save_manifest(merged)
            _events.emit("router_warmup_manifest",
                         router_id=self.router_id,
                         shapes=len(merged["shapes"]),
                         engines=merged["engines"], path=path)

    def warmup_manifest(self):
        """The fleet-union warmup manifest (``/warmup`` on the
        router's exposition server; falls back to the persisted file
        when no engine has reported yet — e.g. right after a full
        fleet restart)."""
        with self._lock:
            if self._fleet_manifest is not None:
                return dict(self._fleet_manifest)
        return compile_cache.load_manifest()

    # -- router active/active HA -------------------------------------------
    def set_peer(self, url):
        """Configure (or repoint) the active/active peer AFTER
        construction — the two-router bootstrap needs each other's
        exposed URL, which only exists post-``expose()``. Starts the
        HA journal listener immediately when this router is already
        exposed. A no-op under ``MXNET_TPU_ROUTER_HA=0`` (the
        disabled path registers no family and pays no per-request
        cid cost)."""
        if not self._ha_on:
            return self
        self._peer_url = str(url).rstrip("/")
        self._ha_setup()
        with self._lock:
            expo = self._expo
            if expo is not None:
                self._ha_listen(expo.host)
        return self

    def _ha_listen(self, host):
        """Start the HA journal listener (caller holds ``_lock``)."""
        if self._ha is not None or not self._ha_on:
            return
        from .wire import WireListener
        try:
            self._ha = WireListener(
                owner_id=self.router_id, handler=self._ha_handle,
                host=host,
                port=envvars.get("MXNET_TPU_ROUTER_HA_PORT"),
                side="ha")
            self._ha_setup()
        except OSError as e:
            _events.emit("router_ha_listen_error",
                         router_id=self.router_id, error=repr(e))

    def _ha_setup(self):
        """Register the HA counter family (the activity gate: journal
        and cid bookkeeping run only once this exists — HA off means
        no family and zero per-request cost)."""
        if self._c_ha is None:
            self._c_ha = _REGISTRY.counter(
                "mxnet_tpu_router_ha_total",
                "router active/active HA events: journal sent/received"
                "/released, ack misses, skipped (no peer link), orphan "
                "adoptions, cid dedup hits, journal-cap drops",
                ("event",))

    def _ha_count(self, event):
        if self._c_ha is not None:
            self._c_ha.labels(event=event).inc()

    def _ha_handle(self, payload):
        """The inbound journal surface (wire-listener handler, runs on
        the peer connection's reader thread — instant bookkeeping
        only)."""
        op = payload.get("op") if isinstance(payload, dict) else None
        if op == "journal":
            cid = str(payload.get("cid"))
            entry = {"tokens": payload.get("tokens"),
                     "token_types": payload.get("token_types"),
                     "deadline_ms": payload.get("deadline_ms"),
                     "decode": payload.get("decode"),
                     "stream": bool(payload.get("stream")),
                     "model_id": payload.get("model_id"),
                     "tenant": payload.get("tenant"),
                     "tenant_class": payload.get("tenant_class"),
                     "router_id": payload.get("router_id"),
                     "t": time.monotonic()}
            dropped = 0
            with self._lock:
                self._journal[cid] = entry
                self._journal.move_to_end(cid)
                while len(self._journal) > self._journal_cap:
                    self._journal.popitem(last=False)
                    dropped += 1
            self._ha_count("journal_rx")
            for _ in range(dropped):
                self._ha_count("journal_drop")
            return {"ok": True}
        if op == "release":
            with self._lock:
                self._journal.pop(str(payload.get("cid")), None)
            self._ha_count("release")
            return {"ok": True}
        raise ValueError(f"unknown HA op {op!r}")

    def _ha_lookup(self, cid):
        """Resubmit dedupe: the future already serving this cid (live
        or adopted), or None. A cid found in the PEER's journal means
        the peer accepted it and died before answering — the client
        re-drove it here, so the entry is consumed (counted an
        adoption) and the resubmitted payload is executed once."""
        with self._lock:
            fut = self._live_cids.get(cid)
            if fut is None:
                fut = self._adopted.get(cid)
            entry = None
            if fut is None:
                entry = self._journal.pop(cid, None)
        if fut is not None:
            self._ha_count("dedup")
            _events.emit("router_ha_dedup", router_id=self.router_id,
                         cid=cid)
            return fut
        if entry is not None:
            self._ha_count("adopt")
            _events.emit("router_ha_adopt", router_id=self.router_id,
                         cid=cid, count=1, path="resubmit")
        return None

    def _ha_journal(self, req):
        """Journal one admitted request to the peer and wait (bounded)
        for the ack — the request must be durable on the peer BEFORE
        it can be dispatched, or a death in between loses it. No live
        peer link degrades to unjournaled (counted ``skip``) —
        availability over durability."""
        peer = self._peer
        if peer is None or not peer.has_live():
            if self._peer_url:
                self._ha_count("skip")
            return
        acked = threading.Event()
        box = {}
        t_ack0 = time.monotonic()

        def _on_ack(exc, body):
            # the reader delivers ERROR frames with exc=None and the
            # error in the body: a peer that REFUSED the journal op
            # must not count as durable
            box["ok"] = (exc is None
                         and not (body or {}).get("error_type"))
            acked.set()

        try:
            peer.dispatch({"op": "journal", "cid": req.cid,
                           "tokens": req.tokens,
                           "token_types": req.token_types,
                           "deadline_ms": req.remaining_ms(),
                           "decode": req.decode,
                           "stream": req.stream,
                           "model_id": req.model_id,
                           "tenant": req.tenant,
                           "tenant_class": req.tenant_class,
                           "router_id": self.router_id},
                          _on_ack, self._ha_ack_s)
        except WireError:
            self._ha_count("skip")
            return
        ok = acked.wait(self._ha_ack_s) and box.get("ok")
        # the durability wait is on the request's critical path — a
        # slow peer surfaces as an ``ha_ack`` stage in /whyslow
        _attribution.stamp(req, "ha_ack", t_ack0, time.monotonic(),
                           attrs={"acked": bool(ok)})
        if ok:
            self._ha_count("journal")
        else:
            self._ha_count("ack_miss")

    def _ha_release(self, req):
        """Tell the peer this cid resolved (fire-and-forget): its
        journal entry must not outlive the request, or a later death
        would re-execute completed work."""
        cid = req.cid
        if cid is None:
            return
        with self._lock:
            self._live_cids.pop(cid, None)
        peer = self._peer
        if peer is None:
            return
        try:
            peer.dispatch({"op": "release", "cid": cid},
                          lambda exc, body: None, self._ha_ack_s)
        except WireError:
            pass

    def _maintain_peer(self):
        """Poll-thread peer upkeep: liveness (any HTTP answer from the
        peer's /healthz means the PROCESS is alive — an unhealthy
        fleet is not a dead router), journal-link connect/sweep, and
        the death edge that triggers orphan adoption."""
        if not (self._ha_on and self._peer_url):
            return
        if not self._peer_recon.ready():
            return      # backing off a recently failed peer dial
        alive, hz = True, {}
        try:
            # capped at the poll period: a slow-but-answering peer
            # must not stretch every seat-health tick
            with urllib.request.urlopen(
                    self._peer_url + "/healthz",
                    timeout=min(2.0, max(0.25,
                                         self._poll_interval_s))) as r:
                hz = json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            try:
                hz = json.loads(e.read().decode())
            except Exception:
                hz = {}
        except Exception:
            alive = False
        if alive:
            self._peer_recon.succeeded()
            self._peer_fails = 0
            self._peer_ha_port = hz.get("ha_port") or self._peer_ha_port
            rid = hz.get("router_id")
            if rid is not None:
                self._peer_rid = str(rid)
            if self._peer_alive is False:
                _events.emit("router_peer_state",
                             router_id=self.router_id,
                             peer=self._peer_rid or self._peer_url,
                             state="up")
            self._peer_alive = True
            port = self._peer_ha_port
            if port:
                peer = self._peer
                if peer is not None and peer.port != int(port):
                    # peer restarted on a new HA port: rebuild
                    self._peer = None
                    peer.close()
                    peer = None
                if peer is None:
                    host = (urlsplit(self._peer_url).hostname
                            or "127.0.0.1")
                    peer = WireClient(host, int(port), conns=1,
                                      client_id=self.router_id,
                                      expect_engine_id=self._peer_rid)
                    self._peer = peer
                    self._ha_setup()
                peer.ensure()
                peer.sweep()
            return
        self._peer_recon.failed()
        self._peer_fails += 1
        if self._peer_alive is True \
                and self._peer_fails >= max(2, self._fail_after):
            self._peer_alive = False
            _events.emit("router_peer_state", router_id=self.router_id,
                         peer=self._peer_rid or self._peer_url,
                         state="down")
            try:
                self._adopt_orphans()
            except Exception as e:
                _events.emit("router_ha_adopt_error",
                             router_id=self.router_id, error=repr(e))

    def _adopt_orphans(self):
        """The peer died: every cid it journaled and never released is
        an in-flight request about to be lost — rebuild each as a
        RouterRequest and requeue it FRONT of the line (it has been
        waiting longest). A client resubmitting its cid attaches to
        the adopted future; a client that never comes back still gets
        the work completed (at-least-once). The cids are RESERVED in
        ``_adopted`` in the same critical section that empties the
        journal, so a resubmit racing this sweep attaches instead of
        being admitted as duplicate new work."""
        reserved = []               # (cid, entry, future)
        with self._cond:
            if self._closed:
                return 0
            entries = list(self._journal.items())
            self._journal.clear()
            for cid, e in entries:
                if cid in self._live_cids or cid in self._adopted:
                    continue
                fut = InferenceFuture()
                self._live_cids[cid] = fut
                self._adopted[cid] = fut
                reserved.append((cid, e, fut))
            while len(self._adopted) > self._adopted_cap:
                self._adopted.popitem(last=False)
        adopt = []
        for cid, e, fut in reserved:
            deadline_ms = e.get("deadline_ms")
            if deadline_ms is not None:
                deadline_ms = (float(deadline_ms)
                               - (time.monotonic() - e["t"]) * 1e3)
                if deadline_ms <= 0:
                    # dead on its own deadline either way — but the
                    # reserved future must resolve for any attached
                    # resubmitter
                    fut.set_exception(DeadlineExceededError(
                        f"adopted request {cid} expired before its "
                        "peer's death was detected"))
                    continue
            try:
                req = RouterRequest(e["tokens"], e.get("token_types"),
                                    deadline_ms,
                                    decode=e.get("decode"),
                                    stream=bool(e.get("stream")),
                                    model_id=e.get("model_id"),
                                    tenant=e.get("tenant"),
                                    tenant_class=e.get("tenant_class"))
            except Exception as exc:
                fut.set_exception(ServingError(
                    f"adopted journal entry {cid} unusable: {exc!r}"))
                continue
            # the RESERVED future is the request's identity (clients
            # may already hold it via a resubmit attach)
            req.future = fut
            fut.trace_id = req.trace_id
            req.cid = cid
            req.adopted = True
            req.span.set_attr(adopted=1)
            adopt.append(req)
        if not adopt:
            _events.emit("router_peer_state", router_id=self.router_id,
                         peer=self._peer_rid or self._peer_url,
                         state="adopted")
            return 0
        with self._cond:
            if self._closed:
                for req in adopt:
                    req.future.set_exception(EngineStoppedError(
                        "router stopped during orphan adoption"))
                return 0
            for req in reversed(adopt):
                self._queue.appendleft(req)
            self._pending += len(adopt)
            self._cond.notify_all()
        for _ in adopt:
            self._ha_count("adopt")
        self._bump("adopted", len(adopt))
        _events.emit("router_ha_adopt", router_id=self.router_id,
                     peer=self._peer_rid or self._peer_url,
                     count=len(adopt), path="peer_death")
        # the peer's orphans are in OUR hands now: release the
        # incident hold (the outage is handled, not ongoing)
        _events.emit("router_peer_state", router_id=self.router_id,
                     peer=self._peer_rid or self._peer_url,
                     state="adopted")
        return len(adopt)

    def die(self):
        """Simulate abrupt router death (the chaos drill's
        ``kill_router`` fault and the HA tests' crash surface): stop
        serving WITHOUT draining, resolving, or handing anything off —
        in-flight work is orphaned exactly as a SIGKILL would leave
        it. The peer's journal adoption (and clients' cid resubmits)
        are the recovery path under test. After ``die()``, ``stop()``
        is a no-op."""
        _events.emit("router_die", router_id=self.router_id)
        # sever the OUTWARD surfaces first — peer link, journal
        # listener, exposition server — exactly what a SIGKILL cuts
        # instantly. In-process work may still complete during the
        # teardown window, but no release/journal/reply escapes it,
        # so the peer's view matches a real crash.
        with self._lock:
            expo, self._expo = self._expo, None
            ha, self._ha = self._ha, None
            peer, self._peer = self._peer, None
        if peer is not None:
            peer.close()
        if ha is not None:
            ha.close()
        if expo is not None:
            expo.close()
        with self._cond:
            self._died = True
            self._closed = True
            self._abort = True
            stranded = list(self._queue)
            self._queue.clear()
            self._cond.notify_all()
        # a real SIGKILL severs every client connection instantly; the
        # in-process simulation must match it — stranded futures fail
        # NOW so a blocked /submit handler answers (503) and its
        # client re-drives the cid at the survivor, instead of hanging
        # out a long timeout on a half-dead router
        for req in stranded:
            req.span.end(error="router died")
            req.future.set_exception(EngineStoppedError(
                "router died with the request undispatched"))
        self._stop_evt.set()
        _recorder.unregister_probe(self._probe_name)
        _recorder.remove_bundle_section("router_scoreboard")
        if self._canary is not None:
            self._canary.stop()
        if self._slo is not None:
            self._slo.stop()
        if self._history is not None:
            self._history.stop()
        with self._lock:
            seats = list(self._seats.values())
        for seat in seats:
            seat.close()
        for t in (self._dispatcher, self._poller):
            if t is not None and t is not threading.current_thread():
                t.join(timeout=5.0)

    def _mark(self, seat, up, reason=None):
        if seat.routable == up and seat.up == up:
            return
        seat.up = up
        seat.routable = up
        seat.last_change = time.time()
        self._g_up.labels(engine_id=seat.engine_id).set(1 if up else 0)
        _events.emit("router_engine_state", router_id=self.router_id,
                     engine_id=seat.engine_id,
                     state="up" if up else "down", reason=reason)
        if up:
            seat.consecutive_failures = 0
            seat.last_error = None

    def _watchdog_probe(self):
        """None while the whole fleet is routable; an anomaly dict
        (which the flight bundle's router_scoreboard.json expands on)
        when any engine is down."""
        if not self.running:
            return None
        with self._lock:
            down = [s.engine_id for s in self._seats.values()
                    if not s.routable]
            total = len(self._seats)
        if not down:
            return None
        kind = ("router_all_engines_down" if len(down) == total
                else "router_engine_down")
        return {"kind": kind, "engines_down": down,
                "engines_total": total}

    def scoreboard(self):
        """Per-engine health rows (the /stats ``engines`` section and
        the flight bundle's fleet view)."""
        with self._lock:
            return {sid: seat.row() for sid, seat in self._seats.items()}

    def snapshot(self):
        board = self.scoreboard()
        with self._lock:
            counters = dict(self._c)
            queue_depth = len(self._queue)
            pending = self._pending
            manifest_shapes = (len(self._fleet_manifest["shapes"])
                               if self._fleet_manifest else 0)
        return {"router_id": self.router_id,
                "running": self.running,
                "counters": counters,
                "queue_depth": queue_depth,
                "pending": pending,
                "manifest_shapes": manifest_shapes,
                "engines": board,
                "engines_up": sum(1 for r in board.values()
                                  if r["routable"]),
                "engines_total": len(board),
                "latency": {"total": self.total_ms.snapshot()},
                "dispatch_overhead": self.dispatch_overhead.snapshot()}

    # -- aggregated observability plane ------------------------------------
    def _remote_seats(self, engine_filter=None):
        """Remote seats worth scraping: unroutable seats are SKIPPED —
        a dead endpoint would stall the aggregated reply by a full
        http timeout per scrape (past Prometheus's own scrape budget)
        while contributing nothing."""
        with self._lock:
            return [s for s in self._seats.values()
                    if isinstance(s, _RemoteSeat) and s.routable
                    and (engine_filter is None
                         or s.engine_id in engine_filter)]

    def metrics_text(self):
        """The fleet exposition: this process's registry (router
        families + every LOCAL engine's labeled families) scrape-merged
        with each routable remote engine's ``/metrics``."""
        from ..telemetry.expo import merge_prometheus_texts

        texts = [_REGISTRY.render_prometheus()]
        for seat in self._remote_seats():
            try:
                texts.append(seat.metrics_text())
            except Exception:
                self._c_scrape_err.labels(engine_id=seat.engine_id).inc()
        return merge_prometheus_texts(texts)

    def traces_summary(self):
        """Fleet /traces: the local span ring (router + in-process
        engines) merged with every routable remote engine's
        tail-sampled ring, each kept trace annotated with the engines
        that served it."""
        parts = [(None, _spans.traces_summary())]
        for seat in self._remote_seats():
            parts.append((seat.engine_id, seat.traces_summary()))
        merged = _spans.merge_trace_summaries(parts)
        with self._lock:
            known = dict(self._trace_engines)
        for rec in merged["kept"]:
            for eid in known.get(rec["trace_id"], ()):
                if eid not in rec["engines"]:
                    rec["engines"].append(eid)
        return merged

    def get_trace(self, trace_id):
        """Fleet /traces/<id>: one merged span tree across every ring
        that kept the trace — engine-side spans parent under the
        ``router/request`` root via the propagated span id. When the
        router dispatched the trace itself it queries only the engines
        that served it; unknown ids fan out to every routable remote
        (the trace may predate this router or be engine-local)."""
        with self._lock:
            known = self._trace_engines.get(trace_id)
        parts = [(None, _spans.get_trace(trace_id))]
        for seat in self._remote_seats(engine_filter=set(known)
                                       if known else None):
            parts.append((seat.engine_id, seat.get_trace(trace_id)))
        return _spans.merge_trace_records(parts)

    def cost_table(self):
        """The fleet ``/costs`` body: every routable engine's
        per-bucket cost ledger (local seats read the handle, remote
        seats scrape their ``/costs``), merged into one fleet table —
        per-bucket sums across engines plus fleet totals with the
        derived cost-per-request / cost-per-1k-tokens rates. The books
        are cumulative, so they must survive seats dying: every seat
        is asked regardless of routability (a stopped LOCAL engine's
        ledger still reads; remote seats fall back to their last
        fetched table) and ``remove_engine`` retires a seat's final
        ledger into the merge. Only a seat that never produced a table
        contributes nothing — named in ``missing`` rather than
        stalling the reply."""
        from .metrics import CostLedger

        engines = {}
        missing = []
        with self._lock:
            seats = list(self._seats.values())
            retired = dict(self._retired_costs)
        for seat in seats:
            table = seat.cost_table()
            if table is None:
                missing.append(seat.engine_id)
                continue
            engines[seat.engine_id] = table
        fleet_buckets = {}
        for table in list(engines.values()) + list(retired.values()):
            for blen, row in (table.get("buckets") or {}).items():
                fleet_buckets.setdefault(str(blen), []).append(row)
        fleet = {b: CostLedger._derive(merge_cost_buckets(rows))
                 for b, rows in sorted(fleet_buckets.items(),
                                       key=lambda kv: int(kv[0]))}
        totals = CostLedger._derive(
            merge_cost_buckets(list(fleet.values())))
        out = {"router_id": self.router_id, "engines": engines,
               "fleet": fleet, "totals": totals, "missing": missing}
        if retired:
            out["retired"] = retired
        return out

    @property
    def alerts(self):
        """The router's fleet :class:`~mxnet_tpu.telemetry.alerts.
        AlertDaemon` (None when ``MXNET_TPU_SLO=0`` or before
        ``start``) — drills drive ``evaluate_once`` / add rules
        through it."""
        return self._slo

    def slo_snapshot(self):
        """The fleet ``/slo`` body: the router's own objectives
        (availability across failover, fleet latency, engines-up
        fraction) plus every seat's seat-level SLO snapshot under
        ``engines`` (local handles read directly, remote seats
        scraped; seats without an evaluator are listed in
        ``missing``)."""
        if self._slo is None:
            out = {"owner": self.router_id, "enabled": False,
                   "objectives": {}}
        else:
            out = self._slo.evaluator.snapshot()
        with self._lock:
            seats = list(self._seats.values())
        engines, missing = {}, []
        for seat in seats:
            snap = seat.slo_snapshot()
            if snap is None:
                missing.append(seat.engine_id)
            else:
                engines[seat.engine_id] = snap
        out["engines"] = engines
        if missing:
            out["missing"] = missing
        return out

    def alerts_snapshot(self):
        """The fleet ``/alerts`` body: the router's own rule table
        plus every seat's, with fleet-wide firing/pending totals on
        top — one endpoint answers "what is burning, and WHERE"."""
        if self._slo is None:
            out = {"owner": self.router_id, "enabled": False,
                   "rules": [], "firing": 0, "pending": 0}
        else:
            out = self._slo.snapshot()
        with self._lock:
            seats = list(self._seats.values())
        engines = {}
        firing = out.get("firing", 0)
        pending = out.get("pending", 0)
        for seat in seats:
            snap = seat.alerts_snapshot()
            if snap is None:
                continue
            engines[seat.engine_id] = snap
            firing += snap.get("firing", 0)
            pending += snap.get("pending", 0)
        out["engines"] = engines
        out["fleet_firing"] = firing
        out["fleet_pending"] = pending
        return out

    def whyslow(self):
        """The fleet ``/whyslow`` body: the router's own stage table
        (dispatch transit, HA-journal ack) merged with every seat's
        per-stage breakdown — one endpoint answers "the fleet is slow,
        WHICH stage, on WHICH engine, and here is the worst trace".
        Seats without attribution (disabled, old peers) simply
        contribute nothing."""
        parts = []
        agg = _attribution.get_aggregator(self.router_id)
        if agg is not None:
            parts.append(agg.snapshot())
        with self._lock:
            seats = list(self._seats.values())
        for seat in seats:
            parts.append(seat.whyslow())
        return _attribution.merge_whyslow(parts, owner=self.router_id)

    def _whyslow_top(self):
        """Fleet top-stage rows for firing alert payloads, memoized
        for ~1s: /alerts renders every rule's payload in one pass and
        must not re-scrape every remote seat's /whyslow per rule.
        An EMPTY result only lives ~0.1s (one render pass): under a
        fast-burn overload the fleet rule can fire within the long
        TTL, and the page must not inherit a pre-traffic empty memo —
        it exists to say WHERE the fleet is slow."""
        now = time.monotonic()
        cached = self._whyslow_top_cache
        if cached is not None and \
                now - cached[0] < (1.0 if cached[1] else 0.1):
            return cached[1]
        top = (self.whyslow() or {}).get("top") or None
        if top:
            # the fleet merge ranks EVERY observed stage (so nothing
            # is truncation-blind); the page payload only wants the
            # leaders
            top = top[:envvars.get("MXNET_TPU_ATTRIBUTION_TOP")]
        self._whyslow_top_cache = (now, top)
        return top

    def capture_summary(self):
        """The fleet ``/capture`` body: every seat's capture-corpus
        summary under ``engines`` plus fleet record/byte totals (local
        handles read directly, remote seats scraped; seats without
        capture — disabled, old peers — land in ``missing``)."""
        from .capture import merge_summaries
        with self._lock:
            seats = list(self._seats.values())
        return merge_summaries(
            [(seat.engine_id, seat.capture_summary()) for seat in seats],
            owner=self.router_id)

    @property
    def shadow(self):
        """The router's :class:`~.shadow.ShadowMirror` (None unless
        ``MXNET_TPU_SHADOW`` was on at start) — drills arm it and
        pass it as the ``swap_model`` gate."""
        return self._shadow

    def set_shadow_target(self, target, model_id=None, version=None,
                          fraction=None):
        """Arm the shadow mirror at a candidate seat (an in-process
        engine handle or a ``"host:port"`` wire address). Raises
        :class:`~.queue.ServingError` when shadow validation is off
        (``MXNET_TPU_SHADOW=0``) — arming a mirror that cannot exist
        should be loud, not a silent no-op."""
        if self._shadow is None:
            raise ServingError(
                "shadow validation disabled (MXNET_TPU_SHADOW=0)")
        self._shadow.set_target(target, model_id=model_id,
                                version=version, fraction=fraction)
        return self

    def clear_shadow_target(self):
        if self._shadow is not None:
            self._shadow.clear_target()
        return self

    def shadow_verdict(self):
        """The ``/shadow`` body (None when shadow validation is
        off)."""
        return (self._shadow.verdict()
                if self._shadow is not None else None)

    def incidents_snapshot(self):
        """The fleet ``/incidents`` body: this process's incident
        tracker (the router's own signals + every in-process seat's —
        they share one tracker) merged with each routable remote
        seat's ``/incidents``, deduped by incident id."""
        parts = [(None, _incidents.snapshot())]
        for seat in self._remote_seats():
            parts.append((seat.engine_id, seat.incidents_snapshot()))
        out = _incidents.merge_snapshots(parts)
        out["router_id"] = self.router_id
        return out

    def _canary_targets(self):
        """The canary prober's view of the fleet: every seat
        (routable or NOT — black-box probing of a down seat is how
        recovery is detected), remote seats by URL + advertised wire
        port, in-process seats by handle."""
        with self._lock:
            seats = list(self._seats.values())
        out = []
        for seat in seats:
            # the generation token lets the prober re-pin its TOFU
            # golden when a REPLACEMENT seat reuses an id (new model,
            # new golden — not a forever checksum_mismatch page). The
            # hosted model VERSIONS ride the token too: a live
            # hot-swap (same seat, new weights) legitimately changes
            # the canary's answer, so the golden re-pins instead of
            # paging checksum_mismatch forever
            token = seat.token
            if seat.models:
                token += "@" + ",".join(
                    f"{m}={v}" for m, v in sorted(seat.models.items()))
            t = {"engine_id": seat.engine_id, "kind": seat.kind,
                 "token": token}
            if isinstance(seat, _RemoteSeat):
                t["url"] = seat.base_url
                # advertised (port, REAL engine id) from the health
                # poll: the prober's wire handshake pins the identity
                # so a replacement engine on a recycled port is never
                # probed (and TOFU-goldened) under the old seat's name
                t["wire_port"] = seat._advertised[0]
                t["wire_engine_id"] = seat._advertised[1]
            else:
                t["engine"] = seat._engine
            out.append(t)
        return out

    @property
    def canary(self):
        """The router's :class:`~mxnet_tpu.telemetry.canary.
        CanaryProber` (None when ``MXNET_TPU_CANARY=0`` or before
        ``start``)."""
        return self._canary

    def _remote_submit(self, payload):
        """``POST /submit`` handler (exposition-server thread): admit
        + block for the result, JSON either way — the surface a
        CLIENT-SIDE failover target (``serve_loadgen --router-url
        r1,r2``) drives, mirroring the engine's own handler. Refusals
        carry their class name in ``error_type``; a fleet-down shed
        answers 503 so a dumb load balancer (or the loadgen's url
        list) knows to try the next router."""
        t0 = time.perf_counter()
        try:
            fut = self.submit(payload["tokens"],
                              payload.get("token_types"),
                              deadline_ms=payload.get("deadline_ms"),
                              cid=payload.get("cid"),
                              max_new_tokens=payload.get("max_new_tokens"),
                              eos_id=payload.get("eos_id"),
                              temperature=payload.get("temperature"),
                              top_k=payload.get("top_k"),
                              top_p=payload.get("top_p"),
                              seed=payload.get("seed"),
                              model_id=payload.get("model_id"),
                              tenant=payload.get("tenant"),
                              tenant_class=payload.get("tenant_class"))
        except (ServingError, ValueError, LookupError, TypeError) as e:
            name = type(e).__name__
            status = {"NoEngineAvailableError": 503}.get(
                name, _SUBMIT_ERROR_STATUS.get(name, 400))
            return (status, {"ok": False, "error_type": name,
                             "error": str(e),
                             "router_id": self.router_id})
        timeout_s = payload.get("timeout_s") or self._dispatch_timeout_s
        try:
            out = fut.result(timeout=float(timeout_s))
        except Exception as e:
            name = type(e).__name__
            status = {"NoEngineAvailableError": 503}.get(
                name, _SUBMIT_ERROR_STATUS.get(name, 500))
            return (status, {"ok": False, "error_type": name,
                             "error": str(e), "trace_id": fut.trace_id,
                             "router_id": self.router_id})
        return 200, {"ok": True, "result": np.asarray(out).tolist(),
                     "trace_id": fut.trace_id,
                     "router_id": self.router_id,
                     "router_ms": round(
                         (time.perf_counter() - t0) * 1e3, 3),
                     "cost": getattr(fut, "cost", None),
                     "breakdown": getattr(fut, "breakdown", None)}

    def _healthz(self):
        board = self.scoreboard()
        up = sum(1 for r in board.values() if r["routable"])
        with self._lock:
            queue_depth = len(self._queue)
            ha = self._ha
        return (self.running and up > 0,
                {"router_id": self.router_id, "engines_up": up,
                 "engines_total": len(board),
                 "queue_depth": queue_depth,
                 "ha_port": ha.port if ha is not None else None})

    def expose(self, port=0, host="127.0.0.1"):
        """Start (or return) the router's exposition server: the
        AGGREGATED ``/metrics``, fleet ``/healthz`` (ok while ≥1
        engine is routable), ``/stats`` (scoreboard + counters), the
        merged ``/traces`` + ``/traces/<id>``, the fleet ``/costs``
        cost table, ``/slo`` + ``/alerts`` (fleet objectives + every
        seat's seat-level view), the fleet ``/whyslow`` stage
        attribution table, the fleet ``/capture`` corpus summary (and
        ``/shadow`` verdict while shadow validation is on), and
        ``POST /submit`` so clients
        (e.g. ``serve_loadgen --router-url``) can drive this router
        from another process. Closed by :meth:`stop`."""
        from ..telemetry.expo import TelemetryServer

        with self._lock:
            if self._closed:
                raise EngineStoppedError(
                    "cannot expose telemetry on a stopped router")
            if self._expo is not None:
                return self._expo
            srv = TelemetryServer(healthz_fn=self._healthz,
                                  stats_fn=self.snapshot,
                                  metrics_fn=self.metrics_text,
                                  traces_fn=self.traces_summary,
                                  trace_fn=self.get_trace,
                                  warmup_fn=self.warmup_manifest,
                                  costs_fn=self.cost_table,
                                  submit_fn=self._remote_submit,
                                  slo_fn=self.slo_snapshot,
                                  alerts_fn=self.alerts_snapshot,
                                  incidents_fn=self.incidents_snapshot,
                                  whyslow_fn=self.whyslow,
                                  history_fn=(
                                      self._history.store
                                      if self._history is not None
                                      else None),
                                  capture_fn=self.capture_summary,
                                  shadow_fn=(
                                      self._shadow.verdict
                                      if self._shadow is not None
                                      else None),
                                  port=port, host=host)
            self._expo = srv
            # active/active HA journal listener: rides the exposition
            # lifecycle like the engine's wire listener; the port is
            # advertised in /healthz as ha_port so the PEER discovers
            # it off its health poll — a bind failure degrades to
            # unjournaled HA, never a dead router
            if (self._peer_url
                    or envvars.get("MXNET_TPU_ROUTER_HA_PORT")):
                self._ha_listen(host)
        _events.emit("telemetry_expose", router_id=self.router_id,
                     port=srv.port, host=srv.host)
        return srv
