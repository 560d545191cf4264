"""In-process continuous-batching model server for encoder models.

``ServingEngine`` owns three pieces: a bounded :class:`RequestQueue`
(admission control), a :class:`ContinuousBatcher` (first-fit packing
into a closed set of shapes), and one worker thread running the
model's hybridized/CachedOp forward per packed batch — the in-process
analog of MXNet Model Server's queue → batcher → backend-worker
pipeline, with iteration-level (Orca-style) scheduling: every batch is
re-formed from whatever is queued the moment the previous batch
finishes, so a long request never convoys short ones behind it.

The model contract is one callable::

    model(ids, token_types, valid_length, segment_ids, positions)
      -> (B, S, U) NDArray            # or a tuple whose [0] is that

with every input an int32 NDArray in the io/packing.py layout
(``gluon.model_zoo.bert.bert_serving_entry`` adapts a BERTModel).
Because inputs arrive in a small closed shape set, the CachedOp
compile cache holds one executable per (rows, row_len) bucket and
steady-state serving never re-traces.
"""
from __future__ import annotations

import itertools
import os
import threading
import time

import numpy as np

from .. import autograd, compile_cache, envvars, profiler
from .. import ndarray as nd
from ..context import current_context
from ..telemetry import attribution as _attribution
from ..telemetry import events as _events
from ..telemetry import incidents as _incidents
from ..telemetry import profiling as _profiling
from ..telemetry import recorder as _recorder
from ..telemetry import spans as _spans
from ..telemetry.registry import REGISTRY as _REGISTRY
from ..telemetry.trace import trace_context as _trace_context
from . import tenancy
from .batcher import ContinuousBatcher
from .metrics import (CostLedger, ServingStats, exemplar_gate,
                      slow_exemplar)
from .queue import (DeadlineExceededError, EngineStoppedError,
                    QueueFullError, Request, RequestQueue,
                    RequestTooLongError, ServingError,
                    UnknownModelError)

__all__ = ["ServingEngine"]

_engine_seq = itertools.count()

# HTTP status for each admission/serving failure the /submit dispatch
# endpoint can report (the router maps error_type back to the class)
_SUBMIT_ERROR_STATUS = {
    "QueueFullError": 429,
    "RequestTooLongError": 413,
    "DeadlineExceededError": 504,
    # the handler's own fut.result(timeout) expiring is request-scoped
    # like a deadline: 504 tells a failover client NOT to replay it as
    # new work while this process may still be executing it
    "TimeoutError": 504,
    "EngineStoppedError": 503,
    # out-of-range sampling params are a malformed request, refused at
    # admission — before the compiled step could turn them into NaNs
    "InvalidSamplingError": 400,
    # the named model is not hosted by this engine — the multi-model
    # fleet's 404 (a router retries another seat; a client fixes its
    # model id)
    "UnknownModelError": 404,
}


def _join_trace_ids(requests, cap=16):
    """One contextvar value for a whole batch: the member requests'
    trace ids, comma-joined (capped — a 128-request batch must not
    grow a kilobyte span annotation). None when the batch is empty
    (warmup dummy forwards)."""
    ids = [r.trace_id for r in requests]
    if not ids:
        return None
    if len(ids) > cap:
        ids = ids[:cap] + [f"+{len(ids) - cap}more"]
    return ",".join(ids)


def _slice_tokens(seq_slice, request):
    """Default postprocess: the request's per-token outputs."""
    return seq_slice


def _mean_pool(seq_slice, request):
    return seq_slice.mean(axis=0)


def _cls_pool(seq_slice, request):
    return seq_slice[0]


_POOLERS = {"tokens": _slice_tokens, "mean": _mean_pool, "cls": _cls_pool}


class ServingEngine:
    """Continuous-batching server around one encoder forward.

    Parameters
    ----------
    model : callable or :class:`~.tenancy.ModelRegistry`
        The packed forward (see module docstring), or a registry of
        several — a multi-model engine dispatches each batch through
        the model its requests named (``submit(model_id=...)``), and
        ``swap_model`` hot-swaps any entry live.
    bucket_lens : row-length buckets (ascending); a request longer
        than the last one is rejected at submit.
    max_rows : packed rows per dispatched batch (row counts are
        quantized to powers of two up to this).
    max_queue_depth : admission bound; a full queue sheds with
        :class:`QueueFullError`.
    default_deadline_ms : deadline applied to requests that don't
        bring their own (None = no deadline).
    batch_wait_ms : linger after the first drained request to let a
        batch fill (0 = pure continuous batching; the queue already
        self-clocks under load because requests pile up while the
        previous batch computes).
    pool : per-request output view — "tokens" (len, U), "mean" (U,),
        "cls" (U,), or a callable ``(seq_slice, request) -> result``.
    engine_id : label value for this engine's serving metric families
        (and the ``engine`` attr on its spans). Defaults to a
        process-unique id; give stable names ("chip0") when a router
        fronts several engines so dashboards and the fleet scoreboard
        agree on who is who.
    """

    def __init__(self, model, ctx=None, bucket_lens=(64, 256, 1024),
                 max_rows=8, max_queue_depth=256, default_deadline_ms=None,
                 batch_wait_ms=0.0, max_batch_requests=None, pool="tokens",
                 pad_value=0, stats_window=4096, engine_id=None):
        # model identity: a plain callable becomes a one-entry
        # registry under the default model id — the pre-tenancy API
        # unchanged. Dispatch resolves the fn through the registry per
        # batch, so a hot-swap (or a chaos wrap via the _model
        # property) takes effect at the next batch boundary.
        self._models = tenancy.ModelRegistry.of(model)
        self.engine_id = str(engine_id) if engine_id is not None \
            else f"e{os.getpid():x}-{next(_engine_seq)}"
        self._ctx = ctx if ctx is not None else current_context()
        self._batcher = ContinuousBatcher(bucket_lens=bucket_lens,
                                          max_rows=max_rows,
                                          pad_value=pad_value)
        self._queue = RequestQueue(max_queue_depth)
        self._default_deadline_ms = default_deadline_ms
        self._batch_wait_s = batch_wait_ms / 1e3
        # a packed batch holds at most rows*row_len/1 requests; the
        # drain cap just bounds per-iteration work
        self._max_batch_requests = (max_batch_requests
                                    or max_rows * self._batcher.max_len)
        self._pool = _POOLERS[pool] if isinstance(pool, str) else pool
        self.stats = ServingStats(stats_window, engine_id=self.engine_id)
        self.stats.set_queue_depth_fn(lambda: len(self._queue))
        # per-tenant/per-model observability slice + the per-class WFQ
        # depth pull gauges (scrape-time reads, zero hot-path cost)
        self.tenants = tenancy.TenantStats(self.engine_id)
        wfq = tenancy.wfq_depth_gauge()
        for cls in tenancy.TENANT_CLASSES:
            wfq.labels(engine_id=self.engine_id, tenant_class=cls) \
               .set_function(
                   lambda c=cls: self._queue.depths().get(c, 0))
        # per-bucket cost ledger: device/compile seconds + requests +
        # tokens, cumulative for the process lifetime (reset_stats
        # swaps the stats WINDOW, never the ledger — /costs scrapers
        # diff, same contract as registry counters)
        self.costs = CostLedger(self.engine_id)
        cc = _REGISTRY.counter(
            "mxnet_tpu_serving_compile_cache_total",
            "per-shape executable cache outcomes at dispatch: "
            "memory_hit (in-process), persistent_hit (on-disk cache "
            "served the compile), miss (fresh backend compile)",
            ("engine_id", "result"))
        self._compile_cache = {
            r: cc.labels(engine_id=self.engine_id, result=r)
            for r in ("memory_hit", "persistent_hit", "miss")}
        self._cc_counts = {r: 0 for r in self._compile_cache}
        # visited shape buckets, keyed (model_id, rows, row_len): each
        # hosted model owns its compile universe; the exported warmup
        # manifest stays the plain (rows, row_len) union
        self._seen_shapes = set()
        # guards _seen_shapes + the compile-cache tallies: the worker
        # dispatches while warmup()/warmup_manifest() run on caller
        # threads and the router's poll thread reads the manifest
        self._shapes_lock = threading.Lock()
        # monotonic stamp while a first-visit trace+compile is in
        # flight — the watchdog widens its stall threshold over this
        # window so legitimate compiles never trip a flight bundle
        self._compiling_since = None
        # serializes model forwards across threads: the worker
        # dispatches live batches while warmup() replays shapes on the
        # caller's thread (and black-box canaries make day-one traffic
        # during warmup the NORMAL case, not a misuse) — the CachedOp
        # build path must never trace one block from two threads at
        # once (UnexpectedTracerError). Uncontended cost per batch is
        # one lock op; a compile legitimately holds it for seconds
        # while a waiter queues, hence the long-hold allowance.
        self._forward_lock = threading.Lock()  # mxsan: allow=long-hold
        # SLO engine (MXNET_TPU_SLO): declarative objectives over this
        # engine's metric families + the alert daemon judging them —
        # built in start(), exposed at /slo + /alerts
        self._slo = None
        # history scraper (MXNET_TPU_HISTORY): the retrospective
        # time-series store behind /query_range — built in start()
        self._history = None
        # traffic capture (MXNET_TPU_CAPTURE): the sampled request
        # corpus behind /capture and deterministic replay — built in
        # start(); None means no record branch in _dispatch at all
        self._capture = None
        # exemplar gate, resolved once; the exemplar↔retrievable-trace
        # contract lives in metrics.slow_exemplar (shared with router)
        self._exemplars = exemplar_gate()
        self._worker = None
        self._expo = None
        self._wire = None           # binary dispatch listener (expose)
        self._abort = False
        self._started = False
        self._lock = threading.Lock()
        # watchdog surface: the worker loop beats every iteration, so
        # a beat that stops while running means a wedged forward (or a
        # deadlocked drain) — exactly what the stall probe reports
        self._beat = time.monotonic()
        self._last_dispatch = self._beat
        self._probe_name = f"serving_engine_{id(self):x}"

    @property
    def _model(self):
        """The DEFAULT model's entry point — the pre-registry
        attribute the chaos harness wraps/unwraps in place."""
        return self._models.resolve(None)[1]

    @_model.setter
    def _model(self, fn):
        # in-place fn replacement keeps the version: chaos wraps must
        # not look like a new model version (no canary re-TOFU)
        self._models.swap(self._models.default_id(), fn)

    @property
    def models(self):
        """The engine's :class:`~.tenancy.ModelRegistry`."""
        return self._models

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        with self._lock:
            if self._started:
                return self
            if self._queue.closed:
                raise EngineStoppedError("engine cannot be restarted")
            self._started = True
            self._beat = time.monotonic()
            self._last_dispatch = self._beat
            self._worker = threading.Thread(target=self._run,
                                            name="mxnet_tpu_serving",
                                            daemon=True)
            self._worker.start()
        # serving compiles should outlive this process: point the
        # persistent compilation cache at disk before the first trace
        compile_cache.ensure()
        # a serving process should be able to explain its own death:
        # flight-recorder crash hooks + the stall watchdog ride along
        _recorder.install()
        _recorder.register_probe(self._probe_name, self._watchdog_probe)
        # flight bundles carry the scheduler's WFQ view: per-class
        # queue split + hosted model versions at crash time
        self._bundle_name = f"engine_scheduler_{self.engine_id}"
        _recorder.add_bundle_section(self._bundle_name,
                                     self.scheduler_state)
        # ... and narrate it: the incident tracker folds alert
        # firings, watchdog trips and scoreboard transitions into the
        # /incidents timeline (thread-free — an events tap)
        _incidents.install()
        # ... and where its host time goes while alive: the always-on
        # sampling profiler + resource sweep (MXNET_TPU_PROF=0 opts out)
        _profiling.ensure_started()
        # ... and judge its own health: the SLO engine declares the
        # default serving objectives (latency quantile, availability,
        # optional cost budget) and the alert daemon walks the SRE
        # multi-window burn-rate rules over them (MXNET_TPU_SLO=0
        # opts out of evaluation, exemplars and the endpoints)
        if envvars.get("MXNET_TPU_SLO"):
            from ..telemetry.alerts import (AlertDaemon, default_burn_rules,
                                            default_serving_objectives,
                                            default_tenant_objectives)
            from ..telemetry.slo import SloEvaluator
            evaluator = SloEvaluator(self.engine_id)
            names = default_serving_objectives(evaluator, self.engine_id)
            names += default_tenant_objectives(evaluator, self.engine_id)
            self._slo = AlertDaemon(evaluator)
            default_burn_rules(self._slo, names)
            self._slo.start()
        # ... and remember: the history scraper samples this process's
        # registry into the retrospective store — /query_range,
        # incident forensics and retro SLO replay all read it
        # (MXNET_TPU_HISTORY=0: no thread, no store)
        if envvars.get("MXNET_TPU_HISTORY"):
            from ..telemetry.history import HistoryScraper
            self._history = HistoryScraper(
                self.engine_id,
                slo_fn=(self.slo_snapshot if self._slo is not None
                        else None),
                alerts_fn=(self.alerts_snapshot
                           if self._slo is not None else None)).start()
        # ... and keep the receipts: sampled traffic capture records a
        # head-sampled fraction of admitted requests into the bounded
        # corpus deterministic replay re-executes (MXNET_TPU_CAPTURE=0:
        # one env read — no thread, no families, no files)
        if envvars.get("MXNET_TPU_CAPTURE"):
            from .capture import CaptureStore
            self._capture = CaptureStore(self.engine_id)
        # chaos harness (MXNET_TPU_CHAOS): register as a fault target.
        # Off (the default) this is ONE env read — nothing is built,
        # patched or spawned.
        if envvars.get("MXNET_TPU_CHAOS"):
            from .chaos import register_engine as _chaos_register
            _chaos_register(self)
        _events.emit("engine_start", engine_id=self.engine_id,
                     bucket_lens=list(self._batcher.bucket_lens),
                     max_rows=self._batcher.max_rows)
        return self

    def stop(self, drain=True, timeout=None):
        """Shut down. ``drain=True`` finishes every queued/in-flight
        request first; ``drain=False`` fails them with
        :class:`EngineStoppedError` (counted ``cancelled``)."""
        _events.emit("engine_abort" if not drain else "engine_stop",
                     engine_id=self.engine_id, drain=drain)
        _recorder.unregister_probe(self._probe_name)
        _recorder.remove_bundle_section(
            getattr(self, "_bundle_name", f"engine_scheduler_"
                                          f"{self.engine_id}"))
        if self._slo is not None:
            self._slo.stop()
        if self._history is not None:
            self._history.stop()
        if self._capture is not None:
            self._capture.close()
        with self._lock:
            self._queue.close()
            if not drain:
                self._abort = True
            worker = self._worker
        timed_out = False
        if worker is not None:
            worker.join(timeout)
            timed_out = worker.is_alive()
        # requests still queued after the worker exited (stop before
        # start, abort racing new submits, or a HUNG worker — a stuck
        # forward will never serve them) fail loudly; the exposition
        # server closes either way so the port never leaks
        for r in self._queue.drain_all():
            self.stats.bump("cancelled")
            r.span.end(error="cancelled: engine stopped")
            r.future.set_exception(
                EngineStoppedError("engine stopped before request ran"))
        # release the registry's queue-depth closure (it would pin this
        # engine — params, compile caches — for the process lifetime
        # and report a dead queue as live) and the exposition server;
        # swap under the lock so a racing expose() can't leak one. The
        # queue was just drained, so a constant 0 stays truthful.
        self.stats.set_queue_depth_fn(lambda: 0)
        with self._lock:
            expo, self._expo = self._expo, None
            wire, self._wire = self._wire, None
        if wire is not None:
            wire.close()
        if expo is not None:
            expo.close()
        if timed_out:
            raise ServingError("serving worker did not stop in time")

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=True)
        return False

    @property
    def running(self):
        with self._lock:
            return (self._started and self._worker is not None
                    and self._worker.is_alive())

    # -- client surface ----------------------------------------------------
    def submit(self, tokens, token_types=None, deadline_ms=None,
               trace_id=None, parent_span_id=None, model_id=None,
               tenant=None, tenant_class=None):
        """Enqueue one request; returns an :class:`InferenceFuture`.
        Raises the admission errors directly (queue full, too long,
        stopped, unknown model) so callers can tell shedding from
        failure.

        ``model_id`` names the hosted model to run (None = the
        default); ``tenant``/``tenant_class`` attribute the request to
        an owner and its WFQ admission class (None = ``standard``).

        ``trace_id``/``parent_span_id`` adopt an upstream trace (the
        router's dispatch, or a remote ``/submit`` payload): the
        request joins that trace and its ``serving/request`` span
        parents under the given — possibly remote — span id."""
        if deadline_ms is None:
            deadline_ms = self._default_deadline_ms
        # validate FIRST: a malformed request (empty tokens, mismatched
        # token_types, unknown class) raises to the caller without
        # touching any counter, so submitted always equals the sum of
        # the outcome counters (the invariant the loadgen cross-check
        # reconciles)
        req = Request(tokens, token_types, deadline_ms,
                      trace_id=trace_id, parent_span_id=parent_span_id,
                      tenant=tenant, tenant_class=tenant_class,
                      model_id=model_id)
        req.span.set_attr(engine=self.engine_id)
        self.stats.bump("submitted")
        try:
            # canonicalize up front: dispatch and billing then never
            # re-resolve, and an unknown model is a typed 404 here
            req.model_id = self._models.resolve_id(req.model_id)
        except UnknownModelError:
            self.stats.bump("rejected_unknown_model")
            self.tenants.observe_event(
                req.tenant, req.tenant_class, str(model_id),
                "rejected_unknown_model")
            _events.emit("request_shed", reason="unknown_model",
                         engine_id=self.engine_id, model=str(model_id),
                         trace_id=req.trace_id, tokens=len(req))
            req.span.set_attr(shed="unknown_model").force_keep() \
               .end(error="shed: unknown_model")
            raise
        self.tenants.observe_event(req.tenant, req.tenant_class,
                                   req.model_id, "submitted")
        if not self._started or self._queue.closed:
            self.stats.bump("rejected_stopped")
            req.span.end(error="rejected: engine not running")
            raise EngineStoppedError("serving engine is not running")
        if len(req) > self._batcher.max_len:
            self.stats.bump("rejected_too_long")
            _events.emit("request_shed", reason="too_long",
                         engine_id=self.engine_id,
                         trace_id=req.trace_id, tokens=len(req))
            req.span.set_attr(shed="too_long").force_keep() \
               .end(error="shed: too_long")
            raise RequestTooLongError(
                f"request of {len(req)} tokens exceeds the largest row "
                f"bucket ({self._batcher.max_len})")
        try:
            victim = self._queue.put(req)
        except ServingError as e:
            full = not self._queue.closed
            reason = "queue_full" if full else "stopped"
            self.stats.bump("rejected_queue_full"
                            if full else "rejected_stopped")
            self.tenants.observe_event(
                req.tenant, req.tenant_class, req.model_id,
                "rejected_queue_full" if full else "rejected_stopped")
            _events.emit("request_shed", reason=reason,
                         engine_id=self.engine_id,
                         trace_id=req.trace_id, tokens=len(req))
            # shed traces are tail-sampling KEEPs by contract: the
            # operator debugging overload wants exactly these
            req.span.set_attr(shed=reason).force_keep() \
               .end(error=f"shed: {reason}")
            raise e
        if victim is not None:
            self._shed_victim(victim)
        return req.future

    def _shed_victim(self, victim):
        """Fail a request the WFQ queue EVICTED to admit a
        higher-class arrival under overload — best-effort sheds
        first, priority last, and the shed is loud on every surface
        (counter, tenant slice, event, kept trace)."""
        self.stats.bump("rejected_queue_full")
        self.tenants.observe_event(victim.tenant, victim.tenant_class,
                                   victim.model_id
                                   or self._models.default_id(),
                                   "shed")
        _events.emit("request_shed", reason="wfq_evicted",
                     engine_id=self.engine_id,
                     trace_id=victim.trace_id,
                     tenant_class=victim.tenant_class,
                     tokens=len(victim))
        victim.span.set_attr(shed="wfq_evicted").force_keep() \
              .end(error="shed: wfq_evicted")
        victim.future.set_exception(QueueFullError(
            f"shed by weighted-fair admission: queue full and a "
            f"higher class arrived (class {victim.tenant_class})"))

    def infer(self, tokens, token_types=None, deadline_ms=None,
              timeout=None):
        """Synchronous convenience: submit + wait."""
        return self.submit(tokens, token_types, deadline_ms).result(timeout)

    def warmup(self, shapes=None, manifest=None, model_id=None):
        """Compile ahead of traffic: run one dummy forward per
        (rows, row_len) shape the batcher can emit (or the given
        subset). Serving latency then never pays a trace+compile.

        ``manifest`` (a dict from :func:`~mxnet_tpu.compile_cache.
        load_manifest` / a router's ``/warmup``, or a path to one)
        replays exactly the fleet's VISITED buckets instead of the
        whole universe — the warm-restart path: with the persistent
        compilation cache primed, each replay is a disk fetch, and
        the first real request after a rolling restart runs warm.
        Manifest shapes outside this batcher's universe are skipped
        (a config drift degrades coverage, never crashes startup).

        Call BEFORE submitting traffic (right after ``start``): the
        dummy forwards run on the caller's thread, and tracing the
        same block from two threads at once (warmup racing a live
        batch) is not supported by the CachedOp build path."""
        if manifest is not None:
            if isinstance(manifest, (str, os.PathLike)):
                manifest = compile_cache.load_manifest(manifest)
            universe = set(self._batcher.shape_universe())
            want = compile_cache.manifest_shapes(manifest)
            shapes = [s for s in want if s in universe]
            _events.emit("warmup_replay", engine_id=self.engine_id,
                         shapes=len(shapes),
                         skipped_incompatible=len(want) - len(shapes))
        if shapes is None:
            shapes = self._batcher.shape_universe()
        for rows, row_len in shapes:
            self._forward_shape(rows, row_len, model_id=model_id)
        return self

    def warmup_manifest(self):
        """This engine's visited-shape warmup manifest (exported at
        ``/warmup`` by :meth:`expose`; the fronting router unions the
        fleet's and persists it for restarts). Shapes are the plain
        (rows, row_len) union across hosted models — the manifest
        format predates the model axis and a replay re-warms every
        registered model through :meth:`warmup` anyway."""
        with self._shapes_lock:
            shapes = sorted({(r, l) for _m, r, l in self._seen_shapes})
        return compile_cache.new_manifest(
            self.engine_id, self._batcher.bucket_lens,
            self._batcher.max_rows, shapes)

    def swap_model(self, model, model_id=None, version=None,
                   shapes=None, gate=None):
        """Live hot-swap: cut ``model_id`` (None = the default model)
        over to the new ``model`` entry point with ZERO lost requests.

        The new fn is first warm-replayed over the model's visited
        shape buckets (or the explicit ``shapes``) on the caller's
        thread — each replay traces+compiles the new version's
        executables under the forward lock, exactly like ``warmup`` —
        and only then does the registry flip atomically. Queued and
        in-flight requests are untouched: a batch dispatched before
        the flip finishes on the old fn, the next batch resolves the
        new one, and post-swap traffic runs warm. The version change
        is advertised at ``/healthz``, so a fronting router's canary
        targets change token and the canary re-TOFUs its golden.

        ``gate`` (optional) is consulted BEFORE any warm-replay work:
        a :class:`~.shadow.ShadowMirror` (its shadow-diff verdict
        decides), or any callable returning ``(ok, reason)``. A
        failing gate raises :class:`~.shadow.SwapGateError` and the
        live model keeps serving — evidence first, flip second."""
        if gate is not None:
            gate_fn = getattr(gate, "gate", None) or gate
            ok, reason = gate_fn()
            if not ok:
                from .shadow import SwapGateError
                _events.emit("model_swap_refused",
                             engine_id=self.engine_id,
                             model=str(model_id), version=version,
                             reason=reason)
                raise SwapGateError(
                    f"swap_model refused by gate: {reason}")
        mid = self._models.resolve_id(model_id)
        if shapes is None:
            with self._shapes_lock:
                shapes = sorted((r, l) for m, r, l in self._seen_shapes
                                if m == mid)
        _events.emit("model_swap_begin", engine_id=self.engine_id,
                     model=mid, version=version, shapes=len(shapes))
        t0 = time.monotonic()
        for rows, row_len in shapes:
            self._forward_shape(rows, row_len, fn=model)
        old = self._models.swap(mid, model, version)
        _events.emit("model_swap", engine_id=self.engine_id, model=mid,
                     from_version=old,
                     to_version=self._models.versions().get(mid),
                     warmed_shapes=len(shapes),
                     ms=round((time.monotonic() - t0) * 1e3, 3))
        return self

    @property
    def capture(self):
        """The engine's :class:`~.capture.CaptureStore` (None unless
        ``MXNET_TPU_CAPTURE`` was on at start)."""
        return self._capture

    def capture_summary(self):
        """The ``/capture`` body (None when capture is disabled) —
        what a fronting router's fleet merge reads per seat."""
        return (self._capture.summary()
                if self._capture is not None else None)

    def reset_stats(self):
        """Swap in a fresh ServingStats (compile cache untouched):
        separates a warmup/throwaway traffic window from the measured
        one — lifetime-cumulative stats would otherwise fold both.
        The process-wide telemetry registry keeps counting (Prometheus
        counters never reset); scrapers diff between scrapes."""
        self.stats = ServingStats(self.stats.window,
                                  engine_id=self.engine_id)
        self.stats.set_queue_depth_fn(lambda: len(self._queue))
        return self

    def expose(self, port=0, host="127.0.0.1"):
        """Start (or return the running) telemetry exposition server
        for this engine: Prometheus ``/metrics`` off the process
        registry, ``/healthz`` liveness (worker thread alive, queue
        open, seconds since the worker loop's last beat), ``/stats``
        serving this engine's ``snapshot()`` JSON, ``/costs`` (the
        per-bucket cost ledger), ``/profile`` (the process continuous
        profiler's collapsed stacks), ``/slo`` + ``/alerts`` (the SLO
        engine's objective table and alert-rule state, present unless
        ``MXNET_TPU_SLO=0``), and ``POST
        /submit`` — the remote dispatch endpoint a
        :class:`~.router.ServingRouter` in another process drives
        (JSON request in, JSON result out, long-polled until the
        forward completes). ``port=0`` picks a free port (read
        ``.port`` back). Closed automatically by :meth:`stop`.

        Unless ``MXNET_TPU_WIRE=0``, a binary dispatch listener
        (:class:`~.wire.WireListener`) starts alongside and its port
        is advertised in ``/healthz`` — wire-capable routers upgrade
        their dispatch transport off that; JSON-only peers keep using
        ``POST /submit``."""
        from ..telemetry.expo import TelemetryServer

        with self._lock:
            if self._queue.closed:
                # stop() already ran (or is draining): a fresh server
                # here would have no one to close it
                raise EngineStoppedError(
                    "cannot expose telemetry on a stopped engine")
            if self._expo is not None:
                return self._expo

            def healthz():
                alive = (self._worker is not None
                         and self._worker.is_alive())
                closed = self._queue.closed
                compiling = self._compiling_since
                wire = self._wire
                return (alive and not closed,
                        {"engine_id": self.engine_id,
                         "worker_alive": alive, "queue_closed": closed,
                         "queue_depth": len(self._queue),
                         "compiling": compiling is not None,
                         "wire_port": (wire.port if wire is not None
                                       else None),
                         # hosted models + versions: the router's seat
                         # model filter AND the canary re-TOFU trigger
                         # (a version flip changes the target token)
                         "models": self._models.versions(),
                         "seconds_since_beat":
                             round(time.monotonic() - self._beat, 3)})

            srv = TelemetryServer(healthz_fn=healthz,
                                  stats_fn=self.snapshot,
                                  submit_fn=self._remote_submit,
                                  warmup_fn=self.warmup_manifest,
                                  costs_fn=self.cost_table,
                                  slo_fn=(self.slo_snapshot
                                          if self._slo is not None
                                          else None),
                                  alerts_fn=(self.alerts_snapshot
                                             if self._slo is not None
                                             else None),
                                  history_fn=(self._history.store
                                              if self._history is not None
                                              else None),
                                  whyslow_fn=self.whyslow,
                                  capture_fn=(self._capture.summary
                                              if self._capture is not None
                                              else None),
                                  port=port, host=host)
            self._expo = srv
            # the binary dispatch listener rides along with the HTTP
            # server (MXNET_TPU_WIRE=0 opts out): /healthz advertises
            # its port so a fronting router upgrades its transport —
            # a bind failure degrades to HTTP dispatch, never to a
            # dead engine
            if envvars.get("MXNET_TPU_WIRE") and self._wire is None:
                from .wire import WireListener
                try:
                    self._wire = WireListener(self, host=host)
                except OSError as e:
                    _events.emit("wire_listen_error",
                                 engine_id=self.engine_id,
                                 error=repr(e))
        # emit/return through the local: a stop() racing in right here
        # may already have swapped self._expo away (and closed it)
        _events.emit("telemetry_expose", engine_id=self.engine_id,
                     port=srv.port, host=srv.host)
        return srv

    def snapshot(self):
        """Stats dict: counters, queue depth, latency percentiles,
        packing efficiency (see metrics.ServingStats).
        ``seconds_since_beat`` is the worker loop's heartbeat age —
        the router's health poll reads it to tell a WEDGED engine
        (alive thread, stuck forward) from a healthy one."""
        out = self.stats.snapshot()
        out["running"] = self.running
        out["bucket_lens"] = list(self._batcher.bucket_lens)
        out["max_rows"] = self._batcher.max_rows
        out["seconds_since_beat"] = round(time.monotonic() - self._beat, 3)
        with self._shapes_lock:
            out["compile_cache"] = dict(self._cc_counts)
            out["manifest_shapes"] = len(self._seen_shapes)
        out["compiling"] = self._compiling_since is not None
        out["costs"] = self.costs.totals()
        out["models"] = self._models.versions()
        out["queue_classes"] = self._queue.depths()
        out["tenants"] = self.tenants.bills()
        return out

    @property
    def alerts(self):
        """The engine's :class:`~mxnet_tpu.telemetry.alerts.
        AlertDaemon` (None when ``MXNET_TPU_SLO=0`` or before
        ``start``) — tests and drills drive ``evaluate_once`` /
        declare extra rules through it."""
        return self._slo

    def slo_snapshot(self):
        """The ``/slo`` body: per declared objective the SLI (or
        windowed value), burn rates over the canonical windows, and
        error budget remaining over the budget window."""
        if self._slo is None:
            return {"owner": self.engine_id, "enabled": False,
                    "objectives": {}}
        return self._slo.evaluator.snapshot()

    def alerts_snapshot(self):
        """The ``/alerts`` body: every rule's state-machine position,
        evidence (burn history, latency exemplars) and the recent
        transition log."""
        if self._slo is None:
            return {"owner": self.engine_id, "enabled": False,
                    "rules": []}
        return self._slo.snapshot()

    def scheduler_state(self):
        """Flight-bundle scheduler section: the WFQ per-class queue
        split + hosted model versions — what was queued for whom when
        the process needed explaining."""
        return {"engine_id": self.engine_id,
                "queue_classes": self._queue.depths(),
                "queue_depth": len(self._queue),
                "models": self._models.versions()}

    def cost_table(self):
        """The ``/costs`` body: this engine's per-bucket cost ledger
        (device/compile seconds, requests, valid tokens, derived
        per-request and per-1k-token rates) plus the cross-bucket
        totals. A fronting router merges these into the fleet table."""
        return {"engine_id": self.engine_id,
                "buckets": self.costs.table(),
                "totals": self.costs.totals()}

    def whyslow(self):
        """The ``/whyslow`` body: per-stage attribution table + top
        stages by share of attributed time (empty, ``enabled:
        false``, when attribution is off — never a 404)."""
        agg = _attribution.get_aggregator(self.engine_id)
        if agg is None:
            return {"owner": self.engine_id,
                    "enabled": _attribution.enabled(),
                    "requests": 0, "stages": [], "top": []}
        return agg.snapshot()

    def _remote_submit(self, payload):
        """``POST /submit`` handler (runs on an exposition-server
        thread): submit + block for the result, JSON-serializable
        either way. Returns ``(http_status, body_dict)`` — admission
        errors carry their class name in ``error_type`` so the remote
        router re-raises the same serving error class. ``engine_ms`` (the
        engine-observed submit→result wall) rides back so the router
        can split its dispatch round trip into engine time vs
        transport overhead — the wire-vs-JSON comparison axis."""
        t0 = time.perf_counter()
        try:
            fut = self.submit(payload["tokens"],
                              payload.get("token_types"),
                              deadline_ms=payload.get("deadline_ms"),
                              trace_id=payload.get("trace_id"),
                              parent_span_id=payload.get("span_id"),
                              model_id=payload.get("model_id"),
                              tenant=payload.get("tenant"),
                              tenant_class=payload.get("tenant_class"))
        except (ServingError, ValueError, LookupError, TypeError) as e:
            name = type(e).__name__
            return (_SUBMIT_ERROR_STATUS.get(name, 400),
                    {"ok": False, "error_type": name, "error": str(e),
                     "engine_id": self.engine_id})
        timeout_s = payload.get("timeout_s") or 600.0
        try:
            out = fut.result(timeout=float(timeout_s))
        except Exception as e:
            name = type(e).__name__
            return (_SUBMIT_ERROR_STATUS.get(name, 500),
                    {"ok": False, "error_type": name, "error": str(e),
                     "trace_id": fut.trace_id,
                     "engine_id": self.engine_id})
        return 200, {"ok": True, "result": np.asarray(out).tolist(),
                     "trace_id": fut.trace_id,
                     "engine_id": self.engine_id,
                     "engine_ms": round(
                         (time.perf_counter() - t0) * 1e3, 3),
                     # amortized cost attribution crosses the wire so
                     # a remote router's caller sees the same bill an
                     # in-process caller would
                     "cost": getattr(fut, "cost", None),
                     "breakdown": getattr(fut, "breakdown", None)}

    # -- watchdog ----------------------------------------------------------
    def _watchdog_probe(self):
        """None while healthy; an anomaly dict when the worker loop
        stopped beating (wedged forward) or the queue sits saturated
        with no dispatch progressing."""
        if not self.running:
            return None
        now = time.monotonic()
        stall = _recorder.stall_seconds()
        if self._compiling_since is not None:
            # a first-visit trace+compile window is open: widen the
            # threshold (ROADMAP carried follow-up) — tens-of-seconds
            # compiles are progress, not a stall, and must not burn
            # flight-recorder bundles; a compile outliving even the
            # grace still trips
            stall += envvars.get("MXNET_TPU_WATCHDOG_COMPILE_GRACE_S")
        since_beat = now - self._beat
        if since_beat > stall:
            return {"kind": "serving_worker_stall",
                    "seconds_since_beat": round(since_beat, 3),
                    "queue_depth": len(self._queue)}
        depth = len(self._queue)
        if (depth >= self._queue.max_depth
                and now - self._last_dispatch > stall):
            return {"kind": "serving_queue_saturated",
                    "queue_depth": depth,
                    "seconds_since_dispatch": round(
                        now - self._last_dispatch, 3)}
        return None

    # -- worker ------------------------------------------------------------
    def _run(self):
        carry = []
        while True:
            self._beat = time.monotonic()
            if self._abort:
                self._fail(carry, EngineStoppedError(
                    "engine stopped before request ran"), "cancelled")
                carry = []
                return
            drained = self._queue.poll(
                self._max_batch_requests - len(carry),
                timeout=0.0 if carry else 0.05)
            if drained and self._batch_wait_s > 0 \
                    and len(carry) + len(drained) < self._max_batch_requests:
                time.sleep(self._batch_wait_s)   # linger for the batch
                drained += self._queue.poll(
                    self._max_batch_requests - len(carry) - len(drained))
            reqs = carry + drained
            carry = []
            if not reqs:
                if self._queue.closed and not len(self._queue):
                    return                       # clean drain complete
                continue
            now = time.monotonic()
            live = []
            for r in reqs:
                if r.expired(now):
                    self.stats.bump("expired")
                    self.tenants.observe_event(
                        r.tenant, r.tenant_class,
                        r.model_id or self._models.default_id(),
                        "expired")
                    _events.emit("request_expired", trace_id=r.trace_id,
                                 waited_ms=round((now - r.t_submit) * 1e3,
                                                 3))
                    self._queue_span(r)
                    r.span.end(error="deadline exceeded before dispatch")
                    r.future.set_exception(DeadlineExceededError(
                        f"request {r.id} deadline exceeded before "
                        "dispatch"))
                else:
                    live.append(r)
            if not live:
                continue
            # one packed batch per MODEL, in first-arrival order: a
            # compiled executable exists per (model, shape), so a
            # batch never mixes models — the WFQ drain order above is
            # preserved within each group
            groups, index = [], {}
            for r in live:
                mid = r.model_id or self._models.default_id()
                if mid not in index:
                    index[mid] = len(groups)
                    groups.append((mid, []))
                groups[index[mid]][1].append(r)
            for mid, members in groups:
                try:
                    t0 = time.perf_counter()
                    with _trace_context(_join_trace_ids(members)):
                        with profiler.Scope("serving/pack"):
                            plan, leftover = self._batcher.plan(members)
                    carry.extend(leftover)
                    pack_t1 = time.perf_counter()
                    self.stats.pack_ms.observe((pack_t1 - t0) * 1e3)
                except Exception as e:  # packing failure: fail the group
                    self._fail(members, e, "failed")
                    continue
                try:
                    self._dispatch(plan, model_id=mid,
                                   pack_interval=(t0, pack_t1))
                except Exception as e:  # model failure: fail ONLY the
                    # dispatched batch's unfulfilled requests and keep
                    # serving — carry was never in this batch and gets
                    # its try next iteration (one poison batch must not
                    # take the engine or innocent leftovers down)
                    self._fail([r for r, _ in plan.entries
                                if not r.future.done()], e, "failed")

    def _fail(self, requests, exc, counter):
        for r in requests:
            self.stats.bump(counter)
            self.tenants.observe_event(
                r.tenant, r.tenant_class,
                r.model_id or self._models.default_id(), counter)
            r.span.end(error=repr(exc))
            r.future.set_exception(exc)

    def _queue_span(self, req):
        """Synthesized queue-wait child span (submit → drain)."""
        if req.t_drain is not None and req.span.span_id is not None:
            _spans.record_span("serving/queue", req.trace_id,
                               parent_id=req.span.span_id,
                               mono_start=req.t_submit,
                               mono_end=req.t_drain,
                               attrs={"engine": self.engine_id})

    def _bump_cc(self, result):
        with self._shapes_lock:
            self._cc_counts[result] += 1
        self._compile_cache[result].inc()

    def _compile_forward(self, plan, fn=None):
        """First-visit forward: open the compile window (watchdog
        grace) and classify the outcome against the jax cache events
        — a disk-served compile (persistent_hit: trace + cache fetch)
        vs a fresh backend build (miss). The event tally is process-
        global, so a CONCURRENT compile on another engine can only
        downgrade a true persistent_hit to miss (its miss events leak
        into this window), never invent one — the warm-restart signal
        stays conservative. Returns (seq, result, t0, t1)."""
        cc_before = compile_cache.events_snapshot()
        self._compiling_since = time.monotonic()
        t0 = time.perf_counter()
        try:
            seq = self._forward(plan, fn)
        finally:
            # refresh the heartbeat IN the same step that closes the
            # window: a probe (or the router's wedge check) must never
            # see the compile flag already cleared while the beat is
            # still as old as the whole compile
            self._beat = time.monotonic()
            self._compiling_since = None
        t1 = time.perf_counter()
        result = compile_cache.classify(
            cc_before, compile_cache.events_snapshot())
        self._bump_cc(result)
        return seq, result, t0, t1

    def _dispatch(self, plan, model_id=None, pack_interval=None):
        mid, fn = self._models.resolve(model_id)
        shape = (mid, plan.rows, plan.row_len)
        with self._shapes_lock:
            hit = shape in self._seen_shapes
        if hit:
            self._bump_cc("memory_hit")
            t0 = time.perf_counter()
            seq = self._forward(plan, fn)
            t1 = time.perf_counter()
            dt_ms = (t1 - t0) * 1e3
            self.stats.compute_ms.observe(dt_ms)
        else:
            _events.emit("compile_begin", engine_id=self.engine_id,
                         model=mid, rows=plan.rows,
                         row_len=plan.row_len)
            seq, result, t0, t1 = self._compile_forward(plan, fn)
            dt_ms = (t1 - t0) * 1e3
            # first visit pays trace+compile; report it as compile
            # latency, not as a (wildly misleading) compute sample
            with self._shapes_lock:
                self._seen_shapes.add(shape)
            self.stats.bump("compiles")
            self.stats.compile_ms.observe(dt_ms)
            _events.emit("compile_end", engine_id=self.engine_id,
                         model=mid, rows=plan.rows,
                         row_len=plan.row_len,
                         result=result, ms=round(dt_ms, 3))
        dt_s = t1 - t0
        self.costs.observe_batch(plan.row_len, dt_s, len(plan.entries),
                                 plan.valid_tokens, compiled=not hit)
        self.stats.observe_batch(plan.rows, plan.row_len,
                                 plan.valid_tokens, len(plan.entries),
                                 plan.row_len)
        # one line per batch (not per request): every served request's
        # trace id is findable in the event log without per-request spam
        _events.emit("batch_dispatch", engine_id=self.engine_id,
                     model=mid, rows=plan.rows,
                     row_len=plan.row_len, requests=len(plan.entries),
                     valid_tokens=plan.valid_tokens, ms=round(dt_ms, 3),
                     trace_ids=[r.trace_id for r, _ in plan.entries])
        self._last_dispatch = time.monotonic()
        now = time.monotonic()
        # per-request span trees: batch stages (pack, compile/forward)
        # time ONCE, but every member request's tree shows them — the
        # acceptance shape submit → queue → pack → compile/forward →
        # complete under one trace id
        fwd_name = "serving/forward" if hit else "serving/compile"
        fwd_attrs = {"rows": plan.rows, "row_len": plan.row_len,
                     "requests": len(plan.entries), "compiled": not hit,
                     "engine": self.engine_id}
        for req, pl in plan.entries:
            # amortized cost attribution: the batch's forward wall,
            # split by token share, rides the future so callers (and
            # the router/loadgen cross-checks) see what THIS request
            # cost the device. Shares sum to the batch time exactly —
            # the ledger-exactness contract. Written before pool/
            # result so even a failing postprocess keeps its bill.
            share = (pl.length / plan.valid_tokens
                     if plan.valid_tokens else 0.0)
            req.future.cost = {"engine_id": self.engine_id,
                               "bucket": plan.row_len,
                               "model": mid,
                               "tenant": req.tenant,
                               "tenant_class": req.tenant_class,
                               "device_s": dt_s * share,
                               "compiled": not hit,
                               "tokens": pl.length,
                               "batch_requests": len(plan.entries)}
            self.tenants.observe_cost(req.tenant, req.tenant_class,
                                      mid, dt_s * share, pl.length)
            record_spans = req.span.span_id is not None
            if record_spans:
                self._queue_span(req)
                if pack_interval is not None:
                    _spans.record_span(
                        "serving/pack", req.trace_id,
                        parent_id=req.span.span_id,
                        start_us=int(pack_interval[0] * 1e6),
                        end_us=int(pack_interval[1] * 1e6),
                        attrs={"engine": self.engine_id})
                _spans.record_span(fwd_name, req.trace_id,
                                   parent_id=req.span.span_id,
                                   start_us=int(t0 * 1e6),
                                   end_us=int(t1 * 1e6),
                                   attrs=fwd_attrs)
            # stage stamps for the critical-path breakdown (wfq_wait
            # was stamped at drain). pack/t0/t1 were timed with
            # perf_counter for the span axis; the breakdown's wall
            # endpoints are time.monotonic(), so map them across —
            # the clocks share CLOCK_MONOTONIC on Linux but not
            # everywhere, and a mismatched epoch clips every interval
            # outside the wall (100% unattributed, silently).
            # The stage spans themselves are skipped — the legacy
            # serving/pack + serving/forward children already carry
            # the same intervals in the tree.
            if req.stages is not None:
                if pack_interval is not None:
                    _attribution.stamp(
                        req, "pack",
                        _spans.perf_to_mono(pack_interval[0]),
                        _spans.perf_to_mono(pack_interval[1]),
                        span=False)
                _attribution.stamp(
                    req, "compute" if hit else "compile",
                    _spans.perf_to_mono(t0), _spans.perf_to_mono(t1),
                    span=False)
            try:
                out = self._pool(
                    seq[pl.row, pl.offset:pl.offset + pl.length], req)
            except Exception as e:  # a bad pool callable fails ITS
                # request, not the rest of the batch
                self.stats.bump("failed")
                req.span.end(error=repr(e))
                if self._capture is not None:
                    self._capture.record_request(
                        req, None, "failed",
                        (now - req.t_submit) * 1e3, model=mid,
                        version=self._models.versions().get(mid),
                        engine_id=self.engine_id)
                req.future.set_exception(e)
                continue
            req.t_done = now
            self.stats.queue_ms.observe((req.t_drain - req.t_submit) * 1e3)
            total_ms = (now - req.t_submit) * 1e3
            # OpenMetrics exemplar: links a firing latency alert
            # straight to a RETRIEVABLE trace at /traces/<id>
            self.stats.total_ms.observe(
                total_ms, exemplar=slow_exemplar(
                    req.trace_id, total_ms, self._exemplars))
            self.stats.bump("completed")
            self.tenants.observe_event(req.tenant, req.tenant_class,
                                       mid, "completed")
            self.tenants.observe_latency(req.tenant, req.tenant_class,
                                         mid, total_ms)
            if record_spans:
                _spans.record_span("serving/complete", req.trace_id,
                                   parent_id=req.span.span_id,
                                   start_us=int(t1 * 1e6),
                                   attrs={"engine": self.engine_id})
            if req.stages is not None:
                breakdown = _attribution.breakdown_from_stamps(
                    req.stages, req.t_submit, now,
                    trace_id=req.trace_id)
                req.future.breakdown = breakdown
                _attribution.aggregator(self.engine_id).observe(
                    breakdown, tenant_class=req.tenant_class,
                    model=mid, trace_id=req.trace_id)
            req.span.end()
            # capture AFTER breakdown/cost landed on the future (the
            # record carries both) and BEFORE the result fires, so a
            # caller observing completion finds its record durable
            if self._capture is not None:
                self._capture.record_request(
                    req, out, "completed", total_ms, model=mid,
                    version=self._models.versions().get(mid),
                    engine_id=self.engine_id)
            req.future.set_result(out)

    def _forward(self, plan, fn=None):
        ids = nd.array(plan.data, dtype="int32", ctx=self._ctx)
        tt = nd.array(plan.token_types, dtype="int32", ctx=self._ctx)
        vl = nd.array(plan.valid_length, dtype="int32", ctx=self._ctx)
        seg = nd.array(plan.segment_ids, dtype="int32", ctx=self._ctx)
        pos = nd.array(plan.positions, dtype="int32", ctx=self._ctx)
        model = fn if fn is not None else self._model
        # the batch adopts its requests' trace ids so the forward span
        # in the Chrome trace / xprof names every request it served
        with self._forward_lock:
            with _trace_context(
                    _join_trace_ids(r for r, _ in plan.entries)):
                with autograd.predict_mode():
                    with profiler.Scope("serving/forward"):
                        out = model(ids, tt, vl, seg, pos)
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out.asnumpy()   # host sync: per-request slicing follows

    def _forward_shape(self, rows, row_len, model_id=None, fn=None):
        """One dummy forward at (rows, row_len) — warmup helper.
        Counts in the compile-cache split like a live dispatch (a
        manifest replay against a primed persistent cache records
        ``persistent_hit``s — the warm-restart acceptance signal).

        With an explicit ``fn`` (the hot-swap warm-replay: a NEW model
        version not yet in the registry) the forward always takes the
        compile path and the shape is NOT marked seen — it already is
        under its model id, and the incoming version must not poison
        the seen-set if its replay fails mid-swap."""
        from .batcher import PackedPlan

        data = np.zeros((rows, row_len), np.int32)
        seg = np.zeros((rows, row_len), np.int32)
        seg[:, 0] = 1
        plan = PackedPlan(data, np.zeros_like(data), seg,
                          np.zeros_like(data), np.ones(rows, np.int32),
                          entries=[], pad_rows=rows)
        if fn is not None:
            _seq, _result, t0, t1 = self._compile_forward(plan, fn)
            self.costs.observe_warmup(row_len, t1 - t0, compiled=True)
            return
        mid, _fn = self._models.resolve(model_id)
        shape = (mid, rows, row_len)
        with self._shapes_lock:
            seen = shape in self._seen_shapes
        if seen:
            t0 = time.perf_counter()
            self._forward(plan, _fn)
            self.costs.observe_warmup(row_len, time.perf_counter() - t0,
                                      compiled=False)
            self._bump_cc("memory_hit")
        else:
            _seq, _result, t0, t1 = self._compile_forward(plan, _fn)
            self.costs.observe_warmup(row_len, t1 - t0, compiled=True)
            # mark seen only AFTER the forward succeeded: a failed
            # warmup replay must leave the shape cold so the first
            # live dispatch still gets the compile path (grace window
            # + compile_ms accounting), not a phantom memory_hit
            with self._shapes_lock:
                self._seen_shapes.add(shape)
