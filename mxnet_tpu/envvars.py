"""Typed registry of every ``MXNET_TPU_*`` environment variable.

The reference documented its ~80 ``MXNET_*`` knobs in one hand-written
faq page (``docs/faq/env_var.md``) and read them ad hoc all over the C++
tree; the TPU backend grew the same scatter (31 ``MXNET_TPU_*`` reads
across kernels, dist, serving and telemetry) until this module. Now:

- every variable is DECLARED here once — name, type, default, doc,
  subsystem scope — and READ here only: :func:`get` returns the parsed,
  typed value (or the declared default), :func:`get_raw` the raw string.
  ``tools/mxlint``'s ``env-raw-read`` pass forbids raw ``os.environ``
  access to ``MXNET_TPU_*`` names anywhere else in ``mxnet_tpu/`` and
  ``tools/``, and its ``env-unregistered`` check rejects :func:`get`
  calls for names not declared here;
- the README "Configuration reference" table is GENERATED from this
  registry (``python -m tools.mxlint --write-envdoc``) and the mxlint
  gate fails when a registered variable is missing from it — the docs
  cannot go stale silently.

This module must stay stdlib-only and import nothing from the package:
``mxnet_tpu/__init__.py`` reads ``MXNET_TPU_MATMUL_PRECISION`` through
it before jax is even configured.

Parsing conventions: ``bool`` treats ``"" / 0 / false / no / off``
(case-insensitive) as False and anything else as True; ``int`` and
``float`` fall back to the declared default on an empty value. A
variable with default ``None`` reads as ``None`` when unset — call
sites own their fallback chain (e.g. the ``DMLC_*`` compat names).
"""
from __future__ import annotations

import os
from collections import OrderedDict

__all__ = ["EnvVar", "ENVVARS", "register", "get", "get_raw", "is_set",
           "all_vars", "markdown_table"]

_FALSY = ("", "0", "false", "no", "off")


class EnvVar:
    """One declared variable: its name, value type (``bool``/``int``/
    ``float``/``str``/``path``), default, one-line doc, and subsystem
    scope (groups the generated reference table)."""

    __slots__ = ("name", "vtype", "default", "doc", "scope")

    def __init__(self, name, vtype, default, doc, scope):
        if not name.startswith("MXNET_TPU_"):
            raise ValueError(f"{name!r} is not an MXNET_TPU_* variable")
        if vtype not in ("bool", "int", "float", "str", "path"):
            raise ValueError(f"unknown env var type {vtype!r}")
        self.name = name
        self.vtype = vtype
        self.default = default
        self.doc = doc
        self.scope = scope

    def parse(self, raw):
        """Raw string → typed value (the declared default when the
        value is empty or unparsable — a typo'd knob must degrade to
        documented behavior, not crash process startup)."""
        if raw is None:
            return self.default
        raw = raw.strip()
        if self.vtype == "bool":
            return raw.lower() not in _FALSY
        if raw == "":
            return self.default
        try:
            if self.vtype == "int":
                return int(raw, 0)
            if self.vtype == "float":
                return float(raw)
        except ValueError:
            return self.default
        return raw      # str / path

    def describe_default(self):
        if self.default is None:
            return "unset"
        if self.vtype == "bool":
            return "on" if self.default else "off"
        return str(self.default)


#: declaration order is documentation order (grouped by scope)
ENVVARS: "OrderedDict[str, EnvVar]" = OrderedDict()


def register(name, vtype, default, doc, scope="runtime"):
    if name in ENVVARS:
        raise ValueError(f"env var {name} registered twice")
    var = EnvVar(name, vtype, default, doc, scope)
    ENVVARS[name] = var
    return var


def get(name):
    """The typed value of a registered variable (its default when
    unset). Raises ``KeyError`` for undeclared names — registering here
    IS the act of creating a configuration knob."""
    return ENVVARS[name].parse(os.environ.get(name))


def get_raw(name):
    """The raw string (None when unset) of a registered variable — for
    fallback chains that must distinguish unset from falsy values."""
    ENVVARS[name]            # undeclared names fail just like get()
    return os.environ.get(name)


def is_set(name):
    ENVVARS[name]
    return name in os.environ


def all_vars():
    return list(ENVVARS.values())


# ---------------------------------------------------------------------------
# the registry — one entry per variable, grouped by subsystem
# ---------------------------------------------------------------------------

# -- core runtime -----------------------------------------------------------
register("MXNET_TPU_SYMBOLIC_JIT", "bool", True,
         "compiled symbolic executor for Module/simple_bind; ``0`` falls "
         "back to the eager per-op DAG walk (bug-bisection ladder)",
         scope="runtime")
register("MXNET_TPU_MATMUL_PRECISION", "str", "high",
         "f32 matmul precision: ``high`` = multi-pass bf16 (~f32 "
         "accuracy), ``default`` = fastest single-pass bf16",
         scope="runtime")
register("MXNET_TPU_MODEL_STORE", "path", None,
         "model-zoo download/cache root (falls back to "
         "``$MXNET_HOME/models``, then ``~/.mxnet/models``)",
         scope="runtime")

# -- persistent compilation cache -------------------------------------------
register("MXNET_TPU_COMPILE_CACHE", "bool", True,
         "persistent on-disk XLA compilation cache, configured at "
         "CachedOp trace / executor bind time; ``0`` disables — every "
         "process then recompiles every shape from scratch",
         scope="compile_cache")
register("MXNET_TPU_COMPILE_CACHE_DIR", "path", None,
         "persistent compile-cache directory, used when jax's own "
         "``JAX_COMPILATION_CACHE_DIR`` is unset (default: the fixed "
         "``<checkout>/.jax_cache``); share it across engine "
         "processes so restarts reuse each other's executables",
         scope="compile_cache")
register("MXNET_TPU_COMPILE_CACHE_MIN_S", "float", 1.0,
         "only compiles slower than this many seconds are persisted "
         "(``0`` persists everything — tests use it to force "
         "cross-process hits)", scope="compile_cache")
register("MXNET_TPU_WARMUP_MANIFEST", "path", None,
         "warmup-manifest path: the serving router persists the "
         "fleet-union visited-shape manifest here, and a restarting "
         "engine replays it via ``warmup(manifest=...)`` before "
         "admitting traffic", scope="compile_cache")

# -- Pallas kernels ---------------------------------------------------------
register("MXNET_TPU_PALLAS_INTERPRET", "bool", False,
         "run Pallas kernels in interpret mode (off-TPU kernel testing)",
         scope="kernels")
register("MXNET_TPU_DISABLE_PALLAS", "bool", False,
         "force the plain jnp/XLA lowering for every fused-kernel op",
         scope="kernels")

# -- distributed ------------------------------------------------------------
register("MXNET_TPU_COORDINATOR", "str", None,
         "jax.distributed coordinator ``host:port`` (set by "
         "``tools/launch.py``; ``DMLC_PS_ROOT_URI``/``_PORT`` accepted "
         "for script compat)", scope="dist")
register("MXNET_TPU_NUM_PROCS", "int", None,
         "world size for multi-process rendezvous (``DMLC_NUM_WORKER`` "
         "compat fallback)", scope="dist")
register("MXNET_TPU_PROC_ID", "int", None,
         "this process's rank (``DMLC_WORKER_ID`` compat fallback)",
         scope="dist")
register("MXNET_TPU_LOCAL_RANK", "int", 0,
         "rank within this host (set per worker by ``tools/launch.py``; "
         "horovod-shim ``local_rank``)", scope="dist")

# -- serving dispatch wire --------------------------------------------------
register("MXNET_TPU_WIRE", "bool", True,
         "binary dispatch wire: ``ServingEngine.expose()`` starts the "
         "typed-frame dispatch listener next to the HTTP server, and a "
         "``ServingRouter`` upgrades remote seats that advertise a "
         "``wire_port`` to persistent multiplexed connections; ``0`` "
         "keeps dispatch on the HTTP/JSON long-poll only", scope="wire")
register("MXNET_TPU_WIRE_PORT", "int", 0,
         "engine dispatch-listener port (``0`` picks a free port; the "
         "bound port is advertised at ``/healthz`` as ``wire_port``). "
         "A taken configured port falls back to ephemeral with a "
         "``wire_port_fallback`` event", scope="wire")
register("MXNET_TPU_WIRE_CONNS", "int", 2,
         "persistent multiplexed wire connections a router keeps per "
         "wire-capable engine (one reader thread each demuxes replies "
         "by correlation id)", scope="wire")
register("MXNET_TPU_WIRE_TIMEOUT_S", "float", 5.0,
         "wire connect/handshake timeout and the grace added on top "
         "of the dispatch timeout before an unanswered in-flight "
         "request is failed over", scope="wire")
register("MXNET_TPU_WIRE_MAX_FRAME_MB", "int", 256,
         "dispatch-wire frame size cap in MiB (length-bomb guard; a "
         "larger prefix refuses the connection before allocating — "
         "the dist_async channel keeps its own 8 GiB cap)",
         scope="wire")
register("MXNET_TPU_WIRE_HTTP_POOL", "int", 8,
         "bounded waiter threads per remote seat for the HTTP/JSON "
         "fallback dispatch path (the legacy thread-per-in-flight-"
         "request shape could thread-bomb under load spikes)",
         scope="wire")

# -- decode serving: paged KV cache + continuous decode batching ------------
register("MXNET_TPU_KV_PAGE_SIZE", "int", 16,
         "tokens per paged-KV-cache page (``serving/kvcache.py``): the "
         "allocation granule of the decode engine's attention memory; "
         "multiples of 8 keep the page a whole sublane tile on TPU",
         scope="decode")
register("MXNET_TPU_KV_PAGES", "int", 256,
         "paged-KV-cache pool capacity in pages, preallocated per "
         "layer at engine start (+1 internal scratch page); an "
         "exhausted pool defers decode joins instead of failing them",
         scope="decode")
register("MXNET_TPU_DECODE_ROWS", "int", 8,
         "decode-batch slot cap (``DecodeEngine`` default max "
         "concurrent sequences; row counts quantize to powers of two "
         "up to this, one compiled step per (rows, table-width) "
         "bucket)", scope="decode")
register("MXNET_TPU_DECODE_MAX_NEW_TOKENS", "int", 64,
         "default generation cap for decode requests that bring no "
         "``max_new_tokens`` of their own", scope="decode")
register("MXNET_TPU_DECODE_DONATE", "bool", True,
         "thread ``jax.jit(..., donate_argnums=...)`` through the "
         "decode/prefill steps so the KV page pool updates in place "
         "(no per-step cache-sized allocation); ``0`` copies — the "
         "A/B knob for the donation win", scope="decode")
register("MXNET_TPU_DECODE_PREFILLS_PER_ITER", "int", 1,
         "prompt prefills admitted per decode-loop iteration: bounds "
         "how long the running decode batch can stall behind prefill "
         "work (the prefill/decode split-scheduling knob)",
         scope="decode")
register("MXNET_TPU_DECODE_PREFILL_BUDGET", "int", 64,
         "prompt tokens prefilled per decode-loop iteration: prompts "
         "are split into kernel-sized chunks interleaved at iteration "
         "boundaries, so a long prompt never stalls the running batch "
         "for more than one chunk; ``0`` restores whole-prompt dense "
         "prefill (the chunked-prefill A/B baseline)", scope="decode")
register("MXNET_TPU_KV_PREFIX", "bool", True,
         "prefix KV cache reuse (``serving/kvcache.py``): prompts "
         "sharing a token prefix share its full KV pages read-only "
         "(refcounted, copy-on-write on divergence); ``0`` disables — "
         "the prefix-reuse A/B knob. Needs chunked prefill "
         "(``MXNET_TPU_DECODE_PREFILL_BUDGET`` > 0) to take effect",
         scope="decode")
register("MXNET_TPU_KV_PREFIX_PAGES", "int", 64,
         "bounded LRU capacity of the prefix-KV index, in entries "
         "(one full page each); eviction unpins the page, which "
         "recycles once no live sequence references it",
         scope="decode")
register("MXNET_TPU_DECODE_TEMPERATURE", "float", 0.0,
         "default decode sampling temperature for requests that bring "
         "none: ``0`` is greedy argmax — deterministic by "
         "construction, the byte-reproducible solo-parity lever",
         scope="decode")
register("MXNET_TPU_DECODE_TOP_K", "int", 0,
         "default top-k sampling cutoff for decode requests (``0`` = "
         "no top-k truncation; only applies when temperature > 0)",
         scope="decode")
register("MXNET_TPU_DECODE_TOP_P", "float", 1.0,
         "default nucleus (top-p) sampling mass for decode requests "
         "(``1.0`` = no truncation; only applies when temperature "
         "> 0)", scope="decode")
register("MXNET_TPU_SLO_INTER_TOKEN_MS", "float", 250.0,
         "decode inter-token latency bound for the default "
         "``decode_inter_token`` LatencySLO (p-target reuses "
         "``MXNET_TPU_SLO_LATENCY_TARGET``)", scope="slo")

# -- telemetry: events / spans ----------------------------------------------
register("MXNET_TPU_EVENT_LOG", "path", None,
         "structured JSONL run-event log path (a directory gets one "
         "``events-<pid>.jsonl`` per process)", scope="telemetry")
register("MXNET_TPU_EVENT_LOG_MAX_MB", "float", None,
         "rotate the event log at this size (MB); unset = no rotation",
         scope="telemetry")
register("MXNET_TPU_EVENT_LOG_KEEP", "int", 3,
         "rotated event-log files kept (``read_events`` reads across "
         "rotations)", scope="telemetry")
register("MXNET_TPU_SPANS", "bool", True,
         "span recording (tail-sampled request tracing); ``0`` disables "
         "— the ring is bounded either way", scope="telemetry")
register("MXNET_TPU_TRACE_SLOW_MS", "float", 250.0,
         "tail-sampling keep threshold: traces whose local root ran "
         "longer are kept in full", scope="telemetry")
register("MXNET_TPU_TRACE_BUFFER", "int", 64,
         "kept-trace ring size", scope="telemetry")
register("MXNET_TPU_TRACE_MAX_SPANS", "int", 256,
         "per-trace span cap (a leaked trace cannot grow the process)",
         scope="telemetry")
register("MXNET_TPU_TRACE_MAX_ACTIVE", "int", 256,
         "in-flight (not yet sampled) trace buffer cap",
         scope="telemetry")
register("MXNET_TPU_ATTRIBUTION", "bool", True,
         "per-request critical-path stage attribution (stage spans, "
         "``InferenceFuture.breakdown``, the ``/whyslow`` aggregator); "
         "``0`` — or spans off — disables: no stamps, no families, no "
         "threads", scope="telemetry")
register("MXNET_TPU_ATTRIBUTION_WINDOW", "int", 2048,
         "per-stage sample window behind the ``/whyslow`` windowed "
         "p99 (per (stage, tenant_class, model) cell)",
         scope="telemetry")
register("MXNET_TPU_ATTRIBUTION_TOP", "int", 3,
         "stages ranked in ``/whyslow``'s ``top`` table and attached "
         "to firing latency alert payloads", scope="telemetry")

# -- telemetry: continuous profiler / resource accounting -------------------
register("MXNET_TPU_PROF", "bool", True,
         "always-on continuous sampling profiler daemon (Google-Wide-"
         "Profiling style): started by serving engines/routers, "
         "samples every thread's Python stack into bounded "
         "folded-stack counts served at ``/profile``; ``0`` disables",
         scope="telemetry")
register("MXNET_TPU_PROF_HZ", "float", 19.0,
         "continuous-profiler sampling rate (Hz); the odd default "
         "avoids phase-locking with 1 s/100 ms periodic work",
         scope="telemetry")
register("MXNET_TPU_PROF_MAX_STACKS", "int", 2048,
         "distinct (thread, folded-stack) entries kept by the "
         "continuous profiler; overflow folds into a per-thread "
         "``(stack-table-full)`` bucket so totals stay honest",
         scope="telemetry")
register("MXNET_TPU_PROF_MAX_DEPTH", "int", 48,
         "frames kept per sampled stack (deepest callees win)",
         scope="telemetry")
register("MXNET_TPU_PROF_RESOURCE_S", "float", 1.0,
         "period of the resource-gauge sweep (host RSS/fds/threads + "
         "device memory) the profiler daemon runs between stack "
         "samples", scope="telemetry")

# -- telemetry: flight recorder / watchdog ----------------------------------
register("MXNET_TPU_FLIGHT_DIR", "path", None,
         "flight-recorder bundle directory (default "
         "``./mxnet_tpu_flight``)", scope="telemetry")
register("MXNET_TPU_WATCHDOG", "bool", True,
         "the stall-watchdog daemon thread; ``0`` disables",
         scope="telemetry")
register("MXNET_TPU_WATCHDOG_INTERVAL_S", "float", 5.0,
         "watchdog probe poll period (seconds)", scope="telemetry")
register("MXNET_TPU_WATCHDOG_STALL_S", "float", 30.0,
         "shared stall threshold watchdog probes compare against "
         "(seconds)", scope="telemetry")
register("MXNET_TPU_WATCHDOG_COMPILE_GRACE_S", "float", 300.0,
         "extra stall allowance while a serving engine has a "
         "first-visit trace+compile window open — first-visit "
         "compiles must not trip flight-recorder bundles",
         scope="telemetry")

# -- SLOs / alerting --------------------------------------------------------
register("MXNET_TPU_SLO", "bool", True,
         "in-process SLO engine: serving engines/routers register "
         "their default objectives (latency quantile, availability, "
         "cost budget, engine-up fraction) and the alert daemon "
         "evaluates multi-window burn-rate / threshold / absence "
         "rules against them; ``0`` disables evaluation, exemplar "
         "recording and the ``/alerts``+``/slo`` endpoints",
         scope="slo")
register("MXNET_TPU_SLO_EVAL_S", "float", 5.0,
         "alert-daemon evaluation period (seconds)", scope="slo")
register("MXNET_TPU_SLO_WINDOW_SCALE", "float", 1.0,
         "multiplier on every SLO window (burn-rate long/short "
         "windows, pending durations, error-budget window) — drills "
         "and tests shrink hours to seconds with one knob",
         scope="slo")
register("MXNET_TPU_SLO_BUDGET_S", "float", 2592000.0,
         "error-budget accounting window in seconds (default 30 "
         "days; clipped to process uptime)", scope="slo")
register("MXNET_TPU_SLO_LATENCY_MS", "float", 1000.0,
         "default serving latency objective: requests must complete "
         "under this many milliseconds (snapped up to the nearest "
         "histogram bucket boundary)", scope="slo")
register("MXNET_TPU_SLO_LATENCY_TARGET", "float", 0.99,
         "fraction of requests that must meet the latency objective "
         "(the quantile, as a ratio target)", scope="slo")
register("MXNET_TPU_SLO_AVAILABILITY_TARGET", "float", 0.999,
         "availability objective: fraction of requests that must "
         "complete (not shed, not errored, not expired)", scope="slo")
register("MXNET_TPU_SLO_COST_S_PER_1K", "float", None,
         "cost objective: device seconds per 1k valid tokens budget "
         "(unset = cost objective off; set it from a measured "
         "baseline)", scope="slo")
register("MXNET_TPU_SLO_ENGINE_UP_FRACTION", "float", 0.5,
         "router fleet objective: alert when fewer than this "
         "fraction of registered engines is routable", scope="slo")
register("MXNET_TPU_SLO_EXEMPLARS", "bool", True,
         "record (latency bucket, trace_id) exemplar pairs on the "
         "serving/router total-latency histograms, rendered "
         "OpenMetrics-style in the text exposition and surfaced on "
         "``/alerts``; ``0`` skips the per-request exemplar write",
         scope="slo")
register("MXNET_TPU_ALERT_RESOLVED_KEEP_S", "float", 300.0,
         "how long a resolved alert stays listed on ``/alerts`` "
         "before decaying to inactive", scope="slo")
register("MXNET_TPU_ALERT_HISTORY", "int", 128,
         "alert state-transition history ring size (served on "
         "``/alerts``, carried into flight bundles)", scope="slo")

# -- synthetic canaries -----------------------------------------------------
register("MXNET_TPU_CANARY", "bool", True,
         "black-box canary prober: a router-side daemon submits "
         "synthetic golden requests to every seat from outside (over "
         "the binary wire and the HTTP dispatch path, round-robined), "
         "checks responses against the golden checksum, and feeds the "
         "per-seat canary-absence page rule; ``0`` spawns no thread "
         "and registers no ``mxnet_tpu_canary_*`` families",
         scope="canary")
register("MXNET_TPU_CANARY_INTERVAL_S", "float", 1.0,
         "canary probe round period (seconds between rounds; every "
         "seat is probed once per round)", scope="canary")
register("MXNET_TPU_CANARY_TIMEOUT_S", "float", 10.0,
         "per-probe completion timeout: a probe still unanswered after "
         "this long counts ``timeout`` (a wedged seat answers nothing "
         "— exactly what the absence rule pages on)", scope="canary")
register("MXNET_TPU_CANARY_ABSENCE_S", "float", 300.0,
         "canary-absence window in pre-scale seconds: no successful "
         "canary against a seat for this long (scaled by "
         "``MXNET_TPU_SLO_WINDOW_SCALE``) pages even when the seat "
         "self-reports healthy", scope="canary")

# -- SLO-aware routing ------------------------------------------------------
register("MXNET_TPU_ROUTER_WEIGHTS", "bool", True,
         "SLO-aware routing weights: the router's health poll folds "
         "per-seat burn rate (``/slo``), windowed device-s/1k-tokens "
         "drift and canary latency into a smoothed per-seat weight "
         "the least-outstanding picker divides by — a seat burning "
         "its error budget sheds traffic smoothly, with hysteresis; "
         "``0`` pins every weight at 1.0 (classic least-outstanding)",
         scope="routing")
register("MXNET_TPU_ROUTER_WEIGHT_FLOOR", "float", 0.05,
         "minimum routing weight for a degraded seat — a trickle of "
         "traffic keeps flowing so recovery is observable (0.05 = "
         "one twentieth of a full share)", scope="routing")
register("MXNET_TPU_ROUTER_WEIGHT_GAIN", "float", 0.4,
         "per-poll smoothing gain toward the weight target (1.0 = "
         "jump immediately, small = glacial)", scope="routing")

# -- multi-tenant, multi-model serving --------------------------------------
register("MXNET_TPU_TENANT_WEIGHTS", "str", None,
         "WFQ admission-class weights as ``class:weight`` pairs "
         "(overlays the 4/2/1 default, e.g. "
         "``priority:8,best-effort:1``): the queue dequeues classes "
         "in proportion to weight under contention", scope="tenancy")
register("MXNET_TPU_TENANT_DEPTH_SHARES", "str", None,
         "per-class admission-queue depth budgets as fractions of "
         "``max_depth`` (``class:share`` pairs, default 1.0 each — "
         "e.g. ``best-effort:0.5`` caps best-effort at half the "
         "queue even before WFQ eviction kicks in)", scope="tenancy")
register("MXNET_TPU_TENANT_DEADLINE_MS", "str", None,
         "per-class DEFAULT deadlines (ms) for requests that bring "
         "none (``class:ms`` pairs, e.g. ``best-effort:2000``): "
         "under overload, expiry consumes the short-deadline classes "
         "first", scope="tenancy")
register("MXNET_TPU_TENANT_SLO_MS", "str", None,
         "per-class total-latency SLO thresholds (ms) for the "
         "``default_tenant_objectives`` set (``class:ms`` pairs; "
         "classes not listed default to 0.5x / 1x / 4x the serving "
         "latency bound for priority/standard/best-effort)",
         scope="tenancy")
register("MXNET_TPU_MODEL_DEFAULT", "str", "default",
         "model id a single-model engine registers under and a "
         "model-less submit targets — the backward-compat identity "
         "of the pre-registry fleet", scope="tenancy")

# -- router active/active HA ------------------------------------------------
register("MXNET_TPU_ROUTER_HA", "bool", True,
         "router active/active HA: with a peer configured, every "
         "admitted request is journaled (correlation id + payload) "
         "to the peer over the wire before dispatch, and a dead "
         "router's survivor adopts the orphaned in-flight requests "
         "front-of-queue; ``0`` disables journaling and the HA "
         "listener entirely", scope="ha")
register("MXNET_TPU_ROUTER_HA_PEER", "str", None,
         "the PEER router's exposition base URL (e.g. "
         "``http://host:9200``): liveness is polled off its "
         "``/healthz`` (which advertises ``ha_port``) and the journal "
         "link connects to that port", scope="ha")
register("MXNET_TPU_ROUTER_HA_PORT", "int", 0,
         "this router's HA journal-listener port (``0`` picks a free "
         "port, advertised at ``/healthz`` as ``ha_port``); setting "
         "it non-zero also starts the listener without a configured "
         "outbound peer (asymmetric HA)", scope="ha")
register("MXNET_TPU_ROUTER_HA_JOURNAL", "int", 4096,
         "peer-journal capacity (in-flight requests held for the "
         "peer); past it the OLDEST entry is dropped (counted "
         "``journal_drop``)", scope="ha")
register("MXNET_TPU_ROUTER_HA_ACK_S", "float", 1.0,
         "bounded wait for the peer's journal ack before a request "
         "becomes dispatchable (the durability cost of zero-loss); "
         "an ack miss degrades that request to unjournaled",
         scope="ha")

# -- autoscaler -------------------------------------------------------------
register("MXNET_TPU_AUTOSCALE", "bool", True,
         "fleet autoscaler enable gate: a constructed "
         "``FleetAutoscaler`` spawns/retires engine seats from "
         "sustained burn rate + queue depth and replaces dead seats "
         "with manifest-warmed engines; ``0`` makes ``start()`` a "
         "no-op (no thread)", scope="autoscale")
register("MXNET_TPU_AUTOSCALE_MIN", "int", 1,
         "minimum seats the autoscaler keeps (scale-down floor)",
         scope="autoscale")
register("MXNET_TPU_AUTOSCALE_MAX", "int", 4,
         "maximum seats the autoscaler grows to (scale-up ceiling)",
         scope="autoscale")
register("MXNET_TPU_AUTOSCALE_INTERVAL_S", "float", 1.0,
         "autoscaler evaluation period (seconds)", scope="autoscale")
register("MXNET_TPU_AUTOSCALE_BURN", "float", 6.0,
         "fleet short-window burn-rate threshold that (sustained) "
         "triggers a scale-up (6x = the SRE ticket factor)",
         scope="autoscale")
register("MXNET_TPU_AUTOSCALE_QUEUE", "int", 64,
         "router queue depth that (sustained) triggers a scale-up",
         scope="autoscale")
register("MXNET_TPU_AUTOSCALE_HOLD_S", "float", 5.0,
         "how long a scale-up signal must hold before acting (a "
         "burst must not buy a seat)", scope="autoscale")
register("MXNET_TPU_AUTOSCALE_COOLDOWN_S", "float", 30.0,
         "minimum seconds between autoscaler actions (replacement of "
         "a DEAD seat is exempt — availability does not wait out a "
         "cooldown)", scope="autoscale")
register("MXNET_TPU_AUTOSCALE_IDLE_S", "float", 120.0,
         "how long the fleet must stay idle (empty queue, burn under "
         "1x) before an autoscaler-added seat is retired",
         scope="autoscale")
register("MXNET_TPU_AUTOSCALE_REPLACE_S", "float", 3.0,
         "how long a seat must stay unroutable before the autoscaler "
         "replaces it (debounces a transient health blip)",
         scope="autoscale")

# -- chaos injection --------------------------------------------------------
register("MXNET_TPU_CHAOS", "bool", False,
         "deterministic fault-injection harness: engines/routers "
         "register with the process chaos controller at start and "
         "the scripted schedule (``MXNET_TPU_CHAOS_SCHEDULE``) "
         "injects faults — slowed/wedged forwards, killed wire "
         "connections, dropped/delayed dispatch frames, killed "
         "engine/router processes; ``0`` (the default) patches "
         "NOTHING and spawns no thread", scope="chaos")
register("MXNET_TPU_CHAOS_SEED", "int", 0,
         "chaos rng seed: the same seed + schedule replays an "
         "identical fault sequence (the determinism contract)",
         scope="chaos")
register("MXNET_TPU_CHAOS_SCHEDULE", "str", None,
         "the fault schedule: inline JSON (a list of "
         "``{at, fault, target, ...}`` entries) or a path to a JSON "
         "file; unset = an armed controller with no scripted faults "
         "(drills drive it programmatically)", scope="chaos")

# -- alert egress -----------------------------------------------------------
register("MXNET_TPU_ALERT_EGRESS", "bool", True,
         "alert delivery out of the process: alert daemons attach the "
         "process notifier (webhook/file/stdout sinks, retry + "
         "dead-letter spool) when any sink is configured; ``0`` spawns "
         "no thread and registers no ``mxnet_tpu_alert_egress_*`` "
         "families", scope="egress")
register("MXNET_TPU_ALERT_EGRESS_URL", "str", None,
         "webhook sink: alert notifications POST here as JSON (unset "
         "= no webhook sink)", scope="egress")
register("MXNET_TPU_ALERT_EGRESS_FILE", "path", None,
         "file sink: alert notifications append here as JSONL (tests "
         "and air-gapped runs page into a file)", scope="egress")
register("MXNET_TPU_ALERT_EGRESS_STDOUT", "bool", False,
         "stdout sink: print alert notifications as JSON lines",
         scope="egress")
register("MXNET_TPU_ALERT_EGRESS_RETRIES", "int", 4,
         "delivery attempts per sink before a notification goes to "
         "the dead-letter spool (exponential backoff + jitter between "
         "attempts)", scope="egress")
register("MXNET_TPU_ALERT_EGRESS_BACKOFF_S", "float", 0.5,
         "base delivery backoff in seconds (doubles per retry, plus "
         "up to 50% jitter)", scope="egress")
register("MXNET_TPU_ALERT_EGRESS_SPOOL", "path", None,
         "dead-letter spool directory for undeliverable notifications "
         "(default ``<MXNET_TPU_FLIGHT_DIR>/egress-spool``); replayed "
         "on the next notifier start so a page survives process death",
         scope="egress")
register("MXNET_TPU_ALERT_EGRESS_SPOOL_MAX", "int", 256,
         "dead-letter spool bound (files); past it the OLDEST spooled "
         "notification is dropped to keep the newest pages",
         scope="egress")

# -- incident timeline ------------------------------------------------------
register("MXNET_TPU_INCIDENT_GAP_S", "float", 120.0,
         "incident correlation gap in pre-scale seconds (scaled by "
         "``MXNET_TPU_SLO_WINDOW_SCALE``): signals this close fold "
         "into one incident, and a quiet incident with nothing firing "
         "and no seat down closes after it", scope="incidents")

# -- retrospective history --------------------------------------------------
register("MXNET_TPU_HISTORY", "bool", True,
         "retrospective time-series history: engines/routers run a "
         "scraper daemon sampling their exposition into a bounded "
         "store served at ``/query_range`` + ``/series`` and frozen "
         "into flight bundles on incident open; ``0`` disables the "
         "whole subsystem (no thread, no store)", scope="history")
register("MXNET_TPU_HISTORY_DIR", "path", None,
         "persist history segments under this directory (append-only "
         "JSONL segment files per family and tier, reloaded on the "
         "next start); unset keeps the store in-memory only — same "
         "bounds, no disk", scope="history")
register("MXNET_TPU_HISTORY_RETAIN_S", "float", 86400.0,
         "retention of the coarsest (60 s) downsampling tier in "
         "seconds; the raw and 10 s tiers retain proportionally "
         "shorter windows", scope="history")
register("MXNET_TPU_HISTORY_MAX_MB", "float", 64.0,
         "on-disk budget for ``MXNET_TPU_HISTORY_DIR`` (MB); past it "
         "the oldest segment files are deleted, finest tier first",
         scope="history")
register("MXNET_TPU_HISTORY_SCRAPE_S", "float", 5.0,
         "history scraper sampling interval in seconds (engines "
         "sample the process registry, routers the fleet-merged "
         "exposition)", scope="history")
register("MXNET_TPU_HISTORY_SEGMENT_MB", "float", 4.0,
         "history segment rotation size (MB): the active append-only "
         "segment file rotates past it, so retention/budget deletes "
         "operate on whole sealed segments", scope="history")

# -- traffic capture & shadow validation ------------------------------------
register("MXNET_TPU_CAPTURE", "bool", False,
         "sampled production-traffic capture: engines record a "
         "head-sampled fraction of admitted requests (prompt, "
         "sampling params + seed, model/tenant identity, outcome, "
         "output digest, latency + stage breakdown) into a bounded "
         "crash-safe corpus for deterministic replay; canary traffic "
         "is excluded; ``0`` (the default) builds nothing — no "
         "thread, no ``mxnet_tpu_capture_*`` families, no files",
         scope="capture")
register("MXNET_TPU_CAPTURE_DIR", "path", None,
         "persist the capture corpus under this directory "
         "(length+CRC-framed wire-codec segment files, rotated and "
         "reloadable across processes); unset keeps the corpus "
         "in-memory only — same byte bound, no disk", scope="capture")
register("MXNET_TPU_CAPTURE_RATE", "float", 1.0,
         "head-sampling rate in 0..1: the fraction of admitted "
         "non-synthetic requests recorded, by exact deterministic "
         "credit accumulation (0.25 records every 4th request)",
         scope="capture")
register("MXNET_TPU_CAPTURE_MAX_MB", "float", 64.0,
         "corpus byte budget (MB); past it the oldest SEALED segments "
         "are evicted (the active segment keeps writing) — the "
         "history-store discipline", scope="capture")
register("MXNET_TPU_CAPTURE_PAYLOAD", "str", "tokens",
         "what the record keeps of the prompt: ``tokens`` (the int32 "
         "token array — the corpus is replayable) or ``digest`` "
         "(only its digest — privacy mode; replay skips such records "
         "and counts them)", scope="capture")
register("MXNET_TPU_SHADOW", "bool", False,
         "shadow-diff validation: the router mirrors a fraction of "
         "completed live requests at a candidate seat "
         "(fire-and-forget — live futures never wait on the shadow), "
         "diffs output digests + latency, and exposes the "
         "``/shadow`` verdict the ``swap_model`` gate consults; "
         "``0`` (the default) builds nothing — no mirror branch, no "
         "``mxnet_tpu_shadow_*`` families", scope="capture")
register("MXNET_TPU_SHADOW_FRACTION", "float", 0.25,
         "fraction of completed non-synthetic live requests mirrored "
         "at the shadow seat (deterministic credit accumulation, "
         "like the capture sampler)", scope="capture")
register("MXNET_TPU_SHADOW_THRESHOLD", "float", 0.0,
         "maximum tolerated shadow divergence rate: the swap gate "
         "refuses the flip while ``divergences/compared`` exceeds "
         "this (0.0 = any divergence blocks — the seeded-decode "
         "byte-identical contract)", scope="capture")
register("MXNET_TPU_SHADOW_MIN_REQUESTS", "int", 16,
         "comparisons required before the shadow verdict may pass: "
         "the gate refuses the flip until this many mirrored "
         "requests have been diffed (a candidate must earn the "
         "swap)", scope="capture")
register("MXNET_TPU_SHADOW_TIMEOUT_S", "float", 30.0,
         "per-mirrored-request timeout on the shadow leg (a wedged "
         "candidate counts as an error, never blocks anything)",
         scope="capture")

# -- concurrency sanitizer --------------------------------------------------
register("MXNET_TPU_SANITIZE", "bool", False,
         "runtime concurrency sanitizer: patches ``threading.Lock``/"
         "``RLock``/``Condition`` (repo-created only) with wrappers "
         "that maintain the observed lock-order graph (cycle = "
         "potential deadlock, flagged even when the fatal "
         "interleaving never fires), time contended holds, and track "
         "thread lifecycles; the pytest plugin fails the session on "
         "unbaselined findings (``tests/mxsan_baseline.json``, "
         "``# mxsan: allow=<rule>`` suppressions). Off = nothing is "
         "patched", scope="sanitize")
register("MXNET_TPU_SANITIZE_HOLD_MS", "float", 100.0,
         "sanitizer long-hold threshold: a lock held longer than this "
         "many milliseconds WHILE another thread waits on it is "
         "reported (``long-hold``) — the convoy shape, not mere "
         "slowness", scope="sanitize")

# -- tests / dev harness ----------------------------------------------------
register("MXNET_TPU_TEST_REAL_DEVICE", "bool", False,
         "run the test suite against the real backend instead of the "
         "virtual 8-device CPU mesh", scope="tests")
register("MXNET_TPU_NIGHTLY", "bool", False,
         "enable the large-tensor nightly test tier (>2^31-element "
         "allocations)", scope="tests")
register("MXNET_TPU_DRYRUN_REAL", "bool", False,
         "``dryrun_multichip`` uses real devices instead of a forced "
         "CPU mesh", scope="tests")


_SCOPE_TITLES = OrderedDict([
    ("runtime", "Core runtime"),
    ("compile_cache", "Persistent compilation cache"),
    ("kernels", "Pallas kernels"),
    ("dist", "Distributed"),
    ("wire", "Serving dispatch wire"),
    ("decode", "Decode serving (paged KV cache + continuous batching)"),
    ("telemetry", "Telemetry / observability"),
    ("slo", "SLOs & alerting"),
    ("routing", "SLO-aware routing"),
    ("tenancy", "Multi-tenant, multi-model serving"),
    ("ha", "Router active/active HA"),
    ("autoscale", "Autoscaler"),
    ("chaos", "Chaos injection"),
    ("canary", "Synthetic canaries"),
    ("egress", "Alert egress"),
    ("incidents", "Incident timeline"),
    ("history", "Retrospective history"),
    ("capture", "Traffic capture & shadow validation"),
    ("sanitize", "Concurrency sanitizer"),
    ("tests", "Tests / dev harness"),
])


def markdown_table():
    """The generated README "Configuration reference" body: one table
    per scope, every registered variable present exactly once."""
    lines = []
    for scope, title in _SCOPE_TITLES.items():
        rows = [v for v in ENVVARS.values() if v.scope == scope]
        if not rows:
            continue
        lines.append(f"**{title}**")
        lines.append("")
        lines.append("| Variable | Type | Default | Effect |")
        lines.append("|---|---|---|---|")
        for v in rows:
            lines.append(f"| `{v.name}` | {v.vtype} | "
                         f"`{v.describe_default()}` | {v.doc} |")
        lines.append("")
    return "\n".join(lines).rstrip() + "\n"
