"""Closed-loop synthetic traffic generator for mxnet_tpu.serving.

``run_load`` and ``run_chaos_drill`` are the harnesses the serving
tests drive (``tests/test_compile_cache.py``, ``tests/test_chaos.py``,
``tests/test_tenancy.py``), and the tool is usable by hand against any
engine::

    python tools/serve_loadgen.py --clients 8 --requests 16

(standalone mode builds a small CPU BERT, serves it, prints the JSON
report). Closed loop: each client thread submits its next request only
after the previous response lands — the standard serving-bench shape
(latency is client-observed, throughput is total completed / wall).

``--router N`` fronts N engines with a ``ServingRouter`` and drives
the ROUTER: the report gains the per-engine request distribution, and
the scrape cross-check reconciles the router's AGGREGATED ``/metrics``
delta (router counter family + engine-labeled serving families summed
across engines) against client-side accounting.

``--router-url http://r1:8080,http://r2:8080`` drives ALREADY-RUNNING
router endpoints instead of building anything locally, with
CLIENT-SIDE FAILOVER: a router that refuses the connection or answers
5xx sends the request to the next URL in the list (sticky — later
requests start from the last router that answered), so a router
restart mid-run costs retries, not failed requests.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time


def scrape_metrics(url, timeout=10.0):
    """GET a /metrics endpoint and parse it into {series: value}."""
    import urllib.request

    from mxnet_tpu.telemetry import parse_prometheus_text

    with urllib.request.urlopen(url, timeout=timeout) as r:
        return parse_prometheus_text(r.read().decode())


_SERVER_EVENTS = ("submitted", "completed", "rejected_queue_full",
                  "rejected_too_long", "rejected_stopped", "expired",
                  "cancelled", "failed")

_ROUTER_EVENTS = ("submitted", "completed", "failed", "expired",
                  "cancelled", "requeued", "shed_queue_full",
                  "shed_no_engine", "rejected_stopped")


def _sum_by_event(parsed, family):
    """Sum a scraped counter family by its ``event`` label across all
    other labels — with engine_id-labeled serving families (and a
    router aggregating N engines) the reconciliation is against the
    FLEET total, not one child."""
    from mxnet_tpu.telemetry.expo import parse_labels

    out = {}
    for key, val in parsed.items():
        name, labels = parse_labels(key)
        if name != family or "event" not in labels:
            continue
        out[labels["event"]] = out.get(labels["event"], 0.0) + val
    return out


def _requests_total_delta(before, after,
                          family="mxnet_tpu_serving_requests_total",
                          events=_SERVER_EVENTS):
    b = _sum_by_event(before, family)
    a = _sum_by_event(after, family)
    return {ev: int(a.get(ev, 0.0) - b.get(ev, 0.0)) for ev in events}


def _per_engine_completed_delta(before, after):
    """Completed-request delta per engine_id — the distribution the
    router report prints next to the router's own dispatch counts."""
    from mxnet_tpu.telemetry.expo import parse_labels

    out = {}
    for parsed, sign in ((before, -1), (after, 1)):
        for key, val in parsed.items():
            name, labels = parse_labels(key)
            if name != "mxnet_tpu_serving_requests_total" \
                    or labels.get("event") != "completed":
                continue
            eid = labels.get("engine_id", "?")
            out[eid] = out.get(eid, 0.0) + sign * val
    return {eid: int(v) for eid, v in out.items() if v}


def parse_tenant_spec(spec):
    """``--tenants 'priority:1,standard:4,best-effort:8'`` → the
    per-client ``(tenant, tenant_class)`` assignment list. Each
    ``class[:count]`` pair contributes ``count`` closed-loop clients
    submitting as tenant ``t-<class>``; the list's length REPLACES
    ``--clients`` (the spec IS the offered-load mix)."""
    from mxnet_tpu.serving.tenancy import normalize_class

    out = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        cls, _, count = part.partition(":")
        cls = normalize_class(cls.strip())
        n = int(count) if count.strip() else 1
        if n <= 0:
            raise ValueError(f"tenant spec count must be > 0: {part!r}")
        out.extend([(f"t-{cls}", cls)] * n)
    if not out:
        raise ValueError(f"empty tenant spec: {spec!r}")
    return out


def _tenant_delta(before, after):
    """Per-tenant deltas off the tenant-slice families: outcome events
    from ``.._tenant_requests_total``, billed tokens/device seconds
    from the cost counters. Canary probes carry no tenant (they bill
    as ``anonymous``), so the loadgen's NAMED tenants reconcile
    exactly even with a live prober."""
    from mxnet_tpu.telemetry.expo import parse_labels

    out = {}
    for parsed, sign in ((before, -1), (after, 1)):
        for key, val in parsed.items():
            name, labels = parse_labels(key)
            tenant = labels.get("tenant")
            if tenant is None or not name.startswith(
                    "mxnet_tpu_serving_tenant_"):
                continue
            slot = out.setdefault(tenant, {"events": {}, "tokens": 0.0,
                                           "device_s": 0.0})
            if name == "mxnet_tpu_serving_tenant_requests_total":
                ev = labels.get("event", "?")
                slot["events"][ev] = (slot["events"].get(ev, 0.0)
                                      + sign * val)
            elif name == "mxnet_tpu_serving_tenant_tokens_total":
                slot["tokens"] += sign * val
            elif name == "mxnet_tpu_serving_tenant_cost_seconds_total":
                slot["device_s"] += sign * val
    for slot in out.values():
        slot["events"] = {ev: int(v) for ev, v in slot["events"].items()
                          if int(v)}
        slot["tokens"] = int(slot["tokens"])
        slot["device_s"] = round(slot["device_s"], 6)
    return {t: s for t, s in sorted(out.items())
            if s["events"] or s["tokens"]}


def cross_check_tenants(books, delta):
    """Per-tenant reconciliation: every named tenant's client-side
    completed count and token sum must equal the server's tenant-slice
    delta — the billing contract, checked tenant by tenant (a fleet
    that reconciles in AGGREGATE can still bill the wrong party)."""
    mismatches = []
    for tenant, b in sorted(books.items()):
        srv = delta.get(tenant)
        if srv is None:
            if b["ok"]:
                mismatches.append(f"{tenant}: no server-side slice")
            continue
        done = srv["events"].get("completed", 0)
        if b["ok"] != done:
            mismatches.append(f"{tenant}: completed client={b['ok']} "
                              f"server={done}")
        if b["tokens"] != srv["tokens"]:
            mismatches.append(f"{tenant}: tokens client={b['tokens']} "
                              f"server={srv['tokens']}")
    return not mismatches, mismatches


def cross_check(outcomes, attempts, delta):
    """Reconcile client-side accounting against the server-observed
    /metrics deltas — every submit must land in exactly one counter on
    both sides. Returns (reconciled, mismatches)."""
    checks = {
        "submitted": (attempts, delta["submitted"]),
        "completed": (outcomes["ok"], delta["completed"]),
        "shed": (outcomes["shed"], delta["rejected_queue_full"]),
        "expired": (outcomes["expired"], delta["expired"]),
        "errors": (outcomes["error"],
                   delta["failed"] + delta["rejected_too_long"]
                   + delta["rejected_stopped"] + delta["cancelled"]),
    }
    mismatches = [f"{name}: client={c} server={s}"
                  for name, (c, s) in checks.items() if c != s]
    return not mismatches, mismatches


def summarize_breakdowns(samples, tolerance=0.25):
    """The report's ``breakdown`` section off per-request critical
    paths: ``samples`` is ``[(client_ms, breakdown|None, class), ...]``
    for completed requests (the server's attributed decomposition
    rides ``InferenceFuture.breakdown`` end to end — engine, wire,
    router relay, HTTP /submit).

    Reconciles the two clocks: the server-side decomposition must sum
    to its own wall by construction (``attributed + unattributed ==
    wall``), and the AGGREGATE server wall must agree with the
    aggregate client wall within ``tolerance`` — that is what
    ``reconciled`` judges. Per-request ratios are reported as
    ``wall_mismatches`` but not gated on: the client adds an ADDITIVE
    transport/relay/GIL overhead of a few ms, which on a short
    request is a large fraction of a small number (a 3 ms overhead on
    a 10 ms request is a 30% "skew" with both clocks perfectly
    honest). Returns None when no sample carried a breakdown."""
    rows = [(c_ms, bd, cls) for c_ms, bd, cls in samples
            if bd is not None]
    if not rows:
        return None

    def _table(sub):
        wall = sum(bd["wall_ms"] for _, bd, _ in sub)
        un = sum(bd.get("unattributed_ms") or 0.0 for _, bd, _ in sub)
        stages = {}
        for _, bd, _ in sub:
            for s in bd.get("stages") or ():
                stages[s["stage"]] = (stages.get(s["stage"], 0.0)
                                      + (s.get("ms") or 0.0))
        out = {"requests": len(sub),
               "wall_ms": round(wall, 3),
               "unattributed_ms": round(un, 3),
               "attributed_share":
                   round((wall - un) / wall, 4) if wall else None,
               "stages": {k: round(v, 3) for k, v in sorted(
                   stages.items(), key=lambda kv: -kv[1])}}
        return out

    out = _table(rows)
    out["missing"] = len(samples) - len(rows)
    mismatches = sum(
        1 for c_ms, bd, _ in rows
        if c_ms > 0 and not (1 - tolerance
                             <= bd["wall_ms"] / c_ms
                             <= 1 + tolerance))
    out["wall_mismatches"] = mismatches
    client_wall = sum(c_ms for c_ms, _, _ in rows)
    server_wall = sum(bd["wall_ms"] for _, bd, _ in rows)
    ratio = (server_wall / client_wall) if client_wall else None
    out["server_client_wall_ratio"] = (round(ratio, 4)
                                       if ratio is not None else None)
    out["reconciled"] = (ratio is not None
                         and 1 - tolerance <= ratio <= 1 + tolerance)
    classes = {cls for _, _, cls in rows if cls}
    if classes:
        out["by_class"] = {cls: _table([r for r in rows
                                        if r[2] == cls])
                           for cls in sorted(classes)}
    return out


def _fetch_costs(metrics_url, timeout=10.0):
    """GET the sibling /costs of a /metrics URL; returns the
    cross-bucket totals row (router bodies carry a fleet ``totals``,
    engines their own) or None when the endpoint is absent."""
    import urllib.request

    base = metrics_url.rsplit("/metrics", 1)[0]
    try:
        with urllib.request.urlopen(base + "/costs", timeout=timeout) as r:
            body = json.loads(r.read().decode())
    except Exception:
        return None
    return body.get("totals")


def _fetch_slo(metrics_url, timeout=10.0):
    """GET the sibling /slo of a /metrics URL; returns the per-
    objective compliance map — error-budget remaining, burn rates and
    ``met`` — or None when no SLO evaluator is attached
    (``MXNET_TPU_SLO=0``, or a pre-SLO engine)."""
    import urllib.request

    base = metrics_url.rsplit("/metrics", 1)[0]
    try:
        with urllib.request.urlopen(base + "/slo", timeout=timeout) as r:
            body = json.loads(r.read().decode())
    except Exception:
        return None
    objectives = body.get("objectives")
    if not objectives:
        return None
    out = {}
    for name, row in objectives.items():
        out[name] = {
            "met": row.get("met"),
            "error_budget_remaining": row.get("error_budget_remaining"),
            "burn_rates": row.get("burn_rates"),
        }
        if "sli" in row:
            out[name]["sli"] = row["sli"]
        if "value" in row:
            out[name]["value"] = row["value"]
    return out


def _canary_delta(before, after):
    """Synthetic-canary deltas over the measured window, scraped off
    the ``mxnet_tpu_canary_*`` families (tagged ``traffic="synthetic"``
    for exactly this): per-seat probe counts by outcome, per-transport
    counts, and the billed device_s/requests/tokens the cost
    reconciliation must EXCLUDE — a background prober drives real
    forwards through the engines, so its bills land in the server's
    cost ledger but never in the loadgen's client books. Returns None
    when no canary counter moved (prober off, or single-engine mode)."""
    from mxnet_tpu.telemetry.expo import parse_labels

    probes = {}
    by_transport = {}
    excluded = {"device_s": 0.0, "requests": 0, "tokens": 0}
    moved = False
    for parsed, sign in ((before or {}, -1), (after or {}, 1)):
        for key, val in parsed.items():
            name, labels = parse_labels(key)
            if name == "mxnet_tpu_canary_requests_total":
                eid = labels.get("engine_id", "?")
                outcome = labels.get("outcome", "?")
                row = probes.setdefault(eid, {})
                row[outcome] = row.get(outcome, 0.0) + sign * val
                tr = labels.get("transport", "?")
                by_transport[tr] = by_transport.get(tr, 0.0) + sign * val
            elif name == "mxnet_tpu_canary_billed_seconds_total":
                excluded["device_s"] += sign * val
            elif name == "mxnet_tpu_canary_billed_requests_total":
                excluded["requests"] += sign * val
            elif name == "mxnet_tpu_canary_billed_tokens_total":
                excluded["tokens"] += sign * val
            else:
                continue
            moved = True
    probes = {eid: {o: int(n) for o, n in row.items() if n}
              for eid, row in probes.items()}
    probes = {eid: row for eid, row in probes.items() if row}
    if not moved or (not probes
                     and not any(excluded.values())):
        return None
    return {"probes": probes,
            "by_transport": {t: int(n)
                             for t, n in by_transport.items() if n},
            "excluded": {"device_s": round(excluded["device_s"], 6),
                         "requests": int(excluded["requests"]),
                         "tokens": int(excluded["tokens"])}}


def cross_check_costs(client_cost, before, after, slack=0,
                      lost_ledgers=False, exclude=None,
                      counters=None):
    """Reconcile client-side cost accounting (summed per-request
    ``future.cost`` bills) against the server cost-ledger DELTA:
    requests and tokens must match exactly, and the client's summed
    amortized device seconds must equal the ledger's ``request_s``
    (batch-time conservation) within 5%.

    ``slack`` is the number of requests the SERVER may legitimately
    have billed beyond the client's books: a dispatched request whose
    reply was lost and failed over is billed on two engines but
    completes once client-side, and a post-dispatch failure is billed
    but lands in the client's error column. With slack > 0 the
    requests/tokens/device_s checks become ``ledger >= client`` (with
    requests bounded by client + slack) instead of exact — a healthy
    run with failovers must not report a mismatch.

    ``lost_ledgers=True`` waives the LOWER bounds too: when an engine
    process died mid-run the router's fleet table may be missing that
    seat's final window (remote seats fall back to their last fetched
    ledger), so the server side can legitimately under-read — only
    over-billing beyond slack stays a mismatch.

    ``exclude`` (a ``_canary_delta``-shaped ``excluded`` dict) removes
    label-identified SYNTHETIC traffic from the ledger delta before
    comparing: canary probes are billed server-side but are not client
    requests, and without the exclusion a background prober would skew
    the ≤5% device_s reconciliation.

    ``counters`` (the before/after PARSED ``/metrics`` snapshots, when
    given) overrides the ``requests``/``valid_tokens`` deltas with the
    ``mxnet_tpu_serving_cost_{requests,tokens}_total`` family sums —
    the same ATOMIC scrape the canary-billed exclusion comes from, so
    the two windows cannot skew (the separate ``/costs`` fetch sits
    OUTSIDE the metrics window by the scrape wall time itself, and
    with a live prober that edge otherwise leaks probe rounds past
    the slack). ``request_s`` stays ledger-sourced (it has no exact
    family) under its looser ≤5% bound. Returns
    (reconciled, mismatches, delta)."""
    if before is None or after is None:
        return None, ["/costs endpoint unavailable"], None
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("request_s", "requests", "valid_tokens")}
    if counters is not None:
        from mxnet_tpu.telemetry.expo import parse_labels

        sums = {"requests": 0.0, "valid_tokens": 0.0}
        fam_of = {"mxnet_tpu_serving_cost_requests_total": "requests",
                  "mxnet_tpu_serving_cost_tokens_total": "valid_tokens"}
        for parsed, sign in ((counters[0], -1), (counters[1], 1)):
            for key, val in (parsed or {}).items():
                name, _labels = parse_labels(key)
                field = fam_of.get(name)
                if field is not None:
                    sums[field] += sign * val
        delta["requests"] = int(round(sums["requests"]))
        delta["valid_tokens"] = int(round(sums["valid_tokens"]))
    if exclude:
        delta["request_s"] -= exclude.get("device_s", 0.0)
        delta["requests"] -= exclude.get("requests", 0)
        delta["valid_tokens"] -= exclude.get("tokens", 0)
    mismatches = []
    req_lo = 0 if lost_ledgers else client_cost["requests"]
    req_hi = client_cost["requests"] + max(int(slack), 0)
    if not req_lo <= delta["requests"] <= req_hi:
        mismatches.append(f"requests: client={client_cost['requests']} "
                          f"ledger={delta['requests']}"
                          + (f" (slack {slack})" if slack else ""))
    if lost_ledgers:
        tokens_ok = True
    elif slack:
        tokens_ok = client_cost["tokens"] <= delta["valid_tokens"]
    else:
        tokens_ok = client_cost["tokens"] == delta["valid_tokens"]
    if not tokens_ok:
        mismatches.append(f"tokens: client={client_cost['tokens']} "
                          f"ledger={delta['valid_tokens']}")
    ledger_s = delta["request_s"]
    client_s = client_cost["device_s"]
    if lost_ledgers:
        device_ok = True
    elif slack:
        device_ok = client_s <= ledger_s * 1.05
    else:
        device_ok = abs(client_s - ledger_s) <= 0.05 * max(ledger_s, 1e-9)
    if not device_ok:
        mismatches.append(f"device_s: client={client_s:.6f} "
                          f"ledger={ledger_s:.6f}")
    return not mismatches, mismatches, delta


def cross_check_router(outcomes, attempts, delta):
    """The router-mode reconciliation: client accounting vs the
    ROUTER's counter family (engine-side counters can't balance the
    books — a router-shed request never reaches an engine, a
    failed-over one reaches two). ``requeued`` is informational: a
    requeue is not a client-visible outcome."""
    checks = {
        "submitted": (attempts, delta["submitted"]),
        "completed": (outcomes["ok"], delta["completed"]),
        "shed": (outcomes["shed"],
                 delta["shed_queue_full"] + delta["shed_no_engine"]),
        "expired": (outcomes["expired"], delta["expired"]),
        "errors": (outcomes["error"],
                   delta["failed"] + delta["rejected_stopped"]
                   + delta["cancelled"]),
    }
    mismatches = [f"{name}: client={c} server={s}"
                  for name, (c, s) in checks.items() if c != s]
    return not mismatches, mismatches


class RouterClient:
    """Client-side target over one-or-more REMOTE ServingRouter
    endpoints (``--router-url url1,url2``): the ``submit`` surface
    ``run_load`` expects, spoken over each router's ``POST /submit``
    long-poll, with client-side failover. A router that refuses the
    connection or answers 5xx advances the request to the NEXT url;
    the first router that answers becomes sticky-preferred so a
    healthy fleet pays zero extra probes. When every router in the
    list refuses, the SWEEP retries per the shared
    :class:`~mxnet_tpu.retrying.RetryPolicy` (bridging a router
    restart / HA-adoption window) before failing as
    ``NoEngineAvailableError`` — the client's shed column.
    ``failovers`` counts the client-observed advances.

    Every request carries a client-minted HA correlation id
    (``cid``): active/active routers journal it to their peer, so a
    request re-driven to the next url after its first router DIED
    mid-flight attaches to the survivor's adopted copy instead of
    executing twice. A mid-request TIMEOUT still never fails over
    (the first router may be alive and still executing)."""

    class _Future:
        """Lazy long-poll: the POST runs inside ``result()`` on the
        calling client thread (closed-loop — exactly where the legacy
        blocking wait lived)."""

        def __init__(self, client, payload):
            self._client = client
            self._payload = payload
            self.trace_id = None
            self.cost = None

        def result(self, timeout=None):
            return self._client._request(self, timeout)

    def __init__(self, urls, timeout_s=600.0, retry=None):
        from mxnet_tpu.retrying import RetryPolicy

        urls = [u.strip().rstrip("/") for u in urls if u.strip()]
        if not urls:
            raise ValueError("no router URLs given")
        self.urls = urls
        self._timeout = float(timeout_s)
        self._preferred = 0
        self._lock = threading.Lock()
        self.failovers = 0
        self._last_board = {}
        self._retry = retry if retry is not None else RetryPolicy(
            retries=2, backoff_s=0.15, max_backoff_s=1.0)
        self._cid_base = f"cli-{os.getpid():x}-{id(self) & 0xffffff:x}"
        self._cid_seq = itertools.count(1)

    def _order(self):
        with self._lock:
            start = self._preferred
        return [(start + i) % len(self.urls)
                for i in range(len(self.urls))]

    def submit(self, tokens, token_types=None, deadline_ms=None,
               model_id=None, tenant=None, tenant_class=None):
        import numpy as np
        payload = {"tokens": np.asarray(tokens).tolist(),
                   "token_types": (np.asarray(token_types).tolist()
                                   if token_types is not None else None),
                   "deadline_ms": deadline_ms,
                   "cid": f"{self._cid_base}-{next(self._cid_seq)}"}
        if model_id is not None:
            payload["model_id"] = model_id
        if tenant is not None:
            payload["tenant"] = tenant
        if tenant_class is not None:
            payload["tenant_class"] = tenant_class
        return self._Future(self, payload)

    def _request(self, fut, timeout):
        from mxnet_tpu.serving import NoEngineAvailableError

        attempt = 0
        while True:
            done, out, last_err, last_body = self._sweep(fut, timeout)
            if done:
                return out
            if attempt >= self._retry.retries:
                break
            # every url refused: back off per the shared policy and
            # re-sweep — a router restart (or the HA survivor still
            # adopting) is a window, not a verdict
            self._retry.sleep_before_retry(attempt)
            attempt += 1
        # the last router-shaped error body (e.g. a single router
        # answering "fleet down") still maps onto the serving
        # error classes; with nothing parseable it's a client shed
        if last_body is not None:
            return self._deliver(fut, last_body)
        raise NoEngineAvailableError(
            f"every router url refused (last: {last_err})")

    def _sweep(self, fut, timeout):
        """One pass down the url list. Returns ``(done, result,
        last_err, last_body)`` — ``done=True`` means ``result`` is
        the delivered answer (or a raised exception escaped)."""
        import urllib.error
        import urllib.request

        from mxnet_tpu.serving import ServingError

        # the server-side wait must not outlive the client's own:
        # a router holding a handler thread 600 s for a client that
        # gave up at 60 is a slow leak
        fut._payload["timeout_s"] = (timeout if timeout is not None
                                     else self._timeout)
        data = json.dumps(fut._payload).encode()
        last_err = None
        last_body = None
        for i in self._order():
            try:
                req = urllib.request.Request(
                    self.urls[i] + "/submit", data=data,
                    headers={"Content-Type": "application/json"})
                resp = urllib.request.urlopen(
                    req, timeout=timeout if timeout is not None
                    else self._timeout)
            except urllib.error.HTTPError as e:
                try:
                    body = json.loads(e.read().decode())
                except Exception:
                    body = None
                if e.code >= 500 and e.code != 504:
                    # the ROUTER is sick (stopped, whole fleet down,
                    # proxy error) — the next url may front healthy
                    # engines. 504 is the REQUEST's own deadline OR the
                    # router's dispatch timeout on it: either way it is
                    # request-scoped and must not be retried somewhere
                    # else as new work.
                    last_err = f"{self.urls[i]}: HTTP {e.code}"
                    last_body = body
                    with self._lock:
                        self.failovers += 1
                    continue
                if body is None:
                    raise ServingError(
                        f"{self.urls[i]}: HTTP {e.code}") from e
            except Exception as e:
                # the long-poll reply comes as one blob, so urlopen
                # returning means the router ANSWERED; timing out here
                # means it accepted the request and is still executing
                # it — the payload's cid would dedupe a replay against
                # an HA PEER, but the same (live) router would treat
                # it as new work, so a BARE timeout still never fails
                # over. Connection DEATH (refused / reset / dns —
                # urllib wraps them in URLError) advances down the
                # list: either the request never arrived, or the
                # router died with it and the survivor's journal
                # adoption + cid dedupe make the replay exactly-once.
                if isinstance(e, TimeoutError):
                    raise ServingError(
                        f"{self.urls[i]}: timed out mid-request "
                        "(not failing over: the router may still be "
                        "executing it)") from e
                last_err = f"{self.urls[i]}: {e!r}"
                with self._lock:
                    self.failovers += 1
                continue
            else:
                try:
                    with resp:
                        body = json.loads(resp.read().decode())
                except Exception as e:
                    # post-accept failure (truncated/garbled reply):
                    # the router took the work — not retriable either
                    raise ServingError(
                        f"{self.urls[i]}: bad reply: {e!r}") from e
            with self._lock:
                self._preferred = i
            return True, self._deliver(fut, body), None, None
        return False, None, last_err, last_body

    def _deliver(self, fut, body):
        import numpy as np

        from mxnet_tpu.serving import NoEngineAvailableError, ServingError
        from mxnet_tpu.serving.router import _ERROR_CLASSES

        fut.trace_id = body.get("trace_id")
        if body.get("ok"):
            fut.cost = body.get("cost")
            fut.breakdown = body.get("breakdown")
            return np.asarray(body["result"], np.float32)
        cls = _ERROR_CLASSES.get(body.get("error_type"), ServingError)
        if body.get("error_type") == "NoEngineAvailableError":
            cls = NoEngineAvailableError
        raise cls(body.get("error") or "router error")

    # run_load's router-mode surface (scoreboard marks router-ness;
    # snapshot feeds the report) — scraped off the preferred /stats
    def snapshot(self):
        import urllib.request
        for i in self._order():
            try:
                with urllib.request.urlopen(
                        self.urls[i] + "/stats", timeout=10.0) as r:
                    snap = json.loads(r.read().decode())
                with self._lock:
                    self._last_board = snap.get("engines") or {}
                return snap
            except Exception:
                continue
        return {"engines": dict(self._last_board), "counters": {}}

    def scoreboard(self):
        snap = self.snapshot()
        return snap.get("engines") or {}


def _watch_restarts(router, stop_evt, restarts, poll_s=0.05):
    """Scoreboard watcher for router-driven runs: an engine seat that
    goes unroutable/disappears and comes back (or a replacement seat
    appearing mid-run — the rolling-restart drill) is recorded with
    its downtime and its time-to-first-token after restart (first
    completed request on that engine after it reappeared; falls back
    to first dispatched for engines whose counters this process can't
    see). Appends dicts to ``restarts`` and returns when stopped."""
    try:
        from mxnet_tpu.telemetry.registry import REGISTRY
        fam = REGISTRY.counter(
            "mxnet_tpu_serving_requests_total",
            "serving requests by admission/completion outcome, "
            "per engine", ("engine_id", "event"))

        def completed(eid):
            return fam.labels(engine_id=eid, event="completed").value
    except Exception:         # remote-only fleet: dispatched fallback
        def completed(eid):
            return None

    seen = {}          # eid -> {"routable", "down_at", "dispatched"}
    open_restarts = {}  # eid -> record still waiting for first token
    first = True
    while True:
        stopped = stop_evt.wait(0.0 if first else poll_s)
        now = time.perf_counter()
        board = router.scoreboard()
        for eid, row in board.items():
            st = seen.get(eid)
            restarted = False
            if st is None:
                # a seat appearing AFTER the initial snapshot is a
                # restarted/replacement engine
                restarted = not first
                seen[eid] = st = {"routable": bool(row["routable"]),
                                  "down_at": None,
                                  "dispatched": row.get("dispatched", 0)}
            elif row.get("dispatched", 0) < st["dispatched"]:
                # dispatch count went BACKWARDS: a replacement seat
                # took this id between two polls (remove+add faster
                # than the poll period)
                restarted = True
                st["routable"] = bool(row["routable"])
            elif bool(row["routable"]) != st["routable"]:
                st["routable"] = bool(row["routable"])
                if not st["routable"]:
                    st["down_at"] = now
                else:
                    restarted = True
            st["dispatched"] = row.get("dispatched", 0)
            if restarted:
                rec = {"engine_id": eid,
                       "downtime_s": (round(now - st["down_at"], 3)
                                      if st.get("down_at") else None),
                       "ttft_ms": None,
                       "_t0": now,
                       "_completed0": completed(eid),
                       "_dispatched0": row.get("dispatched", 0)}
                st["down_at"] = None
                open_restarts[eid] = rec
                restarts.append(rec)
        for eid in [e for e in seen if e not in board]:
            st = seen[eid]
            if st["down_at"] is None:       # removed seat == down
                st["down_at"] = now
            st["routable"] = False
        for eid, rec in list(open_restarts.items()):
            row = board.get(eid)
            if row is None:
                continue
            done_now = completed(eid)
            if row.get("kind") == "remote":
                # remote seats' counters live in another process (the
                # local registry child stays 0 forever): the router's
                # dispatched count is the only observable signal —
                # ttft is then first-dispatch, slightly optimistic
                served = (row.get("dispatched", 0)
                          > rec["_dispatched0"])
            else:
                # local seats: first COMPLETION only. Dispatched moves
                # the moment the router hands the request over — long
                # before a cold engine finishes its first-visit
                # compile, which is exactly the latency to measure.
                served = (done_now is not None
                          and rec["_completed0"] is not None
                          and done_now > rec["_completed0"])
            if served:
                rec["ttft_ms"] = round(
                    (time.perf_counter() - rec["_t0"]) * 1e3, 3)
                del open_restarts[eid]
        first = False
        if stopped:
            for rec in restarts:
                rec.pop("_t0", None)
                rec.pop("_completed0", None)
                rec.pop("_dispatched0", None)
            return


def run_load(engine, n_clients=8, requests_per_client=16,
             min_len=16, max_len=512, vocab=30522, deadline_ms=None,
             result_timeout_s=600.0, seed=0, metrics_url=None,
             tenants=None, model_ids=None):
    """Drive ``engine`` — a ServingEngine OR a ServingRouter (same
    submit surface) — with n_clients closed-loop threads.

    Returns a stats dict: client-observed latency percentiles,
    completed/shed/expired counts, requests_per_sec and
    valid_tokens_per_sec over the loaded wall-clock window, plus the
    engine's own snapshot (queue depth, packing efficiency,
    compile/compute split).

    With ``metrics_url`` (a ``/metrics`` endpoint, e.g. from
    ``engine.expose()``), the loadgen also scrapes BEFORE and AFTER
    the run and cross-checks the server-observed counter deltas
    against its own client-side accounting (registry counters are
    process-cumulative, so deltas are the honest comparison). The
    report then carries a ``server`` section: per-outcome deltas,
    ``reconciled`` (True when both sides agree request-for-request),
    and histogram-estimated server-side total-latency percentiles
    next to the client-observed ones. A ``cost`` section reconciles
    the client-summed per-request amortized bills (``future.cost``)
    against the server's ``/costs`` ledger delta — requests and
    tokens exactly, device seconds within 5% — with label-identified
    SYNTHETIC canary traffic excluded from the ledger side (a
    router-side prober's probes are billed server-side but are not
    client requests); when a prober ran, a ``canary`` section reports
    its per-seat outcome counts, transport split and the excluded
    device_s/requests/tokens.

    ``tenants`` (a ``parse_tenant_spec`` assignment list — its length
    replaces ``n_clients``) tags every client with a tenant + WFQ
    admission class; the report then carries a per-tenant section
    (share, outcome counts, client p50/p99) and — with a
    ``metrics_url`` — a per-tenant billing cross-check against the
    server's tenant-slice counter deltas. ``model_ids`` round-robins
    submits across named hosted models (the multi-model mix).
    """
    import threading

    import numpy as np

    from mxnet_tpu.serving import (DeadlineExceededError,
                                   NoEngineAvailableError, QueueFullError)

    if tenants:
        n_clients = len(tenants)

    # a router reports against its OWN counter family and adds the
    # per-engine request distribution to the report
    is_router = hasattr(engine, "scoreboard")

    # fetch order matters with a live canary prober: /costs BEFORE
    # /metrics here, and /metrics before /costs at the end, so the
    # ledger window CONTAINS the canary-counter window — a probe
    # racing a scrape edge can then only leave an extra ledger-side
    # request (covered by the upper slack), never an under-read that
    # would push the delta below the exact lower bound
    costs_before = _fetch_costs(metrics_url) if metrics_url else None
    before = scrape_metrics(metrics_url) if metrics_url else None

    latencies = []          # (ms, trace_id) — list.append is atomic
    outcomes = {"ok": 0, "expired": 0, "shed": 0, "error": 0}
    valid_tokens = [0]
    # per-request critical paths: (client_ms, breakdown, class) for
    # the report's breakdown section (see summarize_breakdowns)
    breakdown_samples = []
    # client-side cost books: summed per-request amortized bills off
    # future.cost — reconciled against the server's /costs delta
    client_cost = {"device_s": 0.0, "requests": 0, "tokens": 0,
                   "compiled": 0, "missing": 0}
    # per-tenant client books (tenant runs only): the loadgen's side
    # of the per-tenant billing cross-check + per-class percentiles
    tenant_books = {}
    if tenants:
        for tenant, cls in tenants:
            tenant_books.setdefault(
                tenant, {"class": cls, "clients": 0, "ok": 0,
                         "shed": 0, "expired": 0, "error": 0,
                         "tokens": 0, "device_s": 0.0, "lat": []})
            tenant_books[tenant]["clients"] += 1
    lock = threading.Lock()

    def client(cid):
        rs = np.random.RandomState(seed + cid)
        tenant = cls = None
        if tenants:
            tenant, cls = tenants[cid]
        for i in range(requests_per_client):
            n = int(rs.randint(min_len, max_len + 1))
            toks = rs.randint(1, vocab, n).astype(np.int32)
            kwargs = {}
            if tenant is not None:
                kwargs.update(tenant=tenant, tenant_class=cls)
            if model_ids:
                kwargs["model_id"] = model_ids[(cid + i)
                                               % len(model_ids)]
            t0 = time.perf_counter()
            try:
                # submit + result (not infer) so every generated
                # request is TAGGED with its server-side trace id —
                # the report's slowest_traces hand the operator ids to
                # paste straight into `telemetry_dump.py --trace <id>`
                fut = engine.submit(toks, deadline_ms=deadline_ms,
                                    **kwargs)
                fut.result(timeout=result_timeout_s)
            except DeadlineExceededError:
                with lock:
                    outcomes["expired"] += 1
                    if tenant:
                        tenant_books[tenant]["expired"] += 1
                continue
            except (QueueFullError, NoEngineAvailableError):
                with lock:
                    outcomes["shed"] += 1
                    if tenant:
                        tenant_books[tenant]["shed"] += 1
                time.sleep(0.005)       # polite backoff, stay closed-loop
                continue
            except Exception:
                with lock:
                    outcomes["error"] += 1
                    if tenant:
                        tenant_books[tenant]["error"] += 1
                continue
            ms = (time.perf_counter() - t0) * 1e3
            cost = getattr(fut, "cost", None)
            with lock:
                outcomes["ok"] += 1
                valid_tokens[0] += n
                latencies.append((ms, fut.trace_id))
                breakdown_samples.append(
                    (ms, getattr(fut, "breakdown", None), cls))
                if tenant:
                    tb = tenant_books[tenant]
                    tb["ok"] += 1
                    tb["lat"].append(ms)
                    tb["tokens"] += (cost.get("tokens", n)
                                     if cost else n)
                    if cost:
                        tb["device_s"] += cost.get("device_s", 0.0)
                if cost:
                    client_cost["device_s"] += cost.get("device_s", 0.0)
                    client_cost["requests"] += 1
                    client_cost["tokens"] += cost.get("tokens", 0)
                    if cost.get("compiled"):
                        client_cost["compiled"] += 1
                else:
                    client_cost["missing"] += 1

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"loadgen_client_{c}", daemon=True)
               for c in range(n_clients)]
    restarts = []
    watcher = stop_watch = None
    if is_router:
        # restart observer: if an engine dies and comes back mid-run
        # (rolling restart / failover drill), the report carries its
        # downtime and post-restart time-to-first-token
        stop_watch = threading.Event()
        watcher = threading.Thread(
            target=_watch_restarts, args=(engine, stop_watch, restarts),
            name="loadgen_restart_watch", daemon=True)
        watcher.start()
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    if watcher is not None:
        stop_watch.set()
        watcher.join(timeout=5.0)
        # publish COPIES without the watcher's private keys: if the
        # join timed out the thread may still be mutating the records
        restarts = [{k: v for k, v in rec.items()
                     if not k.startswith("_")} for rec in restarts]

    from mxnet_tpu.serving.metrics import nearest_rank

    xs = sorted(ms for ms, _ in latencies)

    def pct(p):
        v = nearest_rank(xs, p)
        return None if v is None else round(v, 3)

    slowest = sorted(latencies, key=lambda x: -x[0])[:5]

    report = {"clients": n_clients,
              "requests_per_client": requests_per_client,
              "wall_s": round(wall, 3),
              "completed": outcomes["ok"],
              "expired": outcomes["expired"],
              "shed": outcomes["shed"],
              "errors": outcomes["error"],
              "requests_per_sec":
                  round(outcomes["ok"] / wall, 2) if wall else 0,
              "valid_tokens_per_sec":
                  round(valid_tokens[0] / wall, 2) if wall else 0,
              "p50_ms": pct(50), "p95_ms": pct(95), "p99_ms": pct(99),
              "slowest_traces": [{"trace_id": tid, "ms": round(ms, 3)}
                                 for ms, tid in slowest],
              "engine": engine.snapshot()}
    breakdown = summarize_breakdowns(breakdown_samples)
    if breakdown is not None:
        report["breakdown"] = breakdown
    if tenants:
        # per-tenant client view: offered share, outcomes, latency
        # percentiles — priority under overload must hold its p99
        # while best-effort sheds (the WFQ acceptance shape)
        tview = {}
        for tenant, tb in sorted(tenant_books.items()):
            ts = sorted(tb["lat"])

            def tpct(p, _ts=ts):
                v = nearest_rank(_ts, p)
                return None if v is None else round(v, 3)

            tview[tenant] = {
                "class": tb["class"], "clients": tb["clients"],
                "completed": tb["ok"], "shed": tb["shed"],
                "expired": tb["expired"], "errors": tb["error"],
                "p50_ms": tpct(50), "p99_ms": tpct(99),
                "client_tokens": tb["tokens"],
                "client_device_s": round(tb["device_s"], 6)}
        report["tenants"] = tview
    if model_ids:
        report["models"] = list(model_ids)
    if is_router:
        snap = report["engine"]
        report["per_engine"] = {eid: row["dispatched"]
                                for eid, row in snap["engines"].items()}
        report["failovers"] = snap["counters"].get("requeued", 0)
        report["engines_up"] = snap.get("engines_up")
        report["restarts"] = restarts
    if metrics_url:
        from mxnet_tpu.telemetry import histogram_quantile

        after = scrape_metrics(metrics_url)
        attempts = n_clients * requests_per_client
        if is_router:
            delta = _requests_total_delta(
                before, after, family="mxnet_tpu_router_requests_total",
                events=_ROUTER_EVENTS)
            reconciled, mismatches = cross_check_router(
                outcomes, attempts, delta)
        else:
            delta = _requests_total_delta(before, after)
            reconciled, mismatches = cross_check(
                outcomes, attempts, delta)
        # quantiles over the DELTA of the bucket counts: the estimate
        # covers this load window only, not warmup traffic
        window = {k: v - before.get(k, 0.0) for k, v in after.items()}
        lat_family = ("mxnet_tpu_router_latency_ms" if is_router
                      else "mxnet_tpu_serving_latency_ms")
        est = {f"p{q}_ms_est": (round(v, 3) if v is not None else None)
               for q in (50, 99)
               for v in [histogram_quantile(
                   window, lat_family, q, match={"stage": "total"})]}
        report["server"] = {"requests_total_delta": delta,
                            "reconciled": reconciled,
                            "mismatches": mismatches,
                            "latency": est}
        if is_router:
            # aggregated /metrics carries every engine's labeled
            # families: the per-engine share as PROMETHEUS sees it,
            # next to the router's own dispatch accounting
            report["server"]["per_engine_completed"] = \
                _per_engine_completed_delta(before, after)
        # cost cross-check: client-summed amortized bills vs the
        # server cost-ledger delta over the measured window
        costs_after = _fetch_costs(metrics_url)
        # synthetic canary traffic (a router-side background prober)
        # is billed in the ledger but never in the client's books:
        # exclude its label-identified deltas so the ≤5% device_s
        # reconciliation holds with canaries running
        canary = _canary_delta(before, after)
        # failed-over and post-dispatch-failed requests are billed in
        # the ledger but not in the client's ok-books — that many
        # extra server-side requests is healthy, not a mismatch; with
        # a live prober, a probe billed inside the (wider) ledger
        # window whose canary counters landed outside the metrics
        # window adds ledger-side-only requests the same way — up to
        # one in-flight probe ROUND (= one probe per seat) per edge
        cost_slack = outcomes["error"] + report.get("failovers", 0)
        if canary:
            seats = len(report.get("per_engine") or {}) or 1
            cost_slack += 2 * seats
        cost_ok, cost_mismatches, cost_delta = cross_check_costs(
            client_cost, costs_before, costs_after, slack=cost_slack,
            lost_ledgers=bool(report.get("restarts")),
            exclude=canary["excluded"] if canary else None,
            counters=(before, after))
        if canary:
            report["canary"] = canary
        report["cost"] = {
            "client_device_s": round(client_cost["device_s"], 6),
            "client_requests": client_cost["requests"],
            "client_tokens": client_cost["tokens"],
            "compiled_requests": client_cost["compiled"],
            "missing_bills": client_cost["missing"],
            "ledger_delta": cost_delta,
            "reconciled": cost_ok,
            "mismatches": cost_mismatches}
        if cost_delta and report["completed"] and wall:
            tokens = cost_delta["valid_tokens"]
            if tokens:
                report["cost"]["device_s_per_1k_tokens"] = round(
                    cost_delta["request_s"] * 1e3 / tokens, 6)
        # per-tenant billing cross-check: the named tenants' completed
        # counts and token sums must match the server's tenant-slice
        # deltas tenant-for-tenant (aggregate reconciliation can hide
        # a bill landing on the wrong party)
        if tenants:
            tdelta = _tenant_delta(before, after)
            t_ok, t_mismatches = cross_check_tenants(
                tenant_books, tdelta)
            for tenant, srv in tdelta.items():
                if tenant in report["tenants"]:
                    report["tenants"][tenant]["server"] = srv
            report["tenants_reconciled"] = t_ok
            report["tenant_mismatches"] = t_mismatches
        # SLO compliance after the measured window: error-budget
        # remaining + burn rates per declared objective (the bench's
        # serving legs forward this as `slo_compliance`)
        slo = _fetch_slo(metrics_url)
        if slo is not None:
            report["slo"] = slo
    return report


def run_decode_load(engine, n_clients=8, requests_per_client=8,
                    min_prompt=4, max_prompt=16, vocab=64,
                    min_new=4, max_new=16, deadline_ms=None,
                    result_timeout_s=600.0, seed=0, metrics_url=None,
                    stream=True, watch_engines=None, prompt_reuse=0.0,
                    temperature=None, top_k=None, top_p=None,
                    sample_seed=None):
    """Closed-loop GENERATION traffic against a ``DecodeEngine`` (or a
    ``ServingRouter`` fronting decode engines): each client submits a
    random prompt with a random ``max_new_tokens``, consumes the
    TOKEN STREAM (``future.stream()``) stamping a perf-counter
    timestamp per token, and verifies the streamed tokens are
    byte-identical to the final authoritative result — the zero
    lost/duplicated-token check running on every single request.

    The report's decode-specific numbers: generated ``tokens_per_sec``
    over the loaded wall, client-observed TTFT (submit → first token)
    and inter-token-gap percentiles, stream consistency, and (with
    ``watch_engines``) the peak KV-page occupancy + slot churn
    observed during the window. ``metrics_url`` adds the same
    server-side reconciliation as :func:`run_load` — request counters,
    cost ledger (canary-billed SYNTHETIC traffic excluded, exactly as
    for encoder loads — streamed bills carry the same
    device_s/requests/tokens fields), and SLO compliance.

    ``stream=False`` drives the same traffic through plain
    ``result()`` waits — the streamed-vs-unstreamed parity axis (the
    token sequences must match bit-for-bit; generation is greedy).

    ``prompt_reuse=FRAC`` prepends a SHARED system prompt (a fixed
    token prefix, identical across clients) to that fraction of
    requests — the traffic shape the prefix KV cache exists for. With
    ``watch_engines`` the report adds the observed prefix-cache hit
    rate and reused-token total off the pools' ``prefix_stats()``
    delta.

    ``temperature``/``top_k``/``top_p`` turn on SEEDED sampling: each
    request carries a deterministic per-request seed (derived from
    ``sample_seed``, or minted server-side when None). The existing
    streamed-vs-final byte-identity check then doubles as the replay
    check: across a ``--router`` failover the relay re-runs the
    request on a sibling seat and drops already-seen part indices, so
    ``stream_mismatches == 0`` proves the resampled continuation was
    byte-identical — the seed, not the seat, owns the randomness.
    """
    import threading

    import numpy as np

    from mxnet_tpu.serving import (DeadlineExceededError,
                                   NoEngineAvailableError, QueueFullError)

    is_router = hasattr(engine, "scoreboard")
    costs_before = _fetch_costs(metrics_url) if metrics_url else None
    before = scrape_metrics(metrics_url) if metrics_url else None

    # the shared system prompt: one fixed token prefix every reusing
    # request starts with (page-aligned sharing is the pool's job —
    # the loadgen just makes the traffic look like production)
    sys_prompt = None
    if prompt_reuse > 0:
        sys_len = max(min_prompt, max_prompt // 2)
        sys_prompt = np.random.RandomState(seed ^ 0x5F5F) \
            .randint(1, vocab, sys_len).astype(np.int32)

    def _prefix_totals():
        if not watch_engines:
            return None
        tot = {}
        for eng in watch_engines:
            for k, v in eng.pool.prefix_stats().items():
                if isinstance(v, (int, float)):
                    tot[k] = tot.get(k, 0) + v
        return tot

    prefix_before = _prefix_totals()

    latencies = []           # (total_ms, trace_id)
    ttfts = []               # ms
    gaps = []                # inter-token gaps, ms
    outcomes = {"ok": 0, "expired": 0, "shed": 0, "error": 0}
    tokens_out = [0]
    stream_bad = [0]
    breakdown_samples = []   # (client_ms, breakdown, None)
    client_cost = {"device_s": 0.0, "requests": 0, "tokens": 0,
                   "compiled": 0, "missing": 0}
    lock = threading.Lock()

    def client(cid):
        rs = np.random.RandomState(seed + cid)
        for i in range(requests_per_client):
            n = int(rs.randint(min_prompt, max_prompt + 1))
            n_new = int(rs.randint(min_new, max_new + 1))
            toks = rs.randint(1, vocab, n).astype(np.int32)
            if sys_prompt is not None and rs.rand() < prompt_reuse:
                tail = max(1, n - len(sys_prompt))
                toks = np.concatenate(
                    [sys_prompt, toks[:tail]]).astype(np.int32)
                toks = toks[:max_prompt]
            kw = {}
            if temperature is not None:
                kw["temperature"] = temperature
                kw["top_k"] = top_k
                kw["top_p"] = top_p
                if sample_seed is not None:
                    kw["seed"] = sample_seed + cid * 1009 + i
            t0 = time.perf_counter()
            try:
                fut = engine.submit(toks, deadline_ms=deadline_ms,
                                    max_new_tokens=n_new, stream=stream,
                                    **kw)
                if stream:
                    stamps = []       # per-token arrival timestamps
                    parts = []
                    for part in fut.stream(timeout=result_timeout_s):
                        stamps.append(time.perf_counter())
                        parts.append(int(part["token"]))
                    out = fut.result(timeout=0)
                else:
                    out = fut.result(timeout=result_timeout_s)
                    stamps = [time.perf_counter()]
                    parts = None
            except DeadlineExceededError:
                with lock:
                    outcomes["expired"] += 1
                continue
            except (QueueFullError, NoEngineAvailableError):
                with lock:
                    outcomes["shed"] += 1
                time.sleep(0.005)
                continue
            except Exception:
                with lock:
                    outcomes["error"] += 1
                continue
            t_end = time.perf_counter()
            out = np.asarray(out).tolist()
            cost = getattr(fut, "cost", None)
            with lock:
                outcomes["ok"] += 1
                tokens_out[0] += len(out)
                latencies.append(((t_end - t0) * 1e3, fut.trace_id))
                breakdown_samples.append(
                    ((t_end - t0) * 1e3,
                     getattr(fut, "breakdown", None), None))
                if stamps:
                    ttfts.append((stamps[0] - t0) * 1e3)
                    gaps.extend((b - a) * 1e3 for a, b in
                                zip(stamps, stamps[1:]))
                if parts is not None and parts != out:
                    # the streamed partials and the final result
                    # disagree: lost or duplicated tokens — the one
                    # thing the streaming path must never do
                    stream_bad[0] += 1
                if cost:
                    client_cost["device_s"] += cost.get("device_s", 0.0)
                    client_cost["requests"] += 1
                    client_cost["tokens"] += cost.get("tokens", 0)
                    if cost.get("compiled"):
                        client_cost["compiled"] += 1
                else:
                    client_cost["missing"] += 1

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"loadgen_decode_{c}", daemon=True)
               for c in range(n_clients)]
    # occupancy watcher: peak KV-page usage + slot churn during the
    # window (in-process engines only — remote ones report via their
    # own /stats)
    occupancy = {"peak": 0.0, "peak_slots": 0}
    stop_watch = watcher = None
    if watch_engines:
        stop_watch = threading.Event()

        def _watch():
            while not stop_watch.wait(0.02):
                for eng in watch_engines:
                    occ = eng.pool.occupancy()["occupancy"]
                    occupancy["peak"] = max(occupancy["peak"], occ)
                    occupancy["peak_slots"] = max(
                        occupancy["peak_slots"], len(eng._active))

        watcher = threading.Thread(target=_watch, daemon=True,
                                   name="loadgen_decode_watch")
        watcher.start()
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    if watcher is not None:
        stop_watch.set()
        watcher.join(timeout=5.0)

    from mxnet_tpu.serving.metrics import nearest_rank

    xs = sorted(ms for ms, _ in latencies)
    ttft_xs = sorted(ttfts)
    gap_xs = sorted(gaps)

    def pct(samples, p):
        v = nearest_rank(samples, p)
        return None if v is None else round(v, 3)

    report = {"clients": n_clients,
              "requests_per_client": requests_per_client,
              "wall_s": round(wall, 3),
              "completed": outcomes["ok"],
              "expired": outcomes["expired"],
              "shed": outcomes["shed"],
              "errors": outcomes["error"],
              "streamed": bool(stream),
              "stream_mismatches": stream_bad[0],
              "generated_tokens": tokens_out[0],
              "tokens_per_sec":
                  round(tokens_out[0] / wall, 2) if wall else 0,
              "requests_per_sec":
                  round(outcomes["ok"] / wall, 2) if wall else 0,
              "p50_ms": pct(xs, 50), "p99_ms": pct(xs, 99),
              "ttft_p50_ms": pct(ttft_xs, 50),
              "ttft_p95_ms": pct(ttft_xs, 95),
              "inter_token_p50_ms": pct(gap_xs, 50),
              "inter_token_p99_ms": pct(gap_xs, 99),
              "engine": engine.snapshot()}
    breakdown = summarize_breakdowns(breakdown_samples)
    if breakdown is not None:
        report["breakdown"] = breakdown
    if temperature is not None:
        report["sampling"] = {"temperature": temperature,
                              "top_k": top_k, "top_p": top_p,
                              "seed_base": sample_seed}
    if prompt_reuse > 0:
        report["prompt_reuse"] = prompt_reuse
    if watch_engines:
        report["kv_occupancy_peak"] = round(occupancy["peak"], 4)
        report["peak_slots"] = occupancy["peak_slots"]
        churn = {"joins": 0, "leaves": 0}
        for eng in watch_engines:
            snap = eng.decode_stats.snapshot()
            churn["joins"] += snap["joins"]
            churn["leaves"] += snap["leaves"]
        report["churn"] = churn
        prefix_after = _prefix_totals()
        if prefix_before is not None and prefix_after is not None:
            delta = {k: prefix_after.get(k, 0) - prefix_before.get(k, 0)
                     for k in prefix_after}
            looks = delta.get("lookups", 0)
            report["prefix"] = {
                "lookups": looks,
                "hits": delta.get("hits", 0),
                "hit_rate": (round(delta.get("hits", 0) / looks, 4)
                             if looks else None),
                "pages_reused": delta.get("pages_reused", 0),
                "tokens_reused": delta.get("tokens_reused", 0),
                "cow_pages": delta.get("cow_pages", 0),
                "evictions": delta.get("evictions", 0)}
    if is_router:
        snap = report["engine"]
        report["per_engine"] = {eid: row["dispatched"]
                                for eid, row in snap["engines"].items()}
        report["failovers"] = snap["counters"].get("requeued", 0)
        report["engines_up"] = snap.get("engines_up")
    if metrics_url:
        after = scrape_metrics(metrics_url)
        attempts = n_clients * requests_per_client
        if is_router:
            delta = _requests_total_delta(
                before, after, family="mxnet_tpu_router_requests_total",
                events=_ROUTER_EVENTS)
            reconciled, mismatches = cross_check_router(
                outcomes, attempts, delta)
        else:
            delta = _requests_total_delta(before, after)
            reconciled, mismatches = cross_check(
                outcomes, attempts, delta)
        report["server"] = {"requests_total_delta": delta,
                            "reconciled": reconciled,
                            "mismatches": mismatches}
        costs_after = _fetch_costs(metrics_url)
        canary = _canary_delta(before, after)
        cost_slack = outcomes["error"] + report.get("failovers", 0)
        if canary:
            seats = len(report.get("per_engine") or {}) or 1
            cost_slack += 2 * seats
        cost_ok, cost_mismatches, cost_delta = cross_check_costs(
            client_cost, costs_before, costs_after, slack=cost_slack,
            exclude=canary["excluded"] if canary else None,
            counters=(before, after))
        if not cost_ok and canary and cost_delta:
            # decode probe-edge tolerance: an encoder probe's ledger
            # entries land at ONE dispatch instant (≈ its bill), but a
            # DECODE probe spreads them across its whole generation —
            # a probe straddling a scrape edge splits its per-
            # iteration ledger entries from its bill, skewing the
            # delta either way. Allow up to 2 in-flight probes per
            # seat of skew (the same edge budget run_load's request
            # slack uses), sized from the observed per-probe averages.
            exc = canary["excluded"]
            n = max(1, exc["requests"])
            seats_ = len(report.get("per_engine") or {}) or 1
            tol_t = -(-exc["tokens"] // n) * 2 * seats_
            tol_s = exc["device_s"] / n * 2 * seats_
            ok_t = abs(client_cost["tokens"]
                       - cost_delta["valid_tokens"]) <= tol_t
            led = cost_delta["request_s"]
            ok_s = (abs(client_cost["device_s"] - led)
                    <= 0.05 * max(led, 1e-9) + tol_s)
            ok_r = abs(client_cost["requests"]
                       - cost_delta["requests"]) <= 2 * seats_
            if ok_t and ok_s and ok_r:
                cost_ok, cost_mismatches = True, [
                    "within decode probe-edge tolerance: "
                    + "; ".join(cost_mismatches)]
        if canary:
            report["canary"] = canary
        report["cost"] = {
            "client_device_s": round(client_cost["device_s"], 6),
            "client_requests": client_cost["requests"],
            "client_tokens": client_cost["tokens"],
            "missing_bills": client_cost["missing"],
            "ledger_delta": cost_delta,
            "reconciled": cost_ok,
            "mismatches": cost_mismatches}
        if cost_delta and cost_delta.get("valid_tokens"):
            report["cost"]["device_s_per_1k_tokens"] = round(
                cost_delta["request_s"] * 1e3
                / cost_delta["valid_tokens"], 6)
        slo = _fetch_slo(metrics_url)
        if slo is not None:
            report["slo"] = slo
    return report


def overload_drill(target, alerts_fn=None, get_trace=None, alert=None,
                   n_clients=8, min_len=16, max_len=64, vocab=1000,
                   deadline_ms=None, fire_timeout_s=60.0,
                   resolve_timeout_s=120.0, poll_s=0.05, seed=0):
    """Induced-overload drill: flood ``target`` (a ServingEngine or
    ServingRouter — same submit surface) with closed-loop traffic
    until the named fast-burn alert FIRES, then stop the load and wait
    for it to RESOLVE. Asserts the full SLO-engine contract:

    - the alert walks the state machine ``pending → firing`` (read
      off the /alerts transition log, so a short pending dwell can't
      be missed between polls);
    - the firing payload carries ≥1 OpenMetrics exemplar whose trace
      id resolves to a retrievable trace (``get_trace``), i.e. the
      alert links to evidence, not just a number;
    - the firing payload carries top-stage ATTRIBUTION (the "why
      slow" attachment): the page names the bottleneck stage of the
      induced overload, and when the top stage carries an exemplar
      trace id it too must be retrievable. Skipped automatically when
      stage attribution is disabled in this process
      (``MXNET_TPU_ATTRIBUTION=0``, or spans off);
    - after the load stops, the alert leaves ``firing`` (resolved).

    ``alerts_fn``/``get_trace`` default to the target's own in-process
    surfaces; pass URL-backed callables to drill a remote fleet. The
    caller is expected to have tuned the SLO knobs for drill time
    scales (``MXNET_TPU_SLO_WINDOW_SCALE``, ``MXNET_TPU_SLO_EVAL_S``,
    ``MXNET_TPU_SLO_LATENCY_MS``) BEFORE starting the engines.

    Returns a report dict (states seen, the firing payload, the
    retrieved exemplar trace, wall timings). Raises AssertionError on
    any violated contract.
    """
    import numpy as np

    is_router = hasattr(target, "scoreboard")
    if alert is None:
        alert = ("fleet_latency_fast_burn" if is_router
                 else "serving_latency_fast_burn")
    if alerts_fn is None:
        if not hasattr(target, "alerts_snapshot"):
            raise ValueError(
                "overload_drill over a remote target needs an "
                "alerts_fn (an /alerts fetcher)")
        alerts_fn = target.alerts_snapshot
    if get_trace is None:
        if hasattr(target, "get_trace"):
            get_trace = target.get_trace
        else:
            from mxnet_tpu.telemetry import spans as _spans
            get_trace = _spans.get_trace

    def rule_row(body):
        for row in body.get("rules", ()):
            if row.get("alert") == alert:
                return row
        raise AssertionError(
            f"alert {alert!r} not declared; have "
            f"{[r.get('alert') for r in body.get('rules', ())]}")

    stop = threading.Event()
    flood_errors = []

    def flooder(cid):
        rs = np.random.RandomState(seed + cid)
        while not stop.is_set():
            n = int(rs.randint(min_len, max_len + 1))
            toks = rs.randint(1, vocab, n).astype(np.int32)
            try:
                # submit+result, not infer: RouterClient (a remote
                # drill target) only speaks the submit surface
                target.submit(toks, deadline_ms=deadline_ms) \
                    .result(timeout=fire_timeout_s)
            except Exception as e:
                # sheds/expiries ARE the overload working; only record
                # for the report, never abort the flood
                flood_errors.append(type(e).__name__)
                time.sleep(0.002)

    threads = [threading.Thread(target=flooder, args=(c,), daemon=True,
                                name=f"overload_drill_{c}")
               for c in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    states_seen = []
    fired = None
    try:
        deadline = time.monotonic() + fire_timeout_s
        while time.monotonic() < deadline:
            body = alerts_fn()
            row = rule_row(body)
            if not states_seen or states_seen[-1] != row["state"]:
                states_seen.append(row["state"])
            if row["state"] == "firing":
                fired = dict(row)
                fired["transitions"] = [
                    t for t in body.get("transitions", ())
                    if t.get("alert") == alert]
                break
            time.sleep(poll_s)
        assert fired is not None, (
            f"alert {alert!r} never fired within {fire_timeout_s}s "
            f"(states seen: {states_seen}; is the latency SLO tuned "
            f"below the flooded latency and the window scale small?)")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    t_fired = time.perf_counter() - t0

    # re-read the firing row now the flood has drained: the bounded
    # trace ring churns hard mid-flood, so the exemplar ids captured
    # at first-firing may already be evicted — the post-flood payload
    # references the freshest (surviving) traces
    body = alerts_fn()
    row = rule_row(body)
    if row.get("state") == "firing":
        fresh = dict(row)
        fresh["transitions"] = [
            t for t in body.get("transitions", ())
            if t.get("alert") == alert]
        fired = fresh

    # the pending dwell may be shorter than a poll period: the
    # transition LOG is the authoritative walk record
    walked = [(t.get("from"), t.get("to")) for t in fired["transitions"]]
    assert ("inactive", "pending") in walked or "pending" in states_seen, (
        f"alert {alert!r} never dwelt pending: {walked}")
    assert ("pending", "firing") in walked, (
        f"alert {alert!r} fired without walking pending→firing: {walked}")

    exemplars = fired.get("exemplars") or []
    assert exemplars, (
        f"firing {alert!r} carries no exemplars — no retrievable "
        f"evidence (are exemplars enabled and requests slow enough "
        f"for tail sampling?)")
    trace = None
    exemplar = None
    for ex in exemplars:
        trace = get_trace(ex["trace_id"])
        if trace is not None and trace.get("spans"):
            exemplar = ex
            break
    assert exemplar is not None, (
        f"none of the {len(exemplars)} exemplar trace ids resolved to "
        f"a kept trace (exemplars: {exemplars})")

    # the page must ANSWER "why slow", not just report it: top-stage
    # attribution rides the firing payload, naming the stage the
    # flooded wall time went to, with its own retrievable trace
    from mxnet_tpu.telemetry import attribution as _attribution
    attribution = fired.get("attribution")
    top_stage = None
    if _attribution.enabled():
        assert attribution, (
            f"firing {alert!r} carries no stage attribution — the "
            f"page says 'slow' without saying WHERE (did any request "
            f"complete and feed the /whyslow aggregator?)")
        top_stage = attribution[0]
        assert top_stage.get("stage") in _attribution.STAGES, (
            f"attribution names unregistered stage {top_stage!r}")
        if top_stage.get("exemplar"):
            st_trace = get_trace(top_stage["exemplar"])
            assert st_trace is not None and st_trace.get("spans"), (
                f"top-stage exemplar {top_stage['exemplar']!r} did "
                f"not resolve to a kept trace")

    # recovery: with the load gone the alert must leave firing
    deadline = time.monotonic() + resolve_timeout_s
    resolved = False
    while time.monotonic() < deadline:
        row = rule_row(alerts_fn())
        if states_seen[-1] != row["state"]:
            states_seen.append(row["state"])
        if row["state"] not in ("firing",):
            resolved = row["state"]
            break
        time.sleep(poll_s)
    assert resolved, (f"alert {alert!r} still firing "
                      f"{resolve_timeout_s}s after the load stopped")
    return {"alert": alert,
            "states": states_seen,
            "fired_after_s": round(t_fired, 3),
            "resolved_state": resolved,
            "resolved_after_s": round(time.perf_counter() - t0, 3),
            "exemplar": exemplar,
            "exemplar_trace_spans": len(trace.get("spans", ())),
            "attribution": attribution,
            "top_stage": (top_stage or {}).get("stage"),
            "error_budget_remaining":
                fired.get("error_budget_remaining"),
            "flood_errors": len(flood_errors),
            "transitions": fired["transitions"]}


class WedgeGate:
    """Wraps a serving model callable with a blocking gate: while
    ``block`` is set the forward spins — the worker THREAD stays
    alive (self-reported health stays green) but nothing completes.
    The ``--drill-wedge`` harness wedges exactly this way."""

    def __init__(self, fn):
        self.fn = fn
        self.block = threading.Event()

    def __call__(self, *args):
        while self.block.is_set():
            time.sleep(0.01)
        return self.fn(*args)


def wedge_drill(router, gates, victim, pages_path,
                fire_timeout_s=90.0, resolve_timeout_s=90.0,
                close_timeout_s=60.0, n_requests=4, poll_s=0.1):
    """Black-box wedged-engine drill: block ``victim``'s forward (the
    worker thread stays alive — its self-reported health stays green)
    and assert the canary absence rule pages, the page leaves the
    process through the file-sink notifier with the correlated
    incident id, ``/incidents`` opens ONE incident, and recovery
    resolves + closes it with zero lost real requests.

    ``gates`` maps engine_id -> an object with a ``block``
    ``threading.Event`` wrapped around the model forward (the loadgen
    CLI builds these for ``--drill-wedge``). Tune the clocks first —
    e.g. ``MXNET_TPU_SLO_WINDOW_SCALE=0.01 MXNET_TPU_SLO_EVAL_S=0.2
    MXNET_TPU_CANARY_INTERVAL_S=0.2 MXNET_TPU_CANARY_TIMEOUT_S=1``.
    Raises AssertionError on any violated contract; returns a report
    dict."""
    import numpy as np

    from mxnet_tpu.telemetry.registry import REGISTRY

    assert router.canary is not None, \
        "wedge drill needs the canary prober (MXNET_TPU_CANARY=1)"
    assert router.alerts is not None, \
        "wedge drill needs the SLO engine (MXNET_TPU_SLO=1)"
    alert = f"canary_absent_{victim}"
    t0 = time.perf_counter()

    # phase 0: canaries green on every seat
    fam = REGISTRY.get("mxnet_tpu_canary_requests_total")

    def ok_probes(eid):
        total = 0.0
        for values, child in fam._sorted_children():
            labels = dict(zip(fam.labelnames, values))
            if labels.get("engine_id") == eid \
                    and labels.get("outcome") == "ok":
                total += child.value
        return total

    deadline = time.monotonic() + fire_timeout_s
    seats = router.engine_ids()
    while time.monotonic() < deadline:
        if fam is None:
            fam = REGISTRY.get("mxnet_tpu_canary_requests_total")
        elif all(ok_probes(eid) > 0 for eid in seats):
            break
        time.sleep(poll_s)
    assert fam is not None and all(ok_probes(eid) > 0
                                   for eid in seats), \
        "canaries never went green on every seat"

    # real (non-synthetic) traffic rides through the whole drill
    futs = [router.submit(np.arange(1, 9, dtype=np.int32))
            for _ in range(n_requests)]

    # phase 1: wedge — then wait for the absence page
    gates[victim].block.set()
    fired = None
    deadline = time.monotonic() + fire_timeout_s
    while time.monotonic() < deadline:
        body = router.alerts_snapshot()
        rows = [r for r in body.get("rules", ())
                if r.get("alert") == alert]
        if rows and rows[0]["state"] == "firing":
            fired = rows[0]
            break
        time.sleep(poll_s)
    assert fired is not None, (
        f"{alert} never fired within {fire_timeout_s}s (is the canary "
        "interval/timeout tuned below the scaled absence window?)")
    walked = [(t.get("from"), t.get("to"))
              for t in body.get("transitions", ())
              if t.get("alert") == alert]
    assert ("pending", "firing") in walked, walked
    t_fired = time.perf_counter() - t0

    # phase 2: the page LEFT the process, exactly once, with the id
    pages = []
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        try:
            with open(pages_path) as f:
                pages = [json.loads(ln) for ln in f.read().splitlines()]
        except OSError:
            pages = []
        if any(p.get("to") == "firing" and p.get("alert") == alert
               for p in pages):
            break
        time.sleep(poll_s)
    firing_pages = [p for p in pages
                    if p.get("to") == "firing"
                    and p.get("alert") == alert]
    assert len(firing_pages) == 1, firing_pages or pages
    incident_id = firing_pages[0].get("incident_id")
    assert incident_id, firing_pages[0]
    # ONLY the wedged seat pages: a healthy sibling firing here means
    # either the serial prober starved it behind the victim's timeout
    # or the absence rule judged a partial window (both fixed bugs)
    others = [p for p in pages if p.get("to") == "firing"
              and p.get("alert") != alert]
    assert not others, others

    inc = router.incidents_snapshot()
    assert len(inc["open"]) == 1, inc["open"]
    assert inc["open"][0]["id"] == incident_id

    # phase 3: recovery — resolve, notify, close, zero loss
    gates[victim].block.clear()
    deadline = time.monotonic() + resolve_timeout_s
    resolved = None
    while time.monotonic() < deadline:
        body = router.alerts_snapshot()
        row = [r for r in body.get("rules", ())
               if r.get("alert") == alert][0]
        if row["state"] in ("resolved", "inactive"):
            resolved = row["state"]
            break
        time.sleep(poll_s)
    assert resolved, f"{alert} still firing after recovery"
    deadline = time.monotonic() + close_timeout_s
    closed = False
    while time.monotonic() < deadline:
        inc = router.incidents_snapshot()
        if not inc["open"]:
            closed = True
            break
        time.sleep(poll_s)
    assert closed, "incident never closed after recovery"
    for f in futs:
        f.result(timeout=max(60.0, resolve_timeout_s))
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        with open(pages_path) as f:
            pages = [json.loads(ln) for ln in f.read().splitlines()]
        if any(p.get("to") == "resolved" and p.get("alert") == alert
               for p in pages):
            break
        time.sleep(poll_s)
    assert any(p.get("to") == "resolved" and p.get("alert") == alert
               for p in pages), pages
    return {"alert": alert,
            "victim": victim,
            "incident_id": incident_id,
            "fired_after_s": round(t_fired, 3),
            "resolved_state": resolved,
            "closed_after_s": round(time.perf_counter() - t0, 3),
            "pages": [{k: p.get(k) for k in
                       ("alert", "to", "incident_id", "fingerprint")}
                      for p in pages],
            "real_requests_completed": len(futs),
            "recent_incident": inc["recent"][0] if inc.get("recent")
            else None}


def _wait_for(pred, timeout_s, what, poll_s=0.05):
    """Poll ``pred`` until truthy; its last value. AssertionError on
    timeout — the drill's one blocking primitive."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(poll_s)
    raise AssertionError(f"timed out after {timeout_s}s waiting for "
                         f"{what}")


def chaos_drill(r_keep, r_kill, urls, ctl, autoscaler, hotspot,
                victim, n_clients=6, hot_ms=80.0, min_len=8,
                max_len=24, vocab=1000, phase_timeout_s=90.0,
                settle_s=1.5, poll_s=0.05, seed=0):
    """The ROADMAP self-healing drill: under closed-loop load through
    TWO active/active routers, inject three scripted faults and assert
    the fleet re-converges each time with ZERO lost requests and one
    correlated incident per fault.

    - **hot-spot**: slow ``hotspot``'s forwards by ``hot_ms`` — the
      seat's latency SLO burns, its canary latency drifts, and the
      routers shed routing weight off it (asserted: weight drops
      under the degraded bound AND its measured per-seat dispatch
      share falls under half a fair share); clearing the fault
      recovers the weight through the hysteresis exit.
    - **seat kill**: abort ``victim`` — the autoscaler replaces it
      under the same id with a manifest-warmed engine (asserted: a
      ``replace`` action carrying a TTFT probe, the seat routable
      again on BOTH routers).
    - **router kill**: ``r_kill`` (the clients' sticky-preferred
      router) dies abruptly — its journaled in-flight requests are
      handed to ``r_keep`` (adoption on resubmit and/or peer-death
      sweep; asserted: the HA adopt counter moved) and every client
      request still completes.

    The caller owns construction (see :func:`run_chaos_drill`) and
    must have tuned the judging clocks for drill time scales
    (``MXNET_TPU_SLO_WINDOW_SCALE`` etc.). ``ctl`` is a
    :class:`~mxnet_tpu.serving.chaos.ChaosController` with every
    engine and both routers registered. Raises AssertionError on any
    violated contract; returns the report dict."""
    import numpy as np

    from mxnet_tpu.telemetry import incidents as _incidents
    from mxnet_tpu.telemetry.registry import REGISTRY

    client = RouterClient(urls)     # urls[0] = r_kill: clients prefer
    # the router that will die, so its death strands real in-flights
    stop = threading.Event()
    lock = threading.Lock()
    counts = {"attempts": 0, "ok": 0}
    errors = []

    def flooder(cidx):
        rs = np.random.RandomState(seed + cidx)
        while not stop.is_set():
            n = int(rs.randint(min_len, max_len + 1))
            toks = rs.randint(1, vocab, n).astype(np.int32)
            with lock:
                counts["attempts"] += 1
            try:
                client.submit(toks).result(timeout=phase_timeout_s)
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                time.sleep(0.01)
                continue
            with lock:
                counts["ok"] += 1

    threads = [threading.Thread(target=flooder, args=(c,), daemon=True,
                                name=f"chaos_drill_client_{c}")
               for c in range(n_clients)]

    def seat_row(router, eid):
        return router.scoreboard().get(eid) or {}

    def incident_ids():
        snap = _incidents.snapshot()
        return ({r["id"] for r in snap["open"]},
                {r["id"] for r in snap["open"]}
                | {r["id"] for r in snap["recent"]})

    def share_window(router, window_s):
        """Per-seat dispatch share over a measured window."""
        b0 = {eid: r.get("dispatched", 0)
              for eid, r in router.scoreboard().items()}
        time.sleep(window_s)
        b1 = {eid: r.get("dispatched", 0)
              for eid, r in router.scoreboard().items()}
        delta = {eid: b1.get(eid, 0) - b0.get(eid, 0) for eid in b1}
        total = max(1, sum(delta.values()))
        return {eid: d / total for eid, d in delta.items()}, total

    def ha_count(event):
        fam = REGISTRY.get("mxnet_tpu_router_ha_total")
        if fam is None:
            return 0.0
        return fam.labels(event=event).value

    def adopt_count():
        return ha_count("adopt")

    report = {"phases": {}, "incidents": []}
    seen0 = incident_ids()[1]
    for t in threads:
        t.start()
    try:
        # steady state: traffic flowing AND journaled to the peer (the
        # death edge only hands off what was journaled before it)
        _wait_for(lambda: counts["ok"] >= n_clients * 2,
                  phase_timeout_s, "steady-state traffic")
        _wait_for(lambda: ha_count("journal") > 0, phase_timeout_s,
                  "submits to journal to the HA peer")

        def phase_incident(name):
            """One NEW incident opened for this fault, then closed."""
            fresh = _wait_for(
                lambda: (incident_ids()[1] - seen0
                         - set(report["incidents"])) or None,
                phase_timeout_s, f"{name}: a correlated incident")
            try:
                _wait_for(lambda: not incident_ids()[0],
                          phase_timeout_s,
                          f"{name}: incident closed after recovery")
            except AssertionError as e:
                held = [{k: r.get(k) for k in
                         ("id", "firing", "down_engines", "counts")}
                        for r in _incidents.snapshot()["open"]]
                raise AssertionError(f"{e}; still held open by: "
                                     f"{held}") from None
            new = sorted(fresh)
            report["incidents"].extend(new)
            return new

        # ---- phase A: induced hot-spot sheds routing weight --------------
        fair = 1.0 / max(1, len(r_kill.engine_ids()))
        ctl.apply({"fault": "hotspot", "target": hotspot, "ms": hot_ms})
        _wait_for(lambda: (seat_row(r_kill, hotspot).get("weight", 1.0)
                           < 0.7), phase_timeout_s,
                  f"hot seat {hotspot} to shed routing weight")
        shares, n_window = share_window(r_kill, settle_s)
        hot_share = shares.get(hotspot, 0.0)
        weight_min = seat_row(r_kill, hotspot).get("weight")
        assert hot_share < 0.5 * fair, (
            f"hot-spot share did not move: {hotspot} still serves "
            f"{hot_share:.0%} (fair {fair:.0%}) over {n_window} reqs")
        ctl.clear({"fault": "hotspot", "target": hotspot})
        _wait_for(lambda: (seat_row(r_kill, hotspot).get("weight", 0.0)
                           >= 0.95), phase_timeout_s,
                  f"{hotspot} weight to recover after the fault")
        report["phases"]["hotspot"] = {
            "target": hotspot, "weight_min": weight_min,
            "fair_share": round(fair, 3),
            "hot_share": round(hot_share, 3),
            "window_requests": n_window,
            "incident": phase_incident("hotspot")}

        # ---- phase B: seat kill -> autoscaler replacement, warm ----------
        n_actions = len(autoscaler.actions)
        ctl.apply({"fault": "kill_engine", "target": victim})
        rec = _wait_for(
            lambda: next((a for a in autoscaler.actions[n_actions:]
                          if a["action"] == "replace"
                          and a["engine_id"] == victim), None),
            phase_timeout_s, f"autoscaler to replace {victim}")
        assert rec.get("ttft_ms") is not None, rec
        assert rec.get("manifest_shapes", 0) >= 1, (
            f"replacement admitted COLD (no manifest replay): {rec}")
        for router in (r_keep, r_kill):
            _wait_for(lambda r=router: seat_row(r, victim)
                      .get("routable"), phase_timeout_s,
                      f"replacement {victim} routable on "
                      f"{router.router_id}")
        report["phases"]["seat_kill"] = {
            "victim": victim, "ttft_ms": rec["ttft_ms"],
            "manifest_shapes": rec["manifest_shapes"],
            "incident": phase_incident("seat_kill")}

        # ---- phase C: router kill -> in-flight handoff -------------------
        # the death must strand real in-flights: a connection refused
        # earlier (a loaded host) is sticky, so point the clients back
        # at the router about to die and see its traffic move first
        def kill_dispatched():
            return sum(r.get("dispatched", 0)
                       for r in r_kill.scoreboard().values())
        with client._lock:
            client._preferred = 0
        d0 = kill_dispatched()
        _wait_for(lambda: kill_dispatched() >= d0 + n_clients,
                  phase_timeout_s, f"traffic through {r_kill.router_id}")
        adopt0 = adopt_count()
        ctl.apply({"fault": "kill_router", "target": r_kill.router_id})
        _wait_for(lambda: adopt_count() > adopt0, phase_timeout_s,
                  "the survivor to adopt orphaned in-flight requests")
        # traffic must keep completing through the survivor
        ok0 = counts["ok"]
        _wait_for(lambda: counts["ok"] >= ok0 + n_clients,
                  phase_timeout_s, "traffic to re-converge on the "
                  "surviving router")
        report["phases"]["router_kill"] = {
            "killed": r_kill.router_id,
            "adopted": int(adopt_count() - adopt0),
            "client_failovers": client.failovers,
            "incident": phase_incident("router_kill")}

        # ---- re-convergence: SLO compliance, quiet alert table -----------
        def quiet():
            body = r_keep.alerts_snapshot()
            return (body.get("fleet_firing", body.get("firing", 0)) == 0
                    and not incident_ids()[0])
        _wait_for(quiet, phase_timeout_s,
                  "the fleet to re-converge to SLO compliance")
    finally:
        stop.set()
        # past the per-request timeout: a stuck request must surface
        # as ITS error (naming where it hung), never a silent count
        for t in threads:
            t.join(timeout=phase_timeout_s + 15.0)

    # zero lost requests: every attempt completed (failover, adoption
    # and cid dedupe mean no client-visible error anywhere in the run)
    assert not errors, f"lost/errored requests: {errors[:8]}"
    assert counts["ok"] == counts["attempts"], counts
    # convergence detail: "met" judges the whole (scaled) budget
    # window — which CONTAINS the induced faults by design — so the
    # re-convergence signal is the short-window burn back under
    # sustainable, plus the quiet alert table asserted above
    slo = r_keep.slo_snapshot()
    report["slo"] = {name: {"met": row.get("met"),
                            "burn_5m":
                                (row.get("burn_rates") or {}).get("5m"),
                            "error_budget_remaining":
                                row.get("error_budget_remaining")}
                     for name, row in
                     (slo.get("objectives") or {}).items()}
    report["attempts"] = counts["attempts"]
    report["completed"] = counts["ok"]
    report["lost"] = counts["attempts"] - counts["ok"]
    report["client_failovers"] = client.failovers
    assert len(report["incidents"]) >= 3, report["incidents"]
    return report


def run_chaos_drill(make_engine, n_engines=3, n_clients=6,
                    hot_ms=80.0, phase_timeout_s=90.0, vocab=1000,
                    min_len=8, max_len=24):
    """Build the two-router active/active chaos fleet and run
    :func:`chaos_drill` over it: ``n_engines`` warmed engines fronted
    by two peered routers (both exposed over HTTP), a
    :class:`~mxnet_tpu.serving.FleetAutoscaler` spanning both (peers
    share seat state through it), and a chaos controller with
    everything registered. Used by ``--drill-chaos`` and the tier-1
    drill test."""
    import contextlib

    from mxnet_tpu.serving import FleetAutoscaler, ServingRouter
    from mxnet_tpu.serving.chaos import ChaosController

    if n_engines < 3:
        raise ValueError("chaos drill needs >= 3 engines (hot-spot, "
                         "kill victim, and a healthy witness)")
    with contextlib.ExitStack() as stack:
        engines = [make_engine(f"e{i}") for i in range(n_engines)]
        for eng in engines:
            eng.start()

            def _safe_stop(e=eng):
                try:
                    e.stop(drain=False, timeout=10.0)
                except Exception:
                    pass
            stack.callback(_safe_stop)
            eng.warmup()
        fleet = {eng.engine_id: eng for eng in engines}
        r_keep = ServingRouter(engines=dict(fleet),
                               poll_interval_s=0.2,
                               router_id="r-keep")
        r_kill = ServingRouter(engines=dict(fleet),
                               poll_interval_s=0.2,
                               router_id="r-kill")
        stack.callback(lambda: r_kill.stop(drain=False))
        stack.callback(lambda: r_keep.stop(drain=False))
        keep_srv = r_keep.expose()
        kill_srv = r_kill.expose()
        keep_url = f"http://{keep_srv.host}:{keep_srv.port}"
        kill_url = f"http://{kill_srv.host}:{kill_srv.port}"
        r_keep.set_peer(kill_url)
        r_kill.set_peer(keep_url)
        r_keep.start()
        r_kill.start()
        ctl = ChaosController(schedule=None)
        stack.callback(ctl.stop)
        for eng in engines:
            ctl.register_engine(eng)
        ctl.register_router(r_keep)
        ctl.register_router(r_kill)
        autoscaler = FleetAutoscaler(
            [r_keep, r_kill], make_engine, interval_s=0.25,
            replace_s=0.5, cooldown_s=1.0, hold_s=1.0,
            min_seats=n_engines, max_seats=n_engines + 1)
        stack.callback(lambda: autoscaler.stop(stop_seats=True))
        autoscaler.start()
        # both routers must see the peer alive BEFORE any kill: the
        # death EDGE (alive -> dead) is what triggers adoption
        _wait_for(lambda: r_keep._peer_alive and r_kill._peer_alive,
                  30.0, "the routers to see each other alive")
        return chaos_drill(
            r_keep, r_kill, [kill_url, keep_url], ctl, autoscaler,
            hotspot=engines[1].engine_id,
            victim=engines[0].engine_id,
            n_clients=n_clients, hot_ms=hot_ms, vocab=vocab,
            min_len=min_len, max_len=max_len,
            phase_timeout_s=phase_timeout_s)


def _main():
    import argparse
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--min-len", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--buckets", default="16,64",
                    help="comma-separated row-length buckets")
    ap.add_argument("--max-rows", type=int, default=4)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--units", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--pool", default="mean")
    ap.add_argument("--expose-port", type=int, default=0,
                    help="telemetry exposition port (0 = auto); the "
                    "loadgen scrapes it and cross-checks server vs "
                    "client accounting")
    ap.add_argument("--no-expose", action="store_true",
                    help="skip exposition + scrape cross-check")
    ap.add_argument("--event-log", default=None,
                    help="write the structured JSONL run-event log here")
    ap.add_argument("--router", type=int, default=0, metavar="N",
                    help="front N in-process engines with a "
                    "ServingRouter and drive the ROUTER endpoint: the "
                    "report adds the per-engine request distribution "
                    "and the cross-check reconciles the router's "
                    "aggregated /metrics delta against client-side "
                    "accounting")
    ap.add_argument("--router-url", default=None, metavar="URL[,URL...]",
                    help="drive ALREADY-RUNNING router endpoint(s) "
                    "instead of building engines locally; a comma-"
                    "separated list gets client-side failover (a "
                    "router that refuses the connection or answers "
                    "5xx advances the request to the next url)")
    ap.add_argument("--drill-wedge", nargs="?", const="e0",
                    default=None, metavar="ENGINE",
                    help="black-box wedged-engine drill (needs "
                    "--router N): block ENGINE's forward (its worker "
                    "thread stays alive — self-reported health stays "
                    "green) and assert the canary absence rule pages "
                    "through the file-sink notifier with the "
                    "correlated incident id, then recover, resolve "
                    "and close with zero lost real requests. Tune "
                    "the clocks first, e.g. "
                    "MXNET_TPU_SLO_WINDOW_SCALE=0.01 "
                    "MXNET_TPU_SLO_EVAL_S=0.2 "
                    "MXNET_TPU_CANARY_INTERVAL_S=0.2 "
                    "MXNET_TPU_CANARY_TIMEOUT_S=1 "
                    "MXNET_TPU_WATCHDOG_INTERVAL_S=0.5 "
                    "MXNET_TPU_WATCHDOG_STALL_S=2")
    ap.add_argument("--pages", default=None, metavar="FILE",
                    help="file-sink path for --drill-wedge page "
                    "notifications (default: a temp file, printed)")
    ap.add_argument("--drill-chaos", action="store_true",
                    help="the self-healing chaos drill: 3+ engines "
                    "behind TWO active/active routers under load; "
                    "inject a hot-spot (routing weight must shed off "
                    "the slow seat), a seat kill (the autoscaler must "
                    "replace it manifest-warm) and a router kill "
                    "(the survivor must adopt the in-flight "
                    "requests) — asserts SLO re-convergence, one "
                    "correlated incident per fault and ZERO lost "
                    "requests. Tune the judging clocks first, e.g. "
                    "MXNET_TPU_SLO_WINDOW_SCALE=0.01 "
                    "MXNET_TPU_SLO_EVAL_S=0.2 "
                    "MXNET_TPU_SLO_LATENCY_MS=40 "
                    "MXNET_TPU_CANARY_INTERVAL_S=0.2")
    ap.add_argument("--decode", action="store_true",
                    help="GENERATION traffic against DecodeEngine(s) "
                    "(a small paged-KV causal LM instead of the BERT "
                    "encoder): closed-loop clients consume the token "
                    "STREAM with per-token timestamps — the report "
                    "carries TTFT + inter-token p50/p99, generated "
                    "tokens/sec, peak KV-page occupancy and slot "
                    "churn, and every stream is verified byte-"
                    "identical to its final result. Composes with "
                    "--router N (decode engines behind the router, "
                    "streams relayed through it)")
    ap.add_argument("--max-new", type=int, default=16,
                    help="--decode: max_new_tokens upper bound "
                    "(per-request draw is U[max(1, max_new//4), "
                    "max_new])")
    ap.add_argument("--no-stream", action="store_true",
                    help="--decode: wait for full results instead of "
                    "consuming token streams (the parity axis)")
    ap.add_argument("--prompt-reuse", type=float, default=0.0,
                    metavar="FRAC",
                    help="--decode: prepend a SHARED system prompt to "
                    "FRAC of requests (0..1) — the traffic shape the "
                    "prefix KV cache serves; the report adds the "
                    "observed prefix-cache hit rate and reused-token "
                    "total")
    ap.add_argument("--sample", default=None,
                    metavar="TEMP[,TOPK[,TOPP[,SEED]]]",
                    help="--decode: seeded sampling instead of greedy "
                    "— e.g. '0.8,40,0.95,7'. Each request carries a "
                    "deterministic per-request seed derived from SEED "
                    "(omitted: the server mints one), so streams "
                    "replay byte-identical across --router failover "
                    "(stream_mismatches stays 0)")
    ap.add_argument("--tenants", default=None, metavar="SPEC",
                    help="tenant-class client mix, e.g. "
                    "'priority:1,standard:4,best-effort:8' — each "
                    "class:count pair runs count closed-loop clients "
                    "as tenant t-<class> in that WFQ admission class "
                    "(the total REPLACES --clients). The report adds "
                    "per-tenant p50/p99 + shed counts and, with a "
                    "scrapeable target, a per-tenant billing "
                    "cross-check against the server's tenant slices")
    ap.add_argument("--models", type=int, default=0, metavar="N",
                    help="register N named models (m0..mN-1) on every "
                    "engine and round-robin submits across them — the "
                    "multi-model mix (per-model splits land in the "
                    "tenant-slice families and /stats)")
    ap.add_argument("--replay", default=None, metavar="DIR",
                    help="instead of generating load, REPLAY a "
                    "captured corpus (MXNET_TPU_CAPTURE_DIR) against "
                    "the target: every completed record with a token "
                    "payload is re-submitted with its captured "
                    "sampling params + seed and the output is "
                    "asserted byte-identical to the recorded digest. "
                    "Build the target with the SAME flags as the "
                    "capture run (--decode, --router N, --models N, "
                    "...). Exits 1 on any divergence, printing the "
                    "per-stage breakdown of the slowest diverging "
                    "request")
    ap.add_argument("--speed", type=float, default=None, metavar="X",
                    help="--replay pacing: X times the captured "
                    "arrival rate (1.0 = original pacing; default: "
                    "as fast as the target admits)")
    ap.add_argument("--drill-overload", nargs="?", const="auto",
                    default=None, metavar="ALERT",
                    help="instead of the measured run, flood the "
                    "target past its latency SLO and assert the "
                    "fast-burn ALERT (default: the target's "
                    "*_latency_fast_burn) walks pending→firing with "
                    "a retrievable trace exemplar, then resolves "
                    "after the load stops. Tune the drill clock "
                    "first, e.g. MXNET_TPU_SLO_WINDOW_SCALE=0.01 "
                    "MXNET_TPU_SLO_EVAL_S=0.2 MXNET_TPU_SLO_LATENCY_MS=20")
    args = ap.parse_args()

    import contextlib

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel, bert_serving_entry
    from mxnet_tpu.serving import ServingEngine, ServingRouter

    buckets = tuple(int(b) for b in args.buckets.split(","))
    if args.event_log:
        from mxnet_tpu.telemetry import events
        events.configure(args.event_log, component="serve_loadgen")

    wedge_gates = {}

    def make_engine(engine_id=None):
        if args.decode:
            from mxnet_tpu.serving import DecodeEngine, PagedCausalLM
            lm = PagedCausalLM(vocab=args.vocab, units=args.units,
                               layers=args.layers, heads=args.heads,
                               max_len=max(4 * max(buckets), 128),
                               seed=0)
            return DecodeEngine(lm, prefill_bucket_lens=buckets,
                                max_rows=args.max_rows,
                                max_new_tokens=args.max_new,
                                engine_id=engine_id)
        net = BERTModel(vocab_size=args.vocab, units=args.units,
                        hidden_size=4 * args.units,
                        num_layers=args.layers, num_heads=args.heads,
                        max_length=args.max_len, dropout=0.0,
                        attention_dropout=0.0, use_pooler=False)
        # fixed weight seed: capture digests must replay
        # byte-identical across processes (--replay rebuilds the
        # target) and across seats (--router N may place the replayed
        # request on a different engine than the recording)
        mx.random.seed(0xC0FFEE)
        net.initialize(init=mx.initializer.Normal(0.02))
        model = bert_serving_entry(net)
        if args.drill_wedge is not None:
            model = wedge_gates.setdefault(engine_id, WedgeGate(model))
        if args.models > 1:
            # N named models sharing one set of weights: exercises the
            # whole model_id path (registry resolution, per-model
            # dispatch groups, labeled slices) without N× parameters
            from mxnet_tpu.serving import ModelRegistry
            reg = ModelRegistry()
            for i in range(args.models):
                reg.register(f"m{i}", model, version="v1")
            model = reg
        return ServingEngine(model, bucket_lens=buckets,
                             max_rows=args.max_rows, pool=args.pool,
                             engine_id=engine_id)

    if args.decode and args.router_url:
        # RouterClient speaks the encoder submit surface only; decode
        # params would be silently swallowed into the error column
        ap.error("--decode drives in-process engines (optionally with "
                 "--router N); --router-url is not supported yet")
    if args.decode and (args.tenants or args.models > 1):
        ap.error("--tenants/--models drive the encoder path (a decode "
                 "engine hosts exactly one model)")
    tenant_assign = (parse_tenant_spec(args.tenants)
                     if args.tenants else None)
    loadgen_models = ([f"m{i}" for i in range(args.models)]
                      if args.models > 1 else None)

    if args.drill_chaos:
        from mxnet_tpu import envvars
        if not envvars.get("MXNET_TPU_SLO"):
            ap.error("--drill-chaos needs the SLO engine "
                     "(MXNET_TPU_SLO=1)")
        if not envvars.get("MXNET_TPU_ROUTER_HA"):
            ap.error("--drill-chaos needs router HA "
                     "(MXNET_TPU_ROUTER_HA=1)")
        # the induced hot-spot must push the seat WELL past the
        # configured latency objective, or only the relative signals
        # shed weight and no page (= no incident) ever fires
        hot_ms = max(80.0, 2.5 * float(
            envvars.get("MXNET_TPU_SLO_LATENCY_MS")))
        report = run_chaos_drill(
            make_engine, n_engines=max(3, args.router or 3),
            n_clients=args.clients, vocab=args.vocab, hot_ms=hot_ms,
            min_len=args.min_len,
            max_len=min(args.max_len, max(buckets)))
        print(json.dumps(report, indent=2))
        ph = report["phases"]
        print("# chaos drill OK: hot-spot shed "
              f"{ph['hotspot']['target']} to weight "
              f"{ph['hotspot']['weight_min']} (share "
              f"{ph['hotspot']['hot_share']:.0%} vs fair "
              f"{ph['hotspot']['fair_share']:.0%}); "
              f"seat {ph['seat_kill']['victim']} replaced warm "
              f"(ttft {ph['seat_kill']['ttft_ms']} ms, "
              f"{ph['seat_kill']['manifest_shapes']} shapes); "
              f"router {ph['router_kill']['killed']} killed, "
              f"{ph['router_kill']['adopted']} in-flight adopted; "
              f"{len(report['incidents'])} incidents, "
              f"{report['completed']}/{report['attempts']} "
              "completed, zero lost", file=sys.stderr)
        return 0

    with contextlib.ExitStack() as stack:
        metrics_url = None
        if args.router_url:
            urls = args.router_url.split(",")
            target = RouterClient(urls)
            engines = []
            # the scrape cross-check needs ONE set of books: with a
            # single router its aggregated /metrics reconciles; with
            # a failover list the traffic may split across routers'
            # registries, so the delta would be an honest mismatch
            if len(urls) == 1 and not args.no_expose:
                metrics_url = urls[0].strip().rstrip("/") + "/metrics"
        elif args.router > 0:
            engines = [stack.enter_context(make_engine(f"e{i}"))
                       for i in range(args.router)]
            # warm BEFORE the router starts: its canary prober makes
            # day-one synthetic traffic, and at drill window scales a
            # cold fleet's first compiles outlast the absence window —
            # a startup page the operator did not ask to drill
            for eng in engines:
                eng.warmup()
            target = stack.enter_context(ServingRouter(engines=engines))
        else:
            engines = [stack.enter_context(make_engine())]
            target = engines[0]
            for eng in engines:
                eng.warmup()
        if not args.router_url and not args.no_expose:
            srv = target.expose(port=args.expose_port)
            metrics_url = srv.url("/metrics")
            print(f"# telemetry: {srv.url('/metrics')} "
                  f"{srv.url('/healthz')} {srv.url('/stats')}",
                  file=sys.stderr)
        if args.replay:
            from mxnet_tpu.serving.capture import load_corpus
            from mxnet_tpu.serving.capture import replay as _replay

            records, torn = load_corpus(args.replay)
            if not records:
                ap.error(f"--replay {args.replay}: no records loaded"
                         + (f" ({torn} torn/corrupt frames skipped)"
                            if torn else ""))
            pacing = (f"pacing x{args.speed:g}" if args.speed
                      else "max speed")
            print(f"# replay: {len(records)} records from "
                  f"{args.replay}"
                  + (f" ({torn} torn/corrupt frames skipped)"
                     if torn else "") + f", {pacing}",
                  file=sys.stderr)
            result = _replay(records, target, speed=args.speed)
            print(json.dumps(result, indent=2))
            div = result["divergences"]
            print(f"# replay done: {result['replayed']} replayed in "
                  f"{result['wall_s']}s, {result['matched']} matched "
                  f"({result['matched_bitwise']} byte-identical, "
                  f"{result['matched_within_tol']} float-tolerance), "
                  f"{len(div)} divergences, "
                  f"{len(result['errors'])} errors, "
                  f"{result['skipped']['not_completed']} "
                  "not-completed + "
                  f"{result['skipped']['no_payload']} payload-less "
                  "records skipped", file=sys.stderr)
            if div:
                slow = max(div, key=lambda d: d.get("replay_ms")
                           or 0.0)
                print("# slowest diverging request "
                      f"{slow['trace_id']} (model {slow['model']}): "
                      f"expected digest {slow['expected']}, got "
                      f"{slow['got']}"
                      + (f" (max |diff| {slow['max_abs_diff']:g})"
                         if slow.get("max_abs_diff") is not None
                         else "")
                      + f"; captured {slow['captured_ms']} ms vs "
                      f"replay {slow['replay_ms']} ms",
                      file=sys.stderr)
                bd = slow.get("breakdown") or {}
                for row in bd.get("stages") or ():
                    print(f"#   {row['stage']:<20} "
                          f"{row['ms']:>10.3f} ms "
                          f"({row['share']:.0%})", file=sys.stderr)
                if bd.get("unattributed_ms") is not None:
                    print(f"#   {'(unattributed)':<20} "
                          f"{bd['unattributed_ms']:>10.3f} ms",
                          file=sys.stderr)
            return 1 if (div or result["errors"]) else 0
        if args.drill_wedge is not None:
            if not args.router or args.router < 2:
                ap.error("--drill-wedge needs --router N with N >= 2 "
                         "(in-process engines the drill can gate)")
            if args.drill_wedge not in wedge_gates:
                ap.error(f"--drill-wedge {args.drill_wedge!r}: no such "
                         f"engine (have {sorted(wedge_gates)})")
            if target.alerts is None or target.canary is None:
                ap.error("--drill-wedge needs the SLO engine and the "
                         "canary prober (MXNET_TPU_SLO=1 and "
                         "MXNET_TPU_CANARY=1)")
            import tempfile

            from mxnet_tpu.telemetry.egress import (AlertNotifier,
                                                    FileSink)
            pages_path = args.pages or os.path.join(
                tempfile.mkdtemp(prefix="mxnet_tpu_drill_"),
                "pages.jsonl")
            print(f"# page notifications (file sink): {pages_path}",
                  file=sys.stderr)
            notifier = AlertNotifier(sinks=[FileSink(pages_path)])
            target.alerts.add_listener(notifier.notify)
            notifier.start()
            try:
                drill = wedge_drill(target, wedge_gates,
                                    args.drill_wedge, pages_path)
            finally:
                notifier.stop()
            print(json.dumps(drill, indent=2))
            print(f"# wedge drill OK: {drill['alert']} paged "
                  f"(incident {drill['incident_id']}), fired after "
                  f"{drill['fired_after_s']}s, closed after "
                  f"{drill['closed_after_s']}s, "
                  f"{drill['real_requests_completed']} real requests "
                  "completed, zero lost", file=sys.stderr)
            return 0
        if args.drill_overload:
            alerts_fn = get_trace = None
            if metrics_url:
                import urllib.request
                from urllib.parse import quote
                base = metrics_url.rsplit("/metrics", 1)[0]

                def alerts_fn():
                    with urllib.request.urlopen(base + "/alerts",
                                                timeout=10.0) as r:
                        return json.loads(r.read().decode())

                def get_trace(tid):
                    try:
                        with urllib.request.urlopen(
                                base + "/traces/" + quote(tid, safe=""),
                                timeout=10.0) as r:
                            return json.loads(r.read().decode())
                    except Exception:
                        return None

            drill_alert = (None if args.drill_overload == "auto"
                           else args.drill_overload)
            if drill_alert is None and args.router_url:
                # a RouterClient target has no scoreboard attr for the
                # auto-pick, but the peer IS a router
                drill_alert = "fleet_latency_fast_burn"
            drill = overload_drill(
                target, alerts_fn=alerts_fn, get_trace=get_trace,
                alert=drill_alert,
                n_clients=args.clients, min_len=args.min_len,
                max_len=args.max_len, vocab=args.vocab,
                deadline_ms=args.deadline_ms)
            print(json.dumps(drill, indent=2))
            print(f"# drill OK: {drill['alert']} walked "
                  f"{'→'.join(drill['states'])}; exemplar trace "
                  f"{drill['exemplar']['trace_id']} retrieved "
                  f"({drill['exemplar_trace_spans']} spans)",
                  file=sys.stderr)
            return 0
        if args.decode:
            sample_kw = {}
            if args.sample:
                parts = [p.strip() for p in args.sample.split(",")]
                sample_kw["temperature"] = float(parts[0])
                if len(parts) > 1:
                    sample_kw["top_k"] = int(parts[1])
                if len(parts) > 2:
                    sample_kw["top_p"] = float(parts[2])
                if len(parts) > 3:
                    sample_kw["sample_seed"] = int(parts[3])
            report = run_decode_load(
                target, n_clients=args.clients,
                requests_per_client=args.requests,
                min_prompt=args.min_len,
                max_prompt=min(args.max_len, max(buckets)),
                vocab=args.vocab, deadline_ms=args.deadline_ms,
                min_new=max(1, args.max_new // 4),
                max_new=args.max_new, stream=not args.no_stream,
                metrics_url=metrics_url, watch_engines=engines,
                prompt_reuse=args.prompt_reuse, **sample_kw)
        else:
            report = run_load(target, n_clients=args.clients,
                              requests_per_client=args.requests,
                              min_len=args.min_len,
                              max_len=args.max_len,
                              vocab=args.vocab,
                              deadline_ms=args.deadline_ms,
                              metrics_url=metrics_url,
                              tenants=tenant_assign,
                              model_ids=loadgen_models)
        if args.router_url:
            report["client_failovers"] = target.failovers
    print(json.dumps(report, indent=2))
    if report.get("streamed") is not None:
        print(f"# decode: {report['generated_tokens']} tokens at "
              f"{report['tokens_per_sec']}/s, ttft p50 "
              f"{report.get('ttft_p50_ms')} ms, inter-token p50/p99 "
              f"{report.get('inter_token_p50_ms')}/"
              f"{report.get('inter_token_p99_ms')} ms, "
              f"{report['stream_mismatches']} stream mismatches",
              file=sys.stderr)
        if report.get("prefix"):
            pfx = report["prefix"]
            rate = pfx.get("hit_rate")
            print(f"# prefix cache: hit rate "
                  f"{(f'{rate:.0%}' if rate is not None else 'n/a')} "
                  f"({pfx['hits']}/{pfx['lookups']} lookups), "
                  f"{pfx['tokens_reused']} tokens reused across "
                  f"{pfx['pages_reused']} pages, {pfx['cow_pages']} "
                  f"copy-on-writes, {pfx['evictions']} evictions",
                  file=sys.stderr)
        if report.get("sampling"):
            print(f"# sampling: temp={report['sampling']['temperature']} "
                  f"top_k={report['sampling']['top_k']} "
                  f"top_p={report['sampling']['top_p']} — streams "
                  "verified byte-identical to final results "
                  f"({report['stream_mismatches']} mismatches; with "
                  "--router failover this is the seeded replay check)",
                  file=sys.stderr)
    if report.get("per_engine"):
        total = max(1, sum(report["per_engine"].values()))
        print("# per-engine distribution: "
              + " ".join(f"{eid}={n} ({n / total:.0%})"
                         for eid, n in sorted(
                             report["per_engine"].items())),
              file=sys.stderr)
    for rec in report.get("restarts") or ():
        ttft = rec.get("ttft_ms")
        print(f"# engine restart observed: {rec['engine_id']} "
              f"downtime={rec.get('downtime_s')}s "
              f"time-to-first-token="
              f"{f'{ttft:.1f} ms' if ttft is not None else 'n/a'}",
              file=sys.stderr)
    if report.get("slowest_traces"):
        print("# slowest traces (span trees, while the ring holds "
              "them: python tools/telemetry_dump.py --trace <id> "
              "<base-url>):", file=sys.stderr)
        for rec in report["slowest_traces"]:
            print(f"#   {rec['ms']:>10.2f} ms  {rec['trace_id']}",
                  file=sys.stderr)
    if report.get("tenants"):
        for tenant, row in sorted(report["tenants"].items()):
            print(f"# tenant {tenant} ({row['class']}): "
                  f"{row['completed']} completed, {row['shed']} shed, "
                  f"{row['expired']} expired, p50/p99="
                  f"{row['p50_ms']}/{row['p99_ms']} ms, "
                  f"{row['client_tokens']} tokens billed",
                  file=sys.stderr)
    cost = report.get("cost")
    if cost:
        delta = cost.get("ledger_delta") or {}
        per_1k = cost.get("device_s_per_1k_tokens")
        print("# cost cross-check: client device_s="
              f"{cost['client_device_s']:.4f} ledger request_s="
              f"{(delta.get('request_s') or 0):.4f} requests="
              f"{cost['client_requests']}/{delta.get('requests')} "
              f"tokens={cost['client_tokens']}/"
              f"{delta.get('valid_tokens')}"
              + (f" device_s_per_1k_tokens={per_1k}"
                 if per_1k is not None else "")
              + f" reconciled={cost['reconciled']}", file=sys.stderr)
    can = report.get("canary")
    if can:
        total_probes = sum(sum(r.values())
                           for r in can["probes"].values())
        ok_probes = sum(r.get("ok", 0) for r in can["probes"].values())
        exc = can["excluded"]
        print(f"# canary (synthetic, excluded from cost books): "
              f"{ok_probes}/{total_probes} ok, transports="
              + ",".join(f"{t}={n}" for t, n in
                         sorted(can["by_transport"].items()))
              + f", excluded device_s={exc['device_s']:.4f} "
              f"requests={exc['requests']} tokens={exc['tokens']}",
              file=sys.stderr)
    rc = 0
    # a multi-URL --router-url list skips the scrape cross-check (no
    # single set of books), so there may be no server section at all
    if "server" in report and not args.no_expose \
            and not report["server"]["reconciled"]:
        print("# WARNING: server/client accounting mismatch: "
              + "; ".join(report["server"]["mismatches"]),
              file=sys.stderr)
        rc = 1
    if cost and cost["reconciled"] is False:
        print("# WARNING: cost-ledger mismatch: "
              + "; ".join(cost["mismatches"]), file=sys.stderr)
        rc = 1
    if report.get("tenants_reconciled") is False:
        print("# WARNING: per-tenant billing mismatch: "
              + "; ".join(report["tenant_mismatches"]),
              file=sys.stderr)
        rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(_main())
