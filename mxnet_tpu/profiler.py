"""Profiler (python/mxnet/profiler.py + src/profiler/ analog).

Keeps the reference's Python API (`set_config`, `set_state('run'/'stop')`,
`dump`, scopes/markers, aggregate per-op stats) while delegating the
device timeline to jax.profiler (XProf/TensorBoard traces) — the
SURVEY §5.1 plan. Op-level wall stats are collected at the dispatch
layer when profiling is on and dumped as Chrome trace-event JSON, same
consumption path (chrome://tracing) as the reference's profiler output.

The Gluon training path's spans (`span`, `op_span`, `Scope`) are one
primitive: a ``jax.profiler.TraceAnnotation`` whenever a jax profiler
session is live, plus a `record_op` event under ``set_state('run')``;
`active()` says whether either holds. `count(name)` bumps and
`counters()` gives the always-on dispatch counts: the spans difference
`invokes`; a training loop polls `cachedop_builds` with tracing off to
catch a re-trace after warm-up, and `fused` / `looped` to see whether its
optimizer takes the compiled update (README, "Profiling a training step").
"""
from __future__ import annotations

import json
import os
import threading
import weakref
import time

import jax

from .telemetry.trace import current_trace_id as _current_trace_id

__all__ = ["set_config", "set_state", "state", "dump", "dumps", "pause",
           "resume", "Task", "Frame", "Event", "Counter", "Marker",
           "profiler_set_config", "profiler_set_state", "Scope",
           "export_metrics", "span", "active", "count", "counters"]

_CONFIG = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": False,
    "xprof_dir": None,
}
_STATE = {"running": False, "jax_trace": False}
_EVENTS: list = []
_AGGREGATE: dict = {}
_LOCK = threading.Lock()
# Always-on dispatch counts (`count` / `counters()`): `register.invoke`
# bumps "invokes", `HybridBlock._call_cached_op` "cachedop_builds" (and,
# from what that trace's rematerialised blocks keep, "remat_kept" and
# "remat_kept_bytes"), `Optimizer.update_multi` "fused" and "looped" (and
# "invokes", once, for its compiled program), the flash attention
# wrappers, while traced, "flash_tiles" and "flash_tiles_live", the sparse
# attention's selection kernel "dsa_topk_chunks" and "_live", the short
# convolution `F.causal_conv1d` "conv1d_calls" and, its kernel pair taken,
# "conv1d_kernel_calls". Not locked: a span reads the difference on its own
# thread, which is exact while no other thread dispatches (a training loop).
_COUNTS = {"invokes": 0, "cachedop_builds": 0, "fused": 0, "looped": 0,
           "remat_kept": 0, "remat_kept_bytes": 0,
           "flash_tiles": 0, "flash_tiles_live": 0, "dsa_layers": 0,
           "dsa_topk_chunks": 0, "dsa_topk_chunks_live": 0,
           "conv1d_calls": 0, "conv1d_kernel_calls": 0}
# bound once: `active()` is the one test `invoke` pays per op when off
_session_live = jax.profiler.TraceAnnotation.is_enabled


def set_config(**kwargs):
    _CONFIG.update(kwargs)


profiler_set_config = set_config


def set_state(state_name="stop", profile_process="worker"):
    if state_name == "run":
        if _STATE["running"]:
            # idempotent: re-entering 'run' while running must neither
            # re-enter jax.profiler.start_trace (it raises on a second
            # start) nor clobber the session's peak_memory_bytes
            return
        _STATE["running"] = True
        _STATE.pop("peak_memory_bytes", None)  # fresh session, fresh peak
        if _STATE.get("jax_trace"):
            # 'run' after pause(): the device trace is still active —
            # re-entering start_trace would raise and orphan it
            return
        if os.environ.get("MXNET_PROFILER_AUTOSTART") != "0" and _CONFIG.get("xprof_dir"):
            try:
                jax.profiler.start_trace(_CONFIG["xprof_dir"])
                _STATE["jax_trace"] = True
            except Exception:
                _STATE["jax_trace"] = False
    elif state_name == "stop":
        if not _STATE["running"] and not _STATE.get("jax_trace"):
            return                             # idempotent no-op
        _STATE["running"] = False
        if _STATE.get("jax_trace"):
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            _STATE["jax_trace"] = False
    else:
        raise ValueError("state must be 'run' or 'stop'")


profiler_set_state = set_state


def state():
    return "run" if _STATE["running"] else "stop"


def peak_memory_bytes():
    """Peak device bytes_in_use observed across profiled ops (requires
    set_config(profile_memory=True) and a backend with memory stats;
    returns None if nothing was sampled)."""
    return _STATE.get("peak_memory_bytes")


def is_running():
    return _STATE["running"]


def active():
    """Do spans record now? Under ``set_state('run')``, or while ANY jax
    profiler session is live (``jax.profiler.start_trace``, the xprof
    server): whoever starts a device trace gets the program's spans in
    it with no further switch."""
    return _STATE["running"] or _session_live()


def count(name, n=1):
    """Bump one of the always-on counts (the dispatch layer's call)."""
    _COUNTS[name] += n


def counters(device=True):
    """The always-on dispatch counts, whether or not anything records:
    ``invokes`` (`register.invoke` calls: what a span's ``invokes`` is
    the difference of), ``cachedop_builds`` (traces of hybridized
    blocks: flat once every shape is warm, so a loop that logs it beside
    its step time sees a re-trace without a profiler session), and
    ``fused`` / ``looped`` (parameters `Optimizer.update_multi` put
    through its one compiled program / through the per-key loop: a
    ``looped`` that grows by the model's size each step names an
    optimizer that dispatches eagerly, parameter by parameter), and
    ``remat_kept`` / ``remat_kept_bytes`` (values, and their bytes, that
    blocks marked ``hybridize(remat=True)`` keep from their forward for
    their backward because a kernel named them dear to rebuild — a flash
    attention call's output and log-sum-exp; a group of rows under
    ``remat_rows`` counts each time. Tallied when a block is traced under
    ``autograd.record``, so flat across steps like ``cachedop_builds``;
    0 with remat'd attention layers means their forward runs twice a step).
    ``flash_tiles`` / ``flash_tiles_live``: the (q tile, kv tile) pairs of
    the score rectangles of every flash attention kernel call traced so far
    (forward and backward calls alike), and those of them that the call's
    static masks (causal, window) leave live, which are the ones that do
    matmul work; the ratio says how much of S^2 a model's attention layers
    skip, and one near 1 under a window says the window is masked inside
    tiles, not skipped. ``dsa_layers``: sparse attention layers (an
    indexer's selection over the flash kernel) traced so far.
    ``dsa_topk_chunks`` / ``dsa_topk_chunks_live``: of every call of the
    selection's kernel ``mxtpu_dsa_topk`` traced so far, the (row block,
    column chunk) visits that 32 counting passes and the writing pass over
    the whole score matrix would make, and those the kernel makes (only up
    to a block's last causal column, and one where no row of the block has
    more causal keys than ``top_k``): 0 and 0 say the XLA form ran, a ratio
    of 1 that the kernel skips nothing. ``conv1d_calls`` /
    ``conv1d_kernel_calls``: calls of the short convolution
    ``F.causal_conv1d`` traced so far, and those of them that took the Pallas
    kernel pair ``mxtpu_conv1d_fwd`` / ``_bwd`` (on the chip, at a shape its
    tiles divide); equal on a chip at published widths, the second 0 where
    the ``jax.numpy`` form ran. Trace-time tallies, flat across steps.

    Blocks that count on the device (`register_device_counters`: an expert
    layer's ``running_slots``) are read here, when the operator polls and
    never inside a step: ``moe_slots`` (slots sent to the experts held,
    summed over the layers), ``moe_dropped`` (slots no branch computed:
    0), and ``moe_slots/<layer>`` (the list per held expert); a sparse
    attention layer's ``running_pairs``: ``dsa_pairs_selected`` (pairs its
    selections kept: the live entries of the masks the kernel was given) and
    ``dsa_pairs_causal`` (key <= query pairs), summed over the layers.
    ``device=False`` leaves them out: the host counts alone, free of any
    device read, for a loop that polls every step."""
    out = dict(_COUNTS)
    for block in list(_DEVICE_COUNTERS) if device else ():
        for key, value in block.device_counters().items():
            if isinstance(value, list):
                out[key] = value
            else:
                out[key] = out.get(key, 0.0) + value
    return out


# What a kernel named with `jax.ad_checkpoint.checkpoint_name` while a
# rematerialised block was being traced: [(name, bytes)]. Trace time
# only; nothing listens, and `note_named` returns at once, in a step.
_NAMED = threading.local()


def note_named(name, value):
    """A kernel's word, while it is traced, that ``value`` carries the
    checkpoint name ``name`` (`ops/pallas/flash_attention.py`)."""
    heard = getattr(_NAMED, "heard", None)
    if heard is not None:
        heard.append((name, value.size * value.dtype.itemsize))


class named_values:
    """``with named_values() as heard:`` — the (name, bytes) of every
    value named inside (`HybridBlock`'s remat sites, around the trace of
    the block they checkpoint)."""

    def __enter__(self):
        self._outer = getattr(_NAMED, "heard", None)
        _NAMED.heard = heard = []
        return heard

    def __exit__(self, *exc):
        _NAMED.heard = self._outer
        return False


_DEVICE_COUNTERS = weakref.WeakSet()


def register_device_counters(block):
    """Have `counters()` include ``block.device_counters()`` ({name: number,
    summed over blocks, or a list, kept per block}) for as long as the
    block lives."""
    _DEVICE_COUNTERS.add(block)


def _device_bytes_in_use():
    """Live device memory (reference src/profiler/ memory profiling
    analog): PJRT memory_stats on an accelerator, the live-jax.Array
    byte total on the CPU backend only (telemetry.resources has the
    one rule)."""
    from .telemetry import resources

    return max(resources.device_memory())


def record_op(name, begin_us, end_us, category="operator", args=None):
    """Called from the dispatch layer (ThreadedEngine ProfileOperator
    analog). ``args`` lands in the Chrome-trace event's ``args`` dict —
    `Scope` stamps the active telemetry trace id through it so one
    request is findable in the device trace."""
    if not _STATE["running"]:
        return
    with _LOCK:
        ev = {"name": name, "cat": category, "ph": "X",
              "ts": begin_us, "dur": end_us - begin_us,
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        if _CONFIG["profile_memory"]:
            mem = _device_bytes_in_use()
            ev.setdefault("args", {})["bytes_in_use"] = mem
            peak = _STATE.get("peak_memory_bytes", 0)
            _STATE["peak_memory_bytes"] = max(peak, mem)
        _EVENTS.append(ev)
        if _CONFIG["aggregate_stats"]:
            agg = _AGGREGATE.setdefault(name, [0, 0.0, float("inf"), 0.0])
            dur = (end_us - begin_us) / 1e3
            agg[0] += 1
            agg[1] += dur
            agg[2] = min(agg[2], dur)
            agg[3] = max(agg[3], dur)


def dump(finished=True, profile_process="worker"):
    """Write Chrome trace-event JSON to the configured filename.

    The telemetry span ring (kept tail-sampled traces + in-flight
    spans) merges into the same stream — span and op events share one
    perf_counter microsecond axis, so chrome://tracing shows a slow
    request's queue/pack/forward spans next to the op timeline."""
    from .telemetry import spans as _spans
    span_events = _spans.export_chrome_events()
    with _LOCK:
        payload = {"traceEvents": list(_EVENTS) + span_events,
                   "displayTimeUnit": "ms"}
        with open(_CONFIG["filename"], "w") as f:
            json.dump(payload, f)
        if finished:
            _EVENTS.clear()


def dumps(reset=False, format="table"):
    """Aggregate per-op stats table (src/profiler/aggregate_stats.cc)."""
    with _LOCK:
        lines = [f"{'Name':<40}{'Count':>8}{'Total(ms)':>12}{'Min(ms)':>10}{'Max(ms)':>10}{'Avg(ms)':>10}"]
        for name, (cnt, tot, mn, mx) in sorted(_AGGREGATE.items(),
                                               key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<40}{cnt:>8}{tot:>12.3f}{mn:>10.3f}{mx:>10.3f}{tot / cnt:>10.3f}")
        if reset:
            _AGGREGATE.clear()
        return "\n".join(lines)


def export_metrics(registry=None):
    """Publish the aggregate per-op stats (``aggregate_stats=True``
    sessions) onto a telemetry registry as gauges —
    ``mxnet_tpu_profiler_op_calls{op=...}`` /
    ``..._op_total_ms{op=...}`` / ``..._op_max_ms{op=...}`` — so a
    /metrics scrape sees the same table ``dumps()`` prints. Returns
    the number of ops exported."""
    from .telemetry.registry import REGISTRY
    reg = registry if registry is not None else REGISTRY
    calls = reg.gauge("mxnet_tpu_profiler_op_calls",
                      "profiled calls per op", ("op",))
    total = reg.gauge("mxnet_tpu_profiler_op_total_ms",
                      "profiled wall ms per op", ("op",))
    mx_ms = reg.gauge("mxnet_tpu_profiler_op_max_ms",
                      "profiled max wall ms per op", ("op",))
    with _LOCK:
        agg = {name: tuple(v) for name, v in _AGGREGATE.items()}
    for name, (cnt, tot, _mn, mx) in agg.items():
        calls.labels(op=name).set(cnt)
        total.labels(op=name).set(round(tot, 3))
        mx_ms.labels(op=name).set(round(mx, 3))
    return len(agg)


def pause(profile_process="worker"):
    _STATE["running"] = False


def resume(profile_process="worker"):
    _STATE["running"] = True


class _Named:
    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


class Task(_Named):
    def __init__(self, domain=None, name="task", args=None):
        super().__init__(name)
        self._start = None
        self._args = args

    def start(self):
        self._start = time.perf_counter_ns() // 1000

    def stop(self):
        if self._start is not None:
            record_op(self.name, self._start, time.perf_counter_ns() // 1000,
                      "task", args=self._args)
            self._start = None


class Frame(Task):
    pass


class Event(Task):
    pass


class Counter(_Named):
    def __init__(self, domain=None, name="counter", value=0):
        super().__init__(name)
        self.value = value

    def set_value(self, value):
        self.value = value

    def increment(self, delta=1):
        self.value += delta

    def decrement(self, delta=1):
        self.value -= delta


class Marker(_Named):
    def __init__(self, domain=None, name="marker"):
        super().__init__(name)

    def mark(self, scope="process"):
        now = time.perf_counter_ns() // 1000
        record_op(self.name, now, now, "marker")


class _NoSpan:
    """What `span` hands out while nothing records: one shared object."""
    __slots__ = ()
    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NO_SPAN = _NoSpan()


class _Span:
    """One open span: a ``jax.profiler.TraceAnnotation`` (so it sits in
    the profiler's trace, on the device's clock, nested by the thread's
    own stack) and, under ``set_state('run')``, a `record_op` event for
    `dump()` / `dumps()`. Degrades to the wall-clock event alone when
    the annotation raises (a broken device-trace backend must not take
    a serving worker down)."""
    __slots__ = ("name", "attrs", "category", "record_as", "_ann", "_t0_us",
                 "_invokes0")
    live = True

    def __init__(self, name, attrs, category="span", record_as=None):
        self.name, self.attrs = name, attrs
        self.category, self.record_as = category, record_as or name

    def set(self, **attrs):
        """Attributes known only before exit (counts, flags)."""
        self.attrs.update(attrs)

    def __enter__(self):
        self._invokes0 = _COUNTS["invokes"]
        self._t0_us = (time.perf_counter_ns() // 1000
                       if _STATE["running"] else None)
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:
            self._ann = None          # wall-clock-only span
        return self

    def __exit__(self, *exc):
        if self.category == "span":
            self.attrs["invokes"] = _COUNTS["invokes"] - self._invokes0
        if self._ann is not None:
            try:
                if self.attrs:
                    self._ann.set_metadata(**self.attrs)
                self._ann.__exit__(*exc)
            except Exception:
                pass
        if self._t0_us is not None:
            record_op(self.record_as, self._t0_us,
                      time.perf_counter_ns() // 1000, self.category,
                      args=self.attrs)
        return False


def span(name, **attrs):
    """``with profiler.span('mxtpu/trainer/step', batch_size=n) as sp:``
    — the one span primitive of the training path. Not `active()`: the
    shared no-op, no allocation, no clock read. Active: the span lands in
    the live jax profiler trace with ``attrs`` (and what ``sp.set(...)``
    adds before exit) in the event's stats, plus ``invokes``, the number
    of `register.invoke` calls made inside it; under ``set_state('run')``
    it is also a ``category='span'`` event of `dump()` and a row of
    `dumps()`. ``sp.live`` says whether attributes are worth computing."""
    return _Span(name, attrs) if active() else _NO_SPAN


def op_span(op_name):
    """`register.invoke`'s span around one op's dispatch:
    ``mxtpu/op/<op_name>`` in the trace; under ``set_state('run')`` the
    operator event keeps the op's bare name, as `dumps()` lists it."""
    if not active():
        return _NO_SPAN
    return _Span("mxtpu/op/" + op_name, None, "operator", op_name)


class Scope:
    """with profiler.Scope('fwd'): ... — custom range: a `span` that
    also carries the active telemetry trace id (serving request ids
    minted at ``ServingEngine.submit``), so one request correlates
    across the wall-clock and device timelines."""

    def __init__(self, name="scope"):
        self.name = name

    def __enter__(self):
        tid = _current_trace_id()
        self._span = span(self.name, **({"trace_id": tid} if tid else {}))
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        return self._span.__exit__(*exc)
