"""Persistent compilation cache + warm-restart manifests.

Every engine restart used to recompile every shape bucket from
scratch: the CachedOp contract is "one engine op per subgraph,
compiled once", and this module extends that *once* across process
lifetimes. It is the single place the framework configures JAX's
on-disk compilation cache (``CachedOp`` tracing in
``gluon/block.py`` and executor binding in ``executor.py`` route
through :func:`configure`/:func:`ensure`), plus the warm-restart
manifest plumbing the serving fleet uses to replay visited shape
buckets before admitting traffic.

Cache keying: JAX keys each persisted executable on a hash of the
lowered computation (the traced graph — which embeds every input
shape/dtype, i.e. the serving shape bucket), the backend/platform,
the compile options, and the JAX version. Because CachedOp traces are
deterministic per (model, shape bucket, dtype/config) — parameter
*names* come from the per-process NameManager counters, which replay
identically for the same construction order — the same model served
in a fresh process lowers to an identical module and the executable
is fetched from disk instead of rebuilt: a ``persistent_hit``.

Hit/miss observability: a ``jax.monitoring`` listener counts the
cache's own ``cache_hits``/``cache_misses`` events into
``mxnet_tpu_compile_cache_persistent_total{result=...}``;
:func:`events_snapshot` + :func:`classify` let the serving engine
label each first-visit compile ``persistent_hit`` (served from disk)
vs ``miss`` (a fresh backend compile) next to its in-memory
``memory_hit`` outcomes.

Warmup manifests are plain JSON dicts::

    {"version": 1, "engines": ["e0", "e1"], "bucket_lens": [64, 256],
     "max_rows": 8, "shapes": [[1, 64], [2, 64], [8, 256]],
     "created": <wall ts>}

An engine exports its visited-shape manifest at ``/warmup`` (see
``ServingEngine.warmup_manifest``), the router's scoreboard poller
unions the fleet and persists it at ``MXNET_TPU_WARMUP_MANIFEST``,
and a restarting engine replays it with ``warmup(manifest=...)`` — a
rolling restart serves its first real request from a warm cache.

Env knobs (see ``envvars.py``): ``MXNET_TPU_COMPILE_CACHE`` (gate),
``MXNET_TPU_COMPILE_CACHE_DIR`` (yields to jax's own
``JAX_COMPILATION_CACHE_DIR``), ``MXNET_TPU_COMPILE_CACHE_MIN_S``,
``MXNET_TPU_WARMUP_MANIFEST``.
"""
from __future__ import annotations

import json
import os
import threading
import time

import jax
from jax._src import compilation_cache as _jax_cc
from jax._src import monitoring as _jax_monitoring

from . import envvars

__all__ = ["configure", "ensure", "enabled", "state", "events_snapshot",
           "classify", "manifest_path", "new_manifest", "manifest_shapes",
           "merge_manifests", "save_manifest", "load_manifest"]

# The directory is part of the cache key's locality: a cache that moves
# never hits. It is therefore placed from OUTSIDE the program or not at
# all — JAX_COMPILATION_CACHE_DIR (jax's own knob, left untouched), then
# MXNET_TPU_COMPILE_CACHE_DIR, then this one fixed path in the checkout
# (gitignored). Never $HOME, a temp name, a pid or a time.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_lock = threading.Lock()
_state = {"configured": False, "dir": None, "min_s": None}
_tally = {"persistent_hits": 0, "persistent_misses": 0}
_listener_installed = False

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _counters():
    from .telemetry.registry import REGISTRY

    fam = REGISTRY.counter(
        "mxnet_tpu_compile_cache_persistent_total",
        "on-disk compilation-cache outcomes (jax cache events), "
        "process-wide", ("result",))
    return {True: fam.labels(result="hit"),
            False: fam.labels(result="miss")}


def _on_cache_event(event, **kw):
    if event == _HIT_EVENT:
        hit = True
    elif event == _MISS_EVENT:
        hit = False
    else:
        return
    with _lock:
        _tally["persistent_hits" if hit else "persistent_misses"] += 1
    _on_cache_event._counters[hit].inc()


def _install_listener():
    global _listener_installed
    with _lock:
        # check-and-set under the lock: two engines' concurrent first
        # compiles must not register the listener twice (every cache
        # event would count double)
        if _listener_installed:
            return
        _listener_installed = True
        _on_cache_event._counters = _counters()
        _jax_monitoring.register_event_listener(_on_cache_event)


def configure(force=False):
    """Turn on JAX's persistent compilation cache and install the
    hit/miss event listener. Idempotent — repeat calls are no-ops once
    configured; ``force=True`` re-reads the environment.

    Where the cache lives is decided outside the program (see
    ``_DEFAULT_DIR``): when ``JAX_COMPILATION_CACHE_DIR`` is set jax
    already points there and this function sets no directory at all.

    Returns the effective state dict ``{"configured", "dir",
    "min_s"}`` (``configured=False`` when the
    ``MXNET_TPU_COMPILE_CACHE`` gate is off).
    """
    if not envvars.get("MXNET_TPU_COMPILE_CACHE"):
        return dict(_state)
    with _lock:
        if _state["configured"] and not force:
            return dict(_state)
    min_s = float(envvars.get("MXNET_TPU_COMPILE_CACHE_MIN_S"))
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.abspath(os.path.expanduser(
            envvars.get("MXNET_TPU_COMPILE_CACHE_DIR") or _DEFAULT_DIR))
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    # size floor off: whether an entry is worth persisting is the
    # compile-TIME knob's job (and tests set it to 0 to force
    # cross-process hits on trivially small computations)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax LATCHES "cache unused" on the first compile of the process
    # (is_cache_used memoizes per task) — any compile before this point
    # (model init, an eager op) would leave the cache permanently inert
    # despite the config. Reset so the next compile re-initializes
    # against the directory above.
    _jax_cc.reset_cache()
    _install_listener()
    with _lock:
        changed = (_state["dir"] != path or _state["min_s"] != min_s
                   or not _state["configured"])
        _state.update(configured=True, dir=path, min_s=min_s)
    if changed:
        from .telemetry import events as _events
        _events.emit("compile_cache_configured", dir=path,
                     min_compile_secs=min_s)
    return dict(_state)


def ensure():
    """Cheap hot-path guard: configure with defaults on first use
    (CachedOp trace time / executor bind time call this)."""
    with _lock:
        if _state["configured"]:
            return dict(_state)
    return configure()


def enabled():
    return bool(envvars.get("MXNET_TPU_COMPILE_CACHE"))


def state():
    with _lock:
        return dict(_state)


# ---------------------------------------------------------------------------
# hit/miss classification (the serving engine's 3-way counter split)
# ---------------------------------------------------------------------------

def events_snapshot():
    """Process-cumulative ``{"persistent_hits": n, "persistent_misses":
    n}`` from the jax cache-event listener. Diff two snapshots around a
    first-visit forward to classify it."""
    with _lock:
        return dict(_tally)


def classify(before, after):
    """Label one first-visit compile window from two
    :func:`events_snapshot` readings: ``"persistent_hit"`` when every
    compile in the window was served from the on-disk cache (hits
    advanced, zero fresh misses), else ``"miss"``.

    The tally is process-global (jax events carry no attribution), so
    a CONCURRENT compile elsewhere in the process can only leak its
    miss events into this window and downgrade a true persistent_hit
    to miss — never upgrade a real miss (its own miss event keeps the
    delta nonzero). The warm-restart signal is thus conservative."""
    hits = after["persistent_hits"] - before["persistent_hits"]
    misses = after["persistent_misses"] - before["persistent_misses"]
    return "persistent_hit" if hits > 0 and misses == 0 else "miss"


# ---------------------------------------------------------------------------
# warmup manifests
# ---------------------------------------------------------------------------

def manifest_path():
    """The configured fleet-manifest path (None when unset)."""
    return envvars.get("MXNET_TPU_WARMUP_MANIFEST")


def new_manifest(engine_id, bucket_lens, max_rows, shapes):
    return {"version": 1,
            "engines": [str(engine_id)],
            "bucket_lens": sorted(int(b) for b in bucket_lens),
            "max_rows": int(max_rows),
            "shapes": sorted([int(r), int(l)] for r, l in shapes),
            "created": round(time.time(), 3)}


def manifest_shapes(manifest):
    """The manifest's visited buckets as ``[(rows, row_len), ...]``
    (empty for None/malformed input — a bad manifest degrades to a
    cold start, never a crash)."""
    try:
        return sorted((int(r), int(l))
                      for r, l in (manifest or {}).get("shapes", ()))
    except (TypeError, ValueError):
        return []


def merge_manifests(parts):
    """Fleet union of several manifests (None entries skipped):
    shapes/buckets/engines union, ``max_rows`` max — the router's
    scoreboard poller folds every live engine's manifest through this.
    A structurally malformed part (a version-skewed remote engine's
    ``/warmup`` reply) is SKIPPED, not raised — same degrade-to-cold
    contract as :func:`manifest_shapes`. Returns None when nothing
    contributed."""
    engines, lens, shapes = set(), set(), set()
    max_rows = 0
    for m in parts:
        if not m:
            continue
        try:        # parse the whole part before touching the union:
            e = {str(x) for x in m.get("engines", ())}
            b = {int(x) for x in m.get("bucket_lens", ())}
            s = {(int(r), int(l)) for r, l in m.get("shapes", ())}
            mr = int(m.get("max_rows", 0))
        except (TypeError, ValueError, AttributeError):
            continue    # a bad part contributes nothing, not a crash
        engines |= e
        lens |= b
        shapes |= s
        max_rows = max(max_rows, mr)
    if not engines and not shapes:
        return None
    return {"version": 1, "engines": sorted(engines),
            "bucket_lens": sorted(lens), "max_rows": max_rows,
            "shapes": sorted(list(s) for s in shapes),
            "created": round(time.time(), 3)}


def save_manifest(manifest, path=None):
    """Atomically persist a manifest (tmp + rename — a reader never
    sees half a file). ``path`` defaults to the registered env knob;
    returns the path written, or None when there is nowhere to write."""
    path = path or manifest_path()
    if not path or manifest is None:
        return None
    path = os.path.abspath(os.path.expanduser(path))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_manifest(path=None):
    """Read a manifest back (None when the path is unset, missing, or
    unparsable — warm restart degrades to cold, loudly via the event)."""
    path = path or manifest_path()
    if not path:
        return None
    try:
        with open(os.path.expanduser(path)) as f:
            return json.load(f)
    except (OSError, ValueError):
        from .telemetry import events as _events
        _events.emit("warmup_manifest_unreadable", path=str(path))
        return None
