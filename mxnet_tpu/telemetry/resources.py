"""Host + device resource accounting: what this process costs to run.

The serving/metrics stack answers *how fast*; this module answers *how
big*: host RSS, open file descriptors, live threads (all read off
``/proc/self`` — stdlib-only, graceful zeros off Linux), and device
memory (PJRT ``memory_stats()`` where the backend provides them, the
byte total of live ``jax.Array``\\ s as the framework-tracked fallback
— graceful zeros on backends with neither).

Two consumption paths:

- **gauges** on the process registry
  (``mxnet_tpu_resource_rss_bytes`` etc.), refreshed by
  :func:`sample` — the continuous-profiler daemon
  (:mod:`.profiling`) calls it every ``MXNET_TPU_PROF_RESOURCE_S``
  seconds, so a ``/metrics`` scrape of any serving process carries
  its resource footprint without extra wiring;
- **watermarks**: :func:`sample` also folds each reading into
  process-lifetime peaks (``rss_peak_bytes`` / ``device_peak_bytes``)
  — the per-leg bench records carry them so a memory regression shows
  up in ``bench_suite_summary``, not just in an OOM three legs later.

Everything here must stay cheap enough to run every second forever: a
few ``/proc`` reads and one pass over live device arrays.
"""
from __future__ import annotations

import os
import threading

from .registry import REGISTRY

__all__ = ["snapshot", "sample", "watermarks", "reset_watermarks",
           "compact"]

_lock = threading.Lock()
_peaks = {"rss_peak_bytes": 0, "device_peak_bytes": 0}

_g_rss = REGISTRY.gauge(
    "mxnet_tpu_resource_rss_bytes",
    "host resident-set size of this process (from /proc/self/statm)")
_g_fds = REGISTRY.gauge(
    "mxnet_tpu_resource_open_fds",
    "open file descriptors of this process")
_g_threads = REGISTRY.gauge(
    "mxnet_tpu_resource_threads",
    "live Python threads in this process")
_g_dev = REGISTRY.gauge(
    "mxnet_tpu_resource_device_bytes_in_use",
    "device bytes in use per PJRT memory_stats (0 when the backend "
    "reports none, e.g. CPU)")
_g_live = REGISTRY.gauge(
    "mxnet_tpu_resource_live_buffer_bytes",
    "byte total of live jax.Array buffers (framework-tracked "
    "allocations; the CPU-visible device-memory proxy)")
_g_rss_peak = REGISTRY.gauge(
    "mxnet_tpu_resource_rss_peak_bytes",
    "process-lifetime peak of mxnet_tpu_resource_rss_bytes as sampled")
_g_dev_peak = REGISTRY.gauge(
    "mxnet_tpu_resource_device_peak_bytes",
    "process-lifetime peak of max(device bytes in use, live buffer "
    "bytes) as sampled")

_page_size = None


def _pagesize():
    global _page_size
    if _page_size is None:
        try:
            _page_size = os.sysconf("SC_PAGE_SIZE")
        except (ValueError, OSError, AttributeError):
            _page_size = 4096
    return _page_size


def rss_bytes():
    """Resident-set bytes from ``/proc/self/statm`` (0 off Linux)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _pagesize()
    except (OSError, ValueError, IndexError):
        return 0


def open_fds():
    """Open fd count from ``/proc/self/fd`` (0 off Linux)."""
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return 0


def thread_count():
    return threading.active_count()


def device_memory():
    """``(bytes_in_use, live_buffer_bytes)`` of the first local device.
    An accelerator reports its allocator's own count through PJRT
    ``memory_stats()`` and the second number is 0. The CPU backend
    keeps no such stats, so there — and only there — the footprint is
    the byte total of live jax.Arrays: what the framework allocated,
    not what the runtime's pool holds."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return 0, int(sum(a.nbytes for a in jax.live_arrays()))
    return int(dev.memory_stats()["bytes_in_use"]), 0


def snapshot():
    """One reading of every resource signal (no gauge/watermark side
    effects — :func:`sample` is the mutating sweep)."""
    in_use, live = device_memory()
    return {"rss_bytes": rss_bytes(),
            "open_fds": open_fds(),
            "threads": thread_count(),
            "device_bytes_in_use": in_use,
            "live_buffer_bytes": live}


def sample():
    """Take one reading, refresh the registry gauges, fold the
    watermarks, and return the snapshot dict (with peaks included).
    This is what the profiler daemon runs every
    ``MXNET_TPU_PROF_RESOURCE_S`` seconds."""
    snap = snapshot()
    dev = max(snap["device_bytes_in_use"], snap["live_buffer_bytes"])
    with _lock:
        if snap["rss_bytes"] > _peaks["rss_peak_bytes"]:
            _peaks["rss_peak_bytes"] = snap["rss_bytes"]
        if dev > _peaks["device_peak_bytes"]:
            _peaks["device_peak_bytes"] = dev
        peaks = dict(_peaks)
    _g_rss.set(snap["rss_bytes"])
    _g_fds.set(snap["open_fds"])
    _g_threads.set(snap["threads"])
    _g_dev.set(snap["device_bytes_in_use"])
    _g_live.set(snap["live_buffer_bytes"])
    _g_rss_peak.set(peaks["rss_peak_bytes"])
    _g_dev_peak.set(peaks["device_peak_bytes"])
    snap.update(peaks)
    return snap


def watermarks():
    """Process-lifetime peaks over every :func:`sample` so far."""
    with _lock:
        return dict(_peaks)


def reset_watermarks():
    """Start a fresh watermark window (a bench leg measuring only its
    own footprint)."""
    with _lock:
        _peaks["rss_peak_bytes"] = 0
        _peaks["device_peak_bytes"] = 0


def compact():
    """Rounded-MB view for bench records (one fresh sample folded in,
    so a leg that never ran the daemon still reports real numbers)."""
    snap = sample()
    mb = 1024.0 * 1024.0
    return {"rss_mb": round(snap["rss_bytes"] / mb, 1),
            "rss_peak_mb": round(snap["rss_peak_bytes"] / mb, 1),
            "device_mem_mb": round(
                max(snap["device_bytes_in_use"],
                    snap["live_buffer_bytes"]) / mb, 1),
            "device_peak_mb": round(snap["device_peak_bytes"] / mb, 1),
            "open_fds": snap["open_fds"],
            "threads": snap["threads"]}
