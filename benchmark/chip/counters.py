"""Programs obtained by this process: backend compiles (jax's own duration
event, every one however small) and executables fetched from the persistent
cache (jax's cache-hit event). Copied from ``chip_smoke.compiles_so_far``;
listens to jax itself, not to the program."""
from __future__ import annotations

_SEEN = {"installed": False, "backend_compiles": 0, "persistent_hits": 0,
         "persistent_misses": 0}


def install():
    import jax

    if _SEEN["installed"]:
        return

    def on_duration(event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _SEEN["backend_compiles"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            _SEEN["persistent_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _SEEN["persistent_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _SEEN["installed"] = True


def snapshot():
    return {k: v for k, v in _SEEN.items() if k != "installed"}


def since(before):
    return {k: v - before[k] for k, v in snapshot().items()}


def memory_peak_bytes(devices):
    """Peak bytes on the fullest of ``devices``. The TPU runtime keeps two
    peaks: the allocator's (arrays) and the region it reserves for loaded
    programs' temporaries, which the allocator's does not count. The larger
    of the two is reported: a lower bound of the true peak that can never
    exceed the chip."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):     # the CPU backend keeps none
        return 0
    return max(max(int(s["peak_bytes_in_use"]),
                   int(s.get("peak_bytes_reserved", 0))) for s in stats)
