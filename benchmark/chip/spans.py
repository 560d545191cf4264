"""Spans recorded from the benchmark's own files around the calls into each
layer. In a traced run they are ``jax.profiler.TraceAnnotation``s, so they
sit on the device trace's clock; their host durations are summed beside.
Nothing blocks between the parts."""
from __future__ import annotations

import time
from contextlib import contextmanager


class Spans:
    def __init__(self, traced):
        self.traced = traced
        self.total_ns = {}
        self.count = {}

    def reset(self):
        self.total_ns.clear()
        self.count.clear()

    @contextmanager
    def __call__(self, name):
        if not self.traced:
            yield
            return
        import jax

        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.total_ns[name] = self.total_ns.get(name, 0) + time.perf_counter_ns() - t0
        self.count[name] = self.count.get(name, 0) + 1
