#!/usr/bin/env python3
"""The program's own spans and the device's executed programs, read from the
profiler trace a run just wrote.

While any jax profiler session is live (``run.py --trace 1`` starts one), or
under ``mx.profiler.set_state('run')``, the Gluon training path writes spans
named ``mxtpu/...`` into the trace through ``mxnet_tpu/profiler.py``'s
``span`` (``jax.profiler.TraceAnnotation``: host plane, the device trace's
clock, nested by the thread's own stack, attributes in the event's stats):

    mxtpu/cachedop/call     HybridBlock._call_cached_op    block, built, invokes
    mxtpu/cachedop/build      _build_cached_op (in call)   block
    mxtpu/autograd/backward autograd.backward              nodes, invokes
    mxtpu/trainer/step      Trainer.step                   step, batch_size, invokes
    mxtpu/trainer/allreduce   _allreduce_grads (in step)   keys
    mxtpu/trainer/update      _update (in step)            params
    mxtpu/kvstore/pushpull      KVStore.pushpull           keys, bytes
    mxtpu/op/<op.name>      register.invoke, one per call  -

``invokes`` is the number of ``register.invoke`` calls made inside the span.
The number of device programs needs no counter of the program's: the device
plane's ``XLA Modules`` line has one event per executed program.

A span belongs to the step that the next ``mxtpu/trainer/step`` closes: the
first step span to end at or after the end of the span's outermost
``mxtpu/`` ancestor. What ends after the last step belongs to none.

By hand, after a ``--trace 1`` run (or on any ``.xplane.pb``):

    python benchmark/chip/program_spans.py [<dir-or-xplane.pb>] [--step N] [--chips C]

prints the counts per step, the nested spans of one step with their
attributes, and that step's ops by host time and count (which op dispatches
cost the step its host time, and how many programs each invoke became).

The benchmark's readers (``readers/program_*.py``, ``readers/invokes_per_step.py``)
call ``of(ctx)``: ``run.py`` hands readers no path, so the trace is the newest
``*.xplane.pb`` under ``.bench_trace/*/`` by modification time, which is right
in a process that runs one cell; it is parsed once per process. A trace of a
program without these spans (an older commit) gives readers nothing to read.
"""
from __future__ import annotations

import collections
import glob
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

PREFIX = "mxtpu/"
OP_PREFIX = "mxtpu/op/"
STEP = "mxtpu/trainer/step"
DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

# parent / top: indices into ProgramTrace.spans (None: no mxtpu/ ancestor;
# top is the span's outermost ancestor, itself where it has none)
Span = collections.namedtuple("Span", "name start end stats parent top")


def newest_trace(root=None):
    """The newest ``*.xplane.pb`` anywhere under ``root`` (default
    ``<checkout>/.bench_trace``: one directory a cell), by modification
    time; None where there is none."""
    root = root or os.path.join(ROOT, ".bench_trace")
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


class ProgramTrace:
    """What the readers ask of one trace. Times are seconds."""

    def __init__(self, spans, modules):
        # spans: [Span], in start order; modules: {device index: [(name, start_s, dur_s)]}
        self.spans, self.modules = spans, modules
        self.steps = sorted((s for s in spans if s.name == STEP), key=lambda s: s.end)

    @classmethod
    def from_file(cls, path, chips=None):
        """``chips``: the cell's devices are ``/device:TPU:<i>``, i < chips
        (as the loop makes its contexts); None takes every device plane."""
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        rows, modules = [], {}
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                idx = int(plane.name[len(DEVICE_PREFIX):].split()[0])
                if chips is not None and idx >= chips:
                    continue
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        modules[idx] = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                                        for ev in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    rows += _nest([ev for ev in line.events if ev.name.startswith(PREFIX)],
                                  len(rows))
        order = sorted(range(len(rows)), key=lambda i: (rows[i][1], -rows[i][2]))
        new = {old: k for k, old in enumerate(order)}
        spans = [Span(n, s * 1e-9, e * 1e-9, st, None if p is None else new[p], new[t])
                 for n, s, e, st, p, t in (rows[i] for i in order)]
        return cls(spans, modules)

    # ---- steps -------------------------------------------------------------------
    def step_of(self, span):
        """Index of the step ``span`` belongs to, or None."""
        end = self.spans[span.top].end
        for k, st in enumerate(self.steps):
            if st.end >= end:
                return k
        return None

    def in_steps(self, match):
        """The spans for which ``match(span)`` holds and that belong to a
        step, outermost only (one whose ancestor also matches is left out)."""
        out = []
        for s in self.spans:
            if not match(s) or self.step_of(s) is None:
                continue
            p = s.parent
            while p is not None and not match(self.spans[p]):
                p = self.spans[p].parent
            if p is None:
                out.append(s)
        return out

    def ms_per_step(self, name):
        """Mean host milliseconds a step inside the spans called ``name``
        (a trailing ``*`` matches a prefix); None where there is no step or
        no such span."""
        found = self.in_steps(matcher(name))
        if not self.steps or not found:
            return None
        return 1e3 * sum(s.end - s.start for s in found) / len(self.steps)

    def invokes_per_step(self):
        """(from the counter, from the events): ``register.invoke`` calls a
        step, once as the sum of ``invokes`` over the steps' outermost spans
        (an op span carries no count: under an outermost op span the events
        are all there is), once as the count of ``mxtpu/op/*`` events; None
        where there is no step."""
        if not self.steps:
            return None
        counted = events = 0
        for s in self.spans:
            if self.step_of(s) is None:
                continue
            if s.name.startswith(OP_PREFIX):
                events += 1
                counted += self.spans[s.top].name.startswith(OP_PREFIX)
            elif s.parent is None:
                counted += s.stats.get("invokes", 0)
        return counted / len(self.steps), events / len(self.steps)

    def programs_per_step(self, steps=None):
        """Executed device programs (``XLA Modules`` events) over ``steps``
        (the trace's own step spans where not given), mean over devices."""
        steps = steps or len(self.steps)
        if not steps or not self.modules:
            return None
        return sum(len(v) for v in self.modules.values()) / len(self.modules) / steps

    def count(self, name):
        """How many spans are called ``name``; None in a trace without any
        ``mxtpu/`` span (a program that has none: nothing to read)."""
        if not self.spans:
            return None
        return sum(1 for s in self.spans if s.name == name)


def matcher(name):
    if name.endswith("*"):
        return lambda s: s.name.startswith(name[:-1])
    return lambda s: s.name == name


def _nest(events, base):
    """Rows (name, start_ns, end_ns, stats, parent, top) of one thread
    line's events, parents by containment; indices start at ``base``."""
    rows, stack = [], []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        end = ev.start_ns + ev.duration_ns
        while stack and rows[stack[-1] - base][2] < end:
            stack.pop()
        parent = stack[-1] if stack else None
        idx = base + len(rows)
        top = idx if parent is None else rows[parent - base][5]
        rows.append((ev.name, ev.start_ns, end,
                     dict(ev.stats), parent, top))
        stack.append(idx)
    return rows


_LOADED = {}


def load(path=None, chips=None):
    """The parsed trace at ``path`` (the newest under ``.bench_trace`` where
    not given), parsed once per process; None where there is no trace."""
    path = path or newest_trace()
    if path is None:
        return None
    key = (os.path.abspath(path), chips)
    if key not in _LOADED:
        _LOADED[key] = ProgramTrace.from_file(path, chips)
    return _LOADED[key]


def of(ctx):
    """The readers' entry: ``ctx['program_trace']`` where a caller (a test)
    gives one, else the trace this process just wrote."""
    if ctx.get("program_trace") is not None:
        return ctx["program_trace"]
    return load(chips=ctx.get("chips"))


# ---- by hand ----------------------------------------------------------------------

def report(pt, step=None, top=15, out=print):
    n = len(pt.steps)
    out(f"{len(pt.spans)} mxtpu/ spans, {n} steps, device programs "
        f"{ {i: len(v) for i, v in pt.modules.items()} }")
    if not n:
        return
    inv = pt.invokes_per_step()
    progs = pt.programs_per_step()
    out(f"per step: invokes {inv[0]:.1f} (counter) / {inv[1]:.1f} (events), "
        f"programs {progs if progs is None else round(progs, 1)}")
    for name in ("mxtpu/cachedop/call", "mxtpu/autograd/backward", STEP,
                 "mxtpu/trainer/allreduce", "mxtpu/trainer/update",
                 "mxtpu/kvstore/pushpull", OP_PREFIX + "*"):
        ms = pt.ms_per_step(name)
        out(f"  {name:<28}{'-' if ms is None else format(ms, '.3f'):>12} ms a step")
    k = n // 2 if step is None else step
    out(f"step {k} (of 0..{n - 1}), spans nested, ops folded:")
    depth = {}
    ops = collections.defaultdict(lambda: [0, 0.0])
    for i, s in enumerate(pt.spans):
        depth[i] = 0 if s.parent is None else depth[s.parent] + 1
        if pt.step_of(s) != k:
            continue
        if s.name.startswith(OP_PREFIX):
            if s.parent is None or not pt.spans[s.parent].name.startswith(OP_PREFIX):
                ops[s.name][0] += 1
                ops[s.name][1] += s.end - s.start
            continue
        out(f"  {'  ' * depth[i]}{s.name}  {1e3 * (s.end - s.start):.3f} ms  {s.stats}")
    out(f"  ops by host time (outermost {OP_PREFIX}* spans):")
    for name, (cnt, sec) in sorted(ops.items(), key=lambda kv: -kv[1][1])[:top]:
        out(f"    {name[len(OP_PREFIX):]:<36}{cnt:>6} x {1e3 * sec / cnt:>9.3f} ms"
            f" = {1e3 * sec:>10.3f} ms")


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="the program's spans in one trace")
    ap.add_argument("path", nargs="?", help="a trace directory or an .xplane.pb; "
                    "default: the newest under .bench_trace/")
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--chips", type=int, default=None)
    args = ap.parse_args(argv)
    path = args.path
    if path and os.path.isdir(path):
        path = newest_trace(path)
    pt = load(path, args.chips)
    if pt is None:
        raise SystemExit("no .xplane.pb found")
    report(pt, args.step)


if __name__ == "__main__":
    main()
