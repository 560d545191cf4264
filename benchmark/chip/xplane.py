"""The reduction from a profiler trace (``.xplane.pb``) to numbers, with
``jax.profiler.ProfileData`` only.

A device plane is one named ``/device:TPU:<n>``; its busy time is the union
of the intervals of the events on its operations line (``XLA Ops``), which
are the device's own timestamps. The benchmark's host spans are events of
the host plane whose names are span names. Both sit on one clock.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Trace:
    """What the readers ask of one trace. Times are seconds."""

    def __init__(self, device_events, host_spans):
        # device_events: {device index: [(name, start_s, dur_s)]}
        # host_spans: [(name, start_s, end_s)]
        self.device_events = device_events
        self.host_spans = sorted(host_spans, key=lambda s: s[1])

    @classmethod
    def from_file(cls, path, span_names, device_ids=None):
        """``device_ids``: the devices the cell used; a chip the host holds
        besides them is left out (it would read as idle)."""
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        device_events, host_spans = {}, []
        names = set(span_names)
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                idx = int(plane.name[len(DEVICE_PREFIX):].split()[0])
                if device_ids is not None and idx not in device_ids:
                    continue
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    device_events[idx] = [
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in names:
                            s = ev.start_ns * 1e-9
                            host_spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
        return cls(device_events, host_spans)

    # ---- the window: from the first span's start to the last device event ----
    def window(self):
        starts = [s for _, s, _ in self.host_spans]
        ends = [e for _, _, e in self.host_spans]
        for evs in self.device_events.values():
            starts += [s for _, s, _ in evs[:1]]
            ends += [max(s + d for _, s, d in evs)] if evs else []
        return min(starts), max(ends)

    def busy_intervals(self, idx):
        return union((s, s + d) for _, s, d in self.device_events[idx])

    def busy_s(self):
        """Seconds with an operation on the device, averaged over devices."""
        if not self.device_events:
            return 0.0
        per = [sum(e - s for s, e in self.busy_intervals(i))
               for i in self.device_events]
        return sum(per) / len(per)

    def kernel_s(self, substring):
        """Summed device seconds of the events whose name has ``substring``,
        averaged over devices; None where no such event ran."""
        per, found = [], False
        for evs in self.device_events.values():
            t = [d for n, _, d in evs if substring in n]
            found = found or bool(t)
            per.append(sum(t))
        return sum(per) / len(per) if found else None

    def top_ops(self, k=10):
        """[[operation, seconds]] by summed device time; an operation is
        named by the left side of its HLO text (``%fusion.12``)."""
        tot = {}
        n = max(len(self.device_events), 1)
        for evs in self.device_events.values():
            for name, _, d in evs:
                name = name.split(" = ", 1)[0][:80]
                tot[name] = tot.get(name, 0.0) + d / n
        return sorted(([a, b] for a, b in tot.items()), key=lambda r: -r[1])[:k]

    def span_at(self, t):
        """The innermost benchmark span open on the host at time ``t``."""
        best = None
        for name, s, e in self.host_spans:
            if s > t:
                break
            if e >= t and (best is None or s >= best[1]):
                best = (name, s)
        return best[0] if best else "between_spans"

    def idle_gaps(self, k=10):
        """Idle seconds of the fullest-traced device inside the window, summed
        by the span that was open on the host when each gap began."""
        if not self.device_events:
            return []
        idx = min(self.device_events)
        w0, w1 = self.window()
        busy = self.busy_intervals(idx)
        tot = {}
        prev = w0
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                name = self.span_at(prev)
                tot[name] = tot.get(name, 0.0) + (s - prev)
            prev = max(prev, e)
        return sorted(([a, b] for a, b in tot.items()), key=lambda r: -r[1])[:k]
