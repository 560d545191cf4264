#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, event counts and samples, then
what the reduction makes of it. ``python inspect_trace.py <dir-or-xplane.pb>``"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(path):
    import jax

    from loops.gluon_train import SPAN_NAMES as spans

    from xplane import Trace, newest_xplane

    if os.path.isdir(path):
        path = newest_xplane(path)
    print("file", path, os.path.getsize(path))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, len(lines))
        for line in lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            seen = set()
            for ev in evs:
                if ev.name in seen or len(seen) > 12:
                    continue
                seen.add(ev.name)
                print("     ", ev.name[:90], ev.start_ns, ev.duration_ns,
                      [(k, str(v)[:60]) for k, v in list(ev.stats)[:6]])
            for ev in evs:
                if "mxtpu" in ev.name or any("mxtpu" in str(v) for _, v in ev.stats):
                    print("   KERNEL", ev.name[:90], ev.duration_ns,
                          [(k, str(v)[:100]) for k, v in ev.stats])
                    break
    tr = Trace.from_file(path, spans)
    w0, w1 = tr.window()
    print("window_s", w1 - w0, "busy_s", tr.busy_s())
    print("top ops", tr.top_ops(10))
    print("idle gaps", tr.idle_gaps(10))
    print("spans", len(tr.host_spans), tr.host_spans[:8])
    for sub in ("mxtpu_flash", "mxtpu_layer_norm", "mxtpu_softmax_xent", "fusion"):
        print("kernel_s", sub, tr.kernel_s(sub))


if __name__ == "__main__":
    main(sys.argv[1])
