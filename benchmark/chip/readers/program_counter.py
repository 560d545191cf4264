"""A ratio of two of the program's own always-on tallies
(``mx.profiler.counters()``), as the loop read them after the window
(``ctx["program_counters"]``): 100 x ``numerator`` / ``denominator``. The
tallies are running sums that the program adds to while it traces, so the
reading after the window holds every program the cell compiled. A loop that
reads no such counters, or a program without these two: nothing."""


def read(ctx, numerator, denominator):
    pc = ctx.get("program_counters")
    if not pc:
        return None
    now = pc["window"]
    if not now.get(denominator):
        return None
    return 100.0 * float(now.get(numerator, 0.0)) / float(now[denominator])
