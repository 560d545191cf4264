"""Two numbers from the expert layers' device tallies, as the loop read
them through ``mx.profiler.counters()`` after set-up and after the window
(``ctx["program_counters"]``): ``dropped`` — the slots routed to a held
expert that no branch computed (must read 0); ``load_max_over_mean`` — the
most loaded held expert's slots over the mean held expert's, the worst
layer. No such counters (a loop or a program without them): nothing."""


def read(ctx, what):
    pc = ctx.get("program_counters")
    if not pc or "moe_slots" not in pc["window"]:
        return None
    before, after = pc["setup"], pc["window"]
    if what == "dropped":
        return float(after["moe_dropped"] - before.get("moe_dropped", 0.0))
    worst = None
    for key, now in after.items():
        if not key.startswith("moe_slots/"):
            continue
        was = before.get(key, [0.0] * len(now))
        load = [a - b for a, b in zip(now, was)]
        if sum(load) > 0:
            ratio = max(load) * len(load) / sum(load)
            worst = ratio if worst is None else max(worst, ratio)
    return worst
