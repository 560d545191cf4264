"""A sum of the process's program counters over a phase (set-up or window)."""


def read(ctx, phase, keys):
    return float(sum(ctx["counters"][phase][k] for k in keys))
