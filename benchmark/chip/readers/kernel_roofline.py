"""A kernel's share of its roofline: the least time the chip could take for
the work (the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
from a function of shapes kept with the configuration) over the summed
device time of the kernel's events in the trace. Nothing to read: nothing."""


def bound(work, peaks):
    t_flops = work["flops"] / peaks["flops"]
    t_bytes = work["bytes"] / peaks["bytes"]
    return max(t_flops, t_bytes), "compute" if t_flops >= t_bytes else "memory"


def read(ctx, event_substring, work):
    if ctx["peaks"] is None or ctx["trace"] is None:
        return None
    kernel_s = ctx["trace"].kernel_s(event_substring)
    fn = getattr(ctx["model_mod"], work, None)
    steps = ctx["measured"].get("steps")
    if not kernel_s or fn is None or not steps:
        return None
    per_chip = fn(ctx["cfg"], ctx["shape"])
    least, _ = bound({k: v / ctx["chips"] for k, v in per_chip.items()}, ctx["peaks"])
    return 100.0 * least * steps / kernel_s
