"""The table of peaks (peaks.json), keyed by ``device_kind``; a device that
is not in the table is an error, not a default. ``guard_impossible`` is
copied from ``bench._guard_impossible``: a time below the chip's physical
bound means the timing is broken."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(kind, path=os.path.join(HERE, "peaks.json")):
    with open(path) as f:
        table = json.load(f)["peaks"]
    for row in table:
        if row["tag"] in kind.lower():
            return {"flops": row["bf16_tflops"] * 1e12, "bytes": row["hbm_gbps"] * 1e9}
    raise KeyError(f"no peak FLOP/s / HBM bandwidth known for device kind "
                   f"{kind!r}: add it to peaks.json with its source")


def guard_impossible(seconds, flops, peaks, chips=1, slack=1.0):
    """Refuse a window the chips cannot have produced."""
    bound = flops / (slack * peaks["flops"] * chips)
    if seconds < bound:
        raise RuntimeError(
            f"measured {flops:.3e} FLOPs in {seconds:.4f}s, below the "
            f"physical bound {bound:.4f}s of {chips} chip(s): the timing "
            "window is broken")
    return seconds
