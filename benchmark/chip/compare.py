"""The comparison that decides ``correct`` for a training cell.

Both sides give, for the first steps: the mean loss of each step, the
per-leaf norm of the first gradient as the optimizer got it, and the
per-leaf norm of the parameters' change after the last of them. A leaf's gap
is |program's norm - reference's norm| (a gap of norms, never a norm of a
difference) against the larger of that leaf's reference norm and the median
leaf's. Numbers compared:

- ``loss_gap``: the widest |program - reference| / |reference| over the steps;
- ``grad_gap``: the worst leaf's gap over the first gradient;
- ``change_gap``: the worst leaf's gap over the parameters' change, leaving
  out leaves whose reference gradient is under a thousandth of the median
  leaf's (they move by round-off alone);
- ``grad_gap_mean``, ``change_gap_mean``: the mean of the same leaves' gaps,
  which is steady from seed to seed where the worst leaf swings (a network
  that amplifies any rounding; PERF.md section 2).

Each number is held to the limit the configuration's ``limits`` gives it; one
without a limit there is printed and held only to being a number at all.
"""
from __future__ import annotations

import math
import statistics


def _leaf_gaps(prog, ref, leaves):
    """(worst gap, mean gap, worst leaf) over ``leaves``."""
    med = statistics.median(ref[k] for k in leaves)
    worst, name, total = 0.0, None, 0.0
    for k in leaves:
        p = prog.get(k)
        if p is None or not math.isfinite(p):
            return math.inf, math.inf, k
        gap = abs(p - ref[k]) / max(ref[k], med)
        total += gap
        if gap > worst:
            worst, name = gap, k
    return worst, total / len(leaves), name


def numbers(prog, ref):
    """{name: value} of every number compared, with the worst leaf's name."""
    out, where = {}, {}
    gaps = []
    for p, r in zip(prog["losses"], ref["losses"]):
        gaps.append(abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
    if len(prog["losses"]) != len(ref["losses"]):
        gaps.append(math.inf)
    out["loss_gap"] = max(gaps)
    leaves = sorted(ref["grad1"])
    med = statistics.median(ref["grad1"][k] for k in leaves)
    moved = [k for k in leaves if ref["grad1"][k] >= 1e-3 * med]
    for name, key, over in (("grad_gap", "grad1", leaves),
                            ("change_gap", "change", moved)):
        out[name], out[name + "_mean"], where[name] = _leaf_gaps(
            prog[key], ref[key], over)
    return out, where


def judge(nums, limits):
    """(correct, {name: {"value", "limit"}}); a number without a limit in
    ``limits`` is reported and held only to being a number at all."""
    ok, table = True, {}
    for k, v in nums.items():
        lim = limits.get(k)
        table[k] = {"value": v, "limit": lim}
        if not math.isfinite(v) or (lim is not None and v > lim):
            ok = False
    return ok, table
