#!/usr/bin/env python3
"""Where a traced step's device time goes, by the names the program gives
its device work (``readers/device_scope_ms.py`` has the rules):

    python benchmark/chip/scope_report.py [<dir-or-xplane.pb>] [--depth N]

(default: the newest trace under ``.bench_trace/``). Milliseconds a step
(a step: a run of the update's program, ``jit_mxtpu_update``) on the first
device of the trace:

- by phase (forward, remat's rebuild, backward, update) with the unscoped
  rest and the busy time;
- by block: the innermost ``--depth`` (2) ``mx.<BlockClass>`` components of
  each event's name stack, by phase. Every event is in one row, so the rows
  add up to the time under block scopes;
- by ``mxtpu_*`` scope and kernel name (an event under several counts in
  each);
- the unscoped rest by jax primitive (the last component of ``op_name``), or
  by HLO opcode where the event has no ``op_name``; a ``%while`` or
  ``%conditional`` row is the whole of what it nests, scoped events included,
  and is left out of nothing else;
- by program: the ``XLA Modules`` line's runs by module name and program,
  with its runs a step, the backward program of a ``jit_mxtpu_fwd_*`` pair
  marked.

A fusion carries its root instruction's ``op_name``: a row is exact to the
fusion, not to the instruction. Rows are sums of event durations; a loop's
own event carries no name on a v5e and counts through its body.
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from readers.device_scope_ms import (BLOCK_PREFIX, FWD_PROGRAM, PHASES,  # noqa: E402
                                     SCOPE_PREFIX, UPDATE_PROGRAM, devices_of)
from readers.scope_roofline import newest_trace  # noqa: E402
from xplane import newest_xplane  # noqa: E402


_OPCODE = re.compile(r"%?[A-Za-z_-]*")     # ``%broadcast.856.clone``: ``%broadcast``


def block_path(path, depth):
    """The innermost ``depth`` block components of a name stack. jax repeats
    the outer blocks' names inside a remat'd block's backward
    (``mx.A/mx.B/mx.A/mx.B/checkpoint/...``): the walk starts again where
    the root's name comes again."""
    blocks = []
    for part in path:
        if part.startswith(BLOCK_PREFIX):
            if blocks and part == blocks[0]:
                blocks = []
            blocks.append(part)
    return "/".join(blocks[-depth:])


def tables(device, depth, steps):
    """{table: {row: {column: picoseconds}}} of one `Device`."""
    out = {k: collections.defaultdict(lambda: collections.defaultdict(int))
           for k in ("phase", "block", "scope", "unscoped", "program")}
    for ev in device.events:
        ps = ev.end - ev.start
        if not ev.scoped:
            row = ev.path[-1] if ev.path else _OPCODE.match(ev.hlo).group()
            out["unscoped"][row]["all"] += ps
            continue
        phase = ev.phase or "no phase"
        out["phase"][phase]["all"] += ps
        block = block_path(ev.path, depth)
        if block:
            out["block"][block][phase] += ps
        for part in ev.parts:
            if part.startswith(SCOPE_PREFIX):
                out["scope"][part][phase] += ps
    runs = collections.Counter(name for _, _, name in device.runs)
    for start, end, name in device.runs:
        row = f"{name.split('(', 1)[0]} x{runs[name] / steps:g}"
        if device.backward and name in device.backward:
            row += " [backward]"
        out["program"][row]["all"] += end - start
    return out


def report(path, depth=2, out=sys.stdout):
    devices = devices_of(path)
    if not devices:
        print(f"no device plane in {path}", file=out)
        return 1
    device = devices[min(devices)]
    steps = sum(r[2].startswith(UPDATE_PROGRAM) for r in device.runs) or 1
    ms = lambda ps: ps * 1e-9 / steps    # noqa: E731
    named = device.union_ps(lambda ev: ev.scoped)
    print(f"{path}\ndevice {min(devices)}: {steps} steps, busy "
          f"{ms(device.busy_ps):.2f} ms a step, under a scope {ms(named):.2f}, "
          f"unscoped {100.0 * (device.busy_ps - named) / device.busy_ps:.2f}%; "
          f"backward programs: "
          f"{'not told apart' if device.backward is None else len(device.backward)}"
          f" of {len({r[2] for r in device.runs if r[2].startswith(FWD_PROGRAM)})}"
          " jit_mxtpu_fwd_* ids", file=out)
    columns = PHASES + ("no phase",)
    for title, table in zip(
            ("by phase", f"by block (innermost {depth})",
             "by mxtpu_* scope and kernel", "unscoped, by primitive or HLO opcode",
             "by program (x runs a step)"),
            tables(device, depth, steps).values()):
        cols = [c for c in columns + ("all",)
                if any(r.get(c) for r in table.values())]
        heads = cols + ["sum"] if len(cols) > 1 else cols
        print(f"\n== {title}: ms a step\n{'':60s}"
              + "".join(f"{c:>12s}" for c in heads), file=out)
        for row in sorted(table, key=lambda r: -sum(table[r].values())):
            cells = [table[row].get(c, 0) for c in cols]
            cells += [sum(cells)] if len(cols) > 1 else []
            print(f"{row[-60:]:60s}"
                  + "".join(f"{ms(v):12.3f}" for v in cells), file=out)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", help="a trace directory or .xplane.pb")
    ap.add_argument("--depth", type=int, default=2)
    args = ap.parse_args(argv)
    path = args.trace or newest_trace()
    if path is None:
        print("no trace under .bench_trace/: run run.py --trace 1 first",
              file=sys.stderr)
        return 1
    if os.path.isdir(path):
        path = newest_xplane(path)
    return report(path, args.depth)


if __name__ == "__main__":
    sys.exit(main())
