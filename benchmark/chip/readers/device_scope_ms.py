"""Device milliseconds a step under the program's name scopes, and by the
step's phase.

The program names its device work (``mxnet_tpu/gluon/block.py``
``_block_scope``): inside a compiled program every Gluon block's call runs
under ``jax.named_scope("mx.<BlockClass>")``, the fused optimizer update
under ``mxtpu_update``, the kernels' mechanisms under their ``mxtpu_*``
scopes. XLA keeps the name stack as each instruction's ``op_name`` and the
TPU profiler writes it as the event's ``tf_op``
(``jit(mxtpu_fwd_MLM)/mx.MLM/mx.BERTModel/mx.Dense/dot_general:``). A scope
matches as a WHOLE component of that path, after unwrapping jax's
``jvp(...)`` / ``transpose(...)``: ``mx.Dense`` is not ``mx.DenseX``, and
the program's own name, ``jit(mxtpu_fwd_MLM)``, is no scope. A fusion
carries its root instruction's ``op_name``, so a number is exact to the
fusion, not to the instruction.

The phase of an event, the first that applies:

- ``update``: a component ``mxtpu_update``;
- ``rebuild``: a component ``rematted_computation`` (jax's own, around what
  ``jax.checkpoint`` runs again in the backward);
- ``backward``: a component wrapped in ``transpose(`` (a step compiled as
  one program), or the event lies in a run of a BACKWARD program. On the
  Gluon path (``jax.vjp`` over a jitted function, called eagerly) jax puts
  ``transpose(`` on the call's equation and not on the equations inside, so
  the compiled backward carries no marker and its module has the forward's
  name, ``jit_mxtpu_fwd_<class>``, under another program id. What tells the
  two apart is the tape's order: between two runs of the update's program,
  every forward run comes before every backward run and there are as many
  of each, so the program ids of the first half are the forward's and those
  of the second the backward's. Where a step's runs do not split so (an
  odd count, or an id on both sides), no phase is read;
- ``forward``: what is left of the events that carry a scope.

The time is the union of the events' intervals on the ``XLA Ops`` line
(a loop's event and its body count once), averaged over the cell's
devices, over the window's steps. A trace without the scopes (a program
from before them, a warm cache's older executable): nothing.
"""
from __future__ import annotations

import bisect
import collections
import functools
import os
import re

from program_spans import MODULES_LINE
from readers.scope_roofline import _xspace_class, newest_trace
from xplane import DEVICE_PREFIX, OPS_LINE, union

BLOCK_PREFIX = "mx."
SCOPE_PREFIX = "mxtpu_"
UPDATE_SCOPE = "mxtpu_update"
REBUILD_MARK = "rematted_computation"
FWD_PROGRAM = "jit_mxtpu_fwd_"
UPDATE_PROGRAM = "jit_mxtpu_update"
PHASES = ("forward", "rebuild", "backward", "update")

_WRAPPED = re.compile(r"^(jvp|transpose)\((.*)\)$")

# one event of the operations line: picoseconds, the components of its name
# stack in order (``path``) and as a set (``parts``), whether one of them is
# a scope, its phase or None, the module run it lies in (the name, program id
# included) or None, and the left side of its HLO text (``%fusion.12``)
Event = collections.namedtuple(
    "Event", "start end parts scoped phase path run hlo")


def components(tf_op):
    """(the name stack's components, unwrapped; whether one was wrapped in
    ``transpose(``) of an event's ``tf_op``. The last component is the jax
    primitive; the program's own ``jit(...)`` stays as it is."""
    out, transposed = [], False
    for part in tf_op.rsplit(":", 1)[0].split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            transposed = transposed or m.group(1) == "transpose"
            part = m.group(2)
        if part:
            out.append(part)
    return out, transposed


def is_scope(component):
    return component.startswith((BLOCK_PREFIX, SCOPE_PREFIX))


def _backward_programs(runs):
    """The names (program id included) of the backward programs among one
    device's module runs ``[(start, end, name)]``, in time order; None where
    the steps do not split into a forward and a backward half."""
    updates = [i for i, r in enumerate(runs) if r[2].startswith(UPDATE_PROGRAM)]
    forward, backward = set(), set()
    for a, b in zip(updates, updates[1:]):
        step = [r[2] for r in runs[a + 1:b] if r[2].startswith(FWD_PROGRAM)]
        if len(step) % 2:
            return None
        forward.update(step[:len(step) // 2])
        backward.update(step[len(step) // 2:])
    if not backward or forward & backward:
        return None
    return backward


class Device:
    """One device plane: ``events`` (`Event`s of the operations line),
    ``runs`` [(start_ps, end_ps, module name)] in time order, ``backward``
    (`_backward_programs` of the runs), ``names_blocks`` (whether an event
    carries an ``mx.`` component), ``busy_ps``."""

    def __init__(self, plane):
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, v in stat_names.items() if v == b"tf_op"}
        names, named = {}, {}
        for entry in plane.event_metadata:
            names[entry.key] = entry.value.name.decode().split(" = ", 1)[0]
            for stat in entry.value.stats:
                if stat.metadata_id in tf_op:
                    path, transposed = components(
                        (stat.str_value
                         or stat_names.get(stat.ref_value, b"")).decode())
                    named[entry.key] = (path, frozenset(path), transposed,
                                        any(is_scope(p) for p in path))
        self.runs = sorted(
            (ev.offset_ps, ev.offset_ps + ev.duration_ps, names[ev.metadata_id])
            for line in plane.lines if line.name.decode() == MODULES_LINE
            for ev in line.events)
        self.backward = _backward_programs(self.runs)
        starts = [r[0] for r in self.runs]
        self.events = []
        for line in plane.lines:
            if line.name.decode() != OPS_LINE:
                continue
            for ev in line.events:
                start = ev.offset_ps
                path, parts, transposed, scoped = named.get(
                    ev.metadata_id, ((), frozenset(), False, False))
                i = bisect.bisect_right(starts, start) - 1
                run = (self.runs[i][2]
                       if i >= 0 and start < self.runs[i][1] else None)
                phase = self._phase(parts, transposed, run) if scoped else None
                self.events.append(Event(
                    start, start + ev.duration_ps, parts, scoped, phase, path,
                    run, names[ev.metadata_id]))
        self.names_blocks = any(p.startswith(BLOCK_PREFIX)
                                for _, parts, _, _ in named.values()
                                for p in parts)
        self.busy_ps = self.union_ps(lambda ev: True)

    def _phase(self, parts, transposed, run):
        if UPDATE_SCOPE in parts:
            return "update"
        if REBUILD_MARK in parts:
            return "rebuild"
        if transposed:
            return "backward"
        if run is not None and run.startswith(FWD_PROGRAM):
            if self.backward is None:
                return None
            return "backward" if run in self.backward else "forward"
        return "forward"

    def union_ps(self, keep):
        return sum(e - s for s, e in union(
            (ev.start, ev.end) for ev in self.events if keep(ev)))


@functools.lru_cache(maxsize=2)
def _parsed(path, mtime):
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        name = plane.name.decode()
        if name.startswith(DEVICE_PREFIX):
            out[int(name[len(DEVICE_PREFIX):].split()[0])] = Device(plane)
    return out


def devices_of(path, device_ids=None):
    """{device index: `Device`} of the trace at ``path`` (``device_ids``:
    those the cell used)."""
    parsed = _parsed(path, os.path.getmtime(path))
    return {i: d for i, d in parsed.items()
            if device_ids is None or i in device_ids}


def names_blocks(devices):
    """Whether the traced program is one that names its blocks."""
    return any(d.names_blocks for d in devices.values())


def scope_ms(path, scopes=None, phase=None, within=None, device_ids=None):
    """Device milliseconds (the whole trace, mean over the devices) of the
    events that carry one of ``scopes`` (None: any scope) and, where
    ``phase`` is given, are of that phase. Where no such event ran: 0.0 if
    the program could have run one and did not (it names its blocks; with
    ``within``, events under one of those scopes ran: a branch the program
    holds and did not take), else None."""
    devices = devices_of(path, device_ids)
    named = names_blocks(devices)
    if not devices or (phase is not None and not named):
        return None
    want = None if scopes is None else frozenset(scopes)

    def keep(ev):
        if phase is not None and ev.phase != phase:
            return False
        return ev.scoped if want is None else bool(want & ev.parts)

    per = [d.union_ps(keep) for d in devices.values()]
    if any(per):
        return sum(per) / len(per) * 1e-9
    if phase is not None and all(d.backward is None for d in devices.values()):
        return None     # the steps did not split: no phase is read
    if within is None:
        return 0.0 if named else None
    inside = frozenset(within)
    ran = any(inside & ev.parts for d in devices.values() for ev in d.events)
    return 0.0 if ran else None


def read(ctx, scopes=None, phase=None, within=None):
    if ctx["trace"] is None:
        return None
    steps = ctx["measured"].get("steps")
    path = newest_trace()
    if not steps or path is None:
        return None
    ms = scope_ms(path, scopes, phase, within, set(ctx["trace"].device_events))
    return None if ms is None else ms / steps
