"""A mechanism's share of its roofline where its device time is more than
its named kernels: the least time for the work (``kernel_roofline.bound``)
over the device seconds spent under a jax name scope.

The program runs the mechanism under ``jax.named_scope(scope)``; XLA keeps
the name stack as each instruction's ``op_name`` and the TPU profiler writes
it into the trace as the ``tf_op`` of the event's metadata, which
``jax.profiler.ProfileData`` does not show. So this reader parses the trace
file itself, with the few fields of the xplane schema it needs declared
here (protobuf skips the rest). The time is the union of the intervals of
the events on the operations line whose ``tf_op`` has ``scope``. On a v5e
the ``%while`` and ``%conditional`` events, which nest their bodies, carry
no ``tf_op``, so a loop counts through its body's events (those without one
were 1.1% of a Kimi step's device time, PERF.md section 5); the union keeps
an event that nests others from counting twice should it carry the name. A
program without the scope, or a trace without ``tf_op``: nothing."""
from __future__ import annotations

import functools
import glob
import os

from readers.kernel_roofline import bound
from xplane import DEVICE_PREFIX, OPS_LINE, union

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# message: [(field, number, type, repeated)]; a map is its entries repeated
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "bytes", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventEntry", True),
               ("stat_metadata", 5, "StatEntry", True)],
    "XLine": [("name", 2, "bytes", False), ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False), ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "EventEntry": [("key", 1, "int64", False), ("value", 2, "XEventMetadata", False)],
    "XEventMetadata": [("name", 2, "bytes", False), ("stats", 5, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False), ("str_value", 5, "bytes", False),
              ("ref_value", 7, "uint64", False)],
    "StatEntry": [("key", 1, "int64", False), ("value", 2, "XStatMetadata", False)],
    "XStatMetadata": [("name", 2, "bytes", False)],
}


@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    scalar = {"bytes": descriptor_pb2.FieldDescriptorProto.TYPE_BYTES,
              "int64": descriptor_pb2.FieldDescriptorProto.TYPE_INT64,
              "uint64": descriptor_pb2.FieldDescriptorProto.TYPE_UINT64}
    package = "mxtpu_bench_xplane"
    file = descriptor_pb2.FileDescriptorProto(
        name=package + ".proto", package=package, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = file.message_type.add(name=name)
        for field, number, kind, repeated in fields:
            f = msg.field.add(
                name=field, number=number,
                label=(descriptor_pb2.FieldDescriptorProto.LABEL_REPEATED if repeated
                       else descriptor_pb2.FieldDescriptorProto.LABEL_OPTIONAL))
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type = descriptor_pb2.FieldDescriptorProto.TYPE_MESSAGE
                f.type_name = f".{package}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(package + ".XSpace"))


def scope_seconds(path, scope, device_ids=None):
    """Device seconds under ``scope`` in the trace at ``path``, averaged over
    the devices (``device_ids``: those the cell used); None where no event
    carries the scope."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    want = scope.encode()
    per, found = [], False
    for plane in space.planes:
        name = plane.name.decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        if device_ids is not None and int(name[len(DEVICE_PREFIX):].split()[0]) not in device_ids:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = {k for k, v in stat_names.items() if v == b"tf_op"}
        inside = set()
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if stat.metadata_id in tf_op and want in (
                        stat.str_value or stat_names.get(stat.ref_value, b"")):
                    inside.add(entry.key)
        spans = [(ev.offset_ps, ev.offset_ps + ev.duration_ps)
                 for line in plane.lines if line.name.decode() == OPS_LINE
                 for ev in line.events if ev.metadata_id in inside]
        found = found or bool(spans)
        per.append(sum(e - s for s, e in union(spans)) * 1e-12)
    return sum(per) / len(per) if found else None


def newest_trace():
    paths = glob.glob(os.path.join(ROOT, ".bench_trace", "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def read(ctx, scope, work):
    if ctx["peaks"] is None or ctx["trace"] is None:
        return None
    fn = getattr(ctx["model_mod"], work, None)
    steps = ctx["measured"].get("steps")
    path = newest_trace()
    if fn is None or not steps or path is None:
        return None
    seconds = scope_seconds(path, scope, set(ctx["trace"].device_events))
    if not seconds:
        return None
    per_chip = fn(ctx["cfg"], ctx["shape"])
    least, _ = bound({k: v / ctx["chips"] for k, v in per_chip.items()}, ctx["peaks"])
    return 100.0 * least * steps / seconds
