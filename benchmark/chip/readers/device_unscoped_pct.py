"""The share of the device's busy time that no scope of the program names:
100 x (busy time - the union of the events that carry an ``mx.<BlockClass>``
component or an ``mxtpu_*`` scope) / busy time, mean over the cell's
devices. What is left is what runs outside every block and kernel scope:
the small eager programs between the step's three, loops' own events (a
``%while`` carries no ``tf_op`` on a v5e and counts through its body),
copies XLA adds without a name. A program from before the block scopes:
nothing (``readers/device_scope_ms.py``)."""
from readers.device_scope_ms import devices_of, names_blocks
from readers.scope_roofline import newest_trace


def unscoped_pct(path, device_ids=None):
    devices = devices_of(path, device_ids)
    if not names_blocks(devices):
        return None
    per = [100.0 * (d.busy_ps - d.union_ps(lambda ev: ev.scoped)) / d.busy_ps
           for d in devices.values() if d.busy_ps]
    return sum(per) / len(per) if per else None


def read(ctx):
    path = newest_trace()
    if ctx["trace"] is None or path is None:
        return None
    return unscoped_pct(path, set(ctx["trace"].device_events))
