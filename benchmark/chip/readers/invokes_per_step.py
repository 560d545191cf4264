"""``register.invoke`` calls per step: the sum of the ``invokes`` attribute
(the program's always-on counter, read at the span's two ends) over each
step's outermost spans. The count of ``mxtpu/op/*`` events in the same steps
must say the same. Where it does not (spans lost, or another thread
dispatching ops, which the process-wide counter sees and the thread's spans
do not) neither number is the step's: the reader says so on stderr and gives
nothing, so the run ends and its other metrics stand. (``run.py`` builds the
result's ``checks`` from the comparison alone; a reader cannot add a row.)"""
import sys

import program_spans


def read(ctx):
    pt = program_spans.of(ctx)
    both = None if pt is None else pt.invokes_per_step()
    if both is None:
        return None
    counted, events = both
    if counted != events:
        print(f"invokes_per_step: the spans' counter says {counted}, the trace "
              f"holds {events} mxtpu/op/* events a step: not reported",
              file=sys.stderr)
        return None
    return float(counted)
