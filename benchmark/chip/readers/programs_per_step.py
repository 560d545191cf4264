"""Executed device programs per step: the events of the device planes'
``XLA Modules`` line (one per program run) over the window's steps, mean
over the cell's devices. Needs no span of the program's."""
import program_spans


def read(ctx):
    pt = program_spans.of(ctx)
    if pt is None:
        return None
    return pt.programs_per_step(ctx["measured"].get("steps"))
