"""How many of one of the program's spans the traced window holds (0 where
the program writes spans and none of this name; nothing where the trace has
no span of the program's at all)."""
import program_spans


def read(ctx, span):
    pt = program_spans.of(ctx)
    n = None if pt is None else pt.count(span)
    return None if n is None else float(n)
