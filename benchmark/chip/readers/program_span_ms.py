"""Mean host milliseconds per step inside one of the PROGRAM's spans
(``mxnet_tpu/profiler.py``'s ``span``, read back from the trace by
``program_spans.py``). A trailing ``*`` in ``span`` matches a prefix; of
nested matches only the outermost counts. No such span, no step: nothing."""
import program_spans


def read(ctx, span):
    pt = program_spans.of(ctx)
    return None if pt is None else pt.ms_per_step(span)
