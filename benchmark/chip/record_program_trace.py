#!/usr/bin/env python3
"""Record the small trace ``tests/chip_benchmark/test_program_spans.py``
keeps: two steps of the rehearsal-width BERT through the benchmark's own
loop (record -> backward -> Trainer.step under the benchmark's spans), on the
chip, after the loop's check steps have compiled everything. Run once on the
chip; the file goes to chiprun_out/program_trace/program.xplane.pb, and what
``program_spans.py`` makes of it is printed for the counts the test states.
``--cpu`` records the same on the CPU (no device plane): a builder's dry run."""
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

STEPS = 2


def main(out="chiprun_out/program_trace", cpu=False):
    import jax

    import program_spans
    import run as harness
    from loops import gluon_train
    from spans import Spans

    if not cpu:
        assert jax.devices()[0].platform == "tpu"
    cell, cfg, traffic, shape = harness.find_cell(
        harness.load_benchmark(), "bert_base.train_b64x512", rehearse=True)
    loop = gluon_train.Run(cfg, traffic, shape, 1, 7, Spans(True), rehearse=cpu)
    loop.setup()
    tmp = os.path.join(out, "_tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        for k in range(STEPS):
            outs = loop.step(loop.pool[k % len(loop.pool)])
        jax.block_until_ready([o._data for o in outs])
    finally:
        jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    dst = os.path.join(out, "program.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print("program trace", dst, os.path.getsize(dst))
    program_spans.report(program_spans.load(dst, chips=1), step=1, top=40)


if __name__ == "__main__":
    main(cpu="--cpu" in sys.argv[1:])
