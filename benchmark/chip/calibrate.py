#!/usr/bin/env python3
"""The builder's readings for a training cell's limits, in ONE process on the
chip: for each seed the program's first steps against the reference (the
lower readings); for the first ``--controls`` seeds also the control (the
reference in float8), a second witness (the reference in bfloat16, which
should side with the program) and the planted fault (half of the batch left
out; a state returned unchanged reads 1 by the measure and needs no run)
against the reference (the upper readings).
Prints one JSON line per reading, every leaf's norms in it. Not part of a
benchmark run.

    python benchmark/chip/calibrate.py --workload <cell> --seeds 1,2,3 --controls 3
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv=None):
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--override", default="{}",
                    help="JSON merged over the configuration, to try a setting")
    args = ap.parse_args(argv)
    harness.place_compile_cache()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU: no readings", file=sys.stderr)
        return 3
    import compare
    import mxnet_tpu.compile_cache as program_cache
    from spans import Spans

    program_cache.configure()
    cell, cfg, traffic, shape = harness.find_cell(
        harness.load_benchmark(), args.workload, args.rehearse)
    cfg = {**cfg, **json.loads(args.override)}
    loop = importlib.import_module(f"loops.{traffic['loop']}")
    seeds = [int(s) for s in args.seeds.split(",")]
    run = loop.Run(cfg, traffic, shape, cell["chips"], seeds[0], Spans(False),
                   rehearse=args.rehearse)
    run.build()
    half = slice(0, run.shape["batch"] // 2)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if i:
            run.reseed(seed)
        run.check_steps()
        ref = run.reference()
        sides = {"program": lambda: run.readings}
        if i < args.controls:
            sides["control_fp8"] = lambda: run.reference(precision="fp8")
            sides["witness_bf16"] = lambda: run.reference(precision="bf16")
            sides["fault_half_batch"] = lambda: run.reference(keep_rows=half)
        for side, read in sides.items():   # each line as soon as it is read
            readings = read()
            nums, where = compare.numbers(readings, ref)
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "side": side, "numbers": nums, "leaf": where,
                              "losses": readings["losses"],
                              "ref_losses": ref["losses"],
                              "readings": readings,
                              "reference": ref if side == "program" else None,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
