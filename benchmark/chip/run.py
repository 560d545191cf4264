#!/usr/bin/env python3
"""One process, one cell, once.

    python benchmark/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Configures the compile cache, builds the cell's model on the device from the
seed, warms up (set-up), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints the contract's last
line. No TPU, or fewer chips than the cell asks for: exit 3, no result.
``--rehearse`` runs the configuration's ``rehearsal`` widths on the CPU for a
builder's dry run; it prints no metric.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()   # process start, to within the interpreter's own

import argparse
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench, name, rehearse=False):
    """(cell, configuration as it is run, traffic mix, the mix's shapes)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    if rehearse:
        cfg = {**cfg, **cfg["rehearsal"]}
    traffic = load_json("traffic", cell["traffic"] + ".json")
    shape = {**cfg.get("traffic_shapes", {}).get(cell["traffic"], {}),
             **traffic.get("shape", {})}
    return cell, cfg, traffic, shape


def metrics_of(bench, cell_name, kind):
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def place_compile_cache():
    """Honour JAX_COMPILATION_CACHE_DIR; else one fixed directory inside the
    checkout, which the program is told to take. The persistence floor is
    set to 0 s so that the optimizer's many sub-second programs are kept."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["MXNET_TPU_COMPILE_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["MXNET_TPU_COMPILE_CACHE_MIN_S"] = "0"


def device_record(devices, memory_peak):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}


def run_cell(args, rehearse=False, bench=None):
    """Everything after the look for a chip; returns the result object.
    ``bench`` stands in for BENCHMARK.json (a test's cell that is not
    registered yet)."""
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    bench = bench or load_benchmark()
    cell, cfg, traffic, shape = find_cell(bench, args.workload, rehearse)
    traced = bool(args.trace)

    import jax

    import counters
    from peaks import guard_impossible, peaks_for
    from spans import Spans

    counters.install()
    import mxnet_tpu.compile_cache as program_cache
    program_cache.configure()

    loop = importlib.import_module(f"loops.{traffic['loop']}")
    spans = Spans(traced)
    run = loop.Run(cfg, traffic, shape, cell["chips"], args.seed, spans,
                   rehearse=rehearse)
    run.setup()
    setup_counts = counters.snapshot()
    spans.reset()
    seconds = float(args.seconds)
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    if traced:
        seconds = min(seconds, float(traffic["trace_seconds"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - _T0
    try:
        measured = run.measure(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    window_counts = counters.since(setup_counts)
    memory_peak = counters.memory_peak_bytes(run.devices)
    device = device_record(run.devices, memory_peak)

    end_to_end = dict(measured["end_to_end"], setup_s=setup_s)
    values = end_to_end
    breakdown = None
    if traced:
        from xplane import Trace, newest_xplane

        tr = Trace.from_file(newest_xplane(trace_dir), loop.SPAN_NAMES,
                             [d.id for d in run.devices])
        w0, w1 = tr.window()
        device["busy_s"], device["window_s"] = tr.busy_s(), w1 - w0
        breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
        ctx = dict(run.reader_context(), trace=tr, spans=spans, cfg=cfg,
                   chips=cell["chips"], measured=measured,
                   peaks=None if rehearse else peaks_for(device["kind"]),
                   counters={"setup": setup_counts, "window": window_counts},
                   memory_peak_bytes=memory_peak)
        values = {}
        for m in metrics_of(bench, cell["name"], "per_layer"):
            spec = load_json("metrics", m["name"] + ".json")
            reader = importlib.import_module(f"readers.{spec['reader']}")
            v = reader.read(ctx, **spec.get("args", {}))
            if v is not None:
                values[m["name"]] = v
    if not rehearse and "flops" in measured:
        guard_impossible(measured["seconds"], measured["flops"],
                         peaks_for(device["kind"]), cell["chips"])

    run.release()
    correct, checks = run.verify()

    kind = "per_layer" if traced else "end_to_end"
    units = {m["name"]: m["unit"] for m in bench[kind]}
    result = {"correct": bool(correct), "attempted": measured["attempted"],
              "failed": measured["failed"],
              "metrics": {} if rehearse else {
                  k: {"value": v, "unit": units[k]}
                  for k, v in values.items() if k in units},
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["workload"] = cell["name"]
    result["seed"] = args.seed
    result["checks"] = checks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    place_compile_cache()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    else:
        cell = find_cell(load_benchmark(), args.workload)[0]
        import jax

        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
            print(f"needs {cell['chips']} TPU chip(s); jax found "
                  f"{len(devs)} x {devs[0].platform}: no measurement "
                  "without the chip", file=sys.stderr)
            return 3
    result = run_cell(args, rehearse=args.rehearse)
    sys.stdout.flush()
    for name, row in result["checks"].items():
        print(f"check {name}: value {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
