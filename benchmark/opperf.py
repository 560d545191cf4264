"""Per-operator throughput harness — the reference `benchmark/opperf/`
(v>=1.5 "opperf" utility) re-designed TPU-first.

Reference surface (benchmark/opperf/opperf.py, utils/benchmark_utils.py
`run_performance_test`): benchmark individual operators with default or
user-given input shapes, forward and backward, and emit per-op timing
tables. Differences by design:

- timing excludes compilation (first call traces+compiles under XLA;
  the harness warms up before measuring) and synchronizes with
  `wait_to_read` — the PJRT analog of the reference's engine
  `WaitForAll` around each measured run;
- per-op achieved GB/s and GFLOP/s are derived from input/output byte
  counts so memory-bound elementwise ops report bandwidth (the number
  that matters on HBM) rather than a bare latency.

Caveat: each eager op is its own dispatch, so small-op latencies
measure the host's dispatch path as much as the chip.

Usage:
    python benchmark/opperf.py                   # default suite
    python benchmark/opperf.py --ops add,dot     # a subset
    python benchmark/opperf.py --backward        # include backward
    python benchmark/opperf.py --json out.json   # machine-readable dump
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd


def _t(shape, dtype="float32", low=-1.0, high=1.0):
    rng = np.random.default_rng(7)
    return nd.array(rng.uniform(low, high, shape).astype(dtype))


def _ti(shape, high):
    rng = np.random.default_rng(7)
    return nd.array(rng.integers(0, high, shape).astype("int64"))


# Default suite: one representative config per op family (reference
# opperf's nd_operations categories). Each entry: name -> (op callable
# kwargs-builder). Builders return (args, kwargs).
def _default_suite(large: bool) -> dict:
    n = 1024 if large else 256
    b = 128 if large else 16
    img = (b, 64, 56, 56) if large else (8, 8, 14, 14)
    return {
        # elementwise / broadcast (HBM-bound)
        "elemwise_add": lambda: ((_t((n, n)), _t((n, n))), {}),
        "elemwise_mul": lambda: ((_t((n, n)), _t((n, n))), {}),
        "exp": lambda: ((_t((n, n)),), {}),
        "tanh": lambda: ((_t((n, n)),), {}),
        "broadcast_add": lambda: ((_t((n, n)), _t((1, n))), {}),
        # reductions
        "sum": lambda: ((_t((n, n)),), {}),
        "mean": lambda: ((_t((n, n)),), {"axis": 1}),
        "softmax": lambda: ((_t((b, n)),), {}),
        # MXU (compute-bound)
        "dot": lambda: ((_t((n, n)), _t((n, n))), {}),
        "batch_dot": lambda: ((_t((b, n, n // 4)), _t((b, n // 4, n))), {}),
        "FullyConnected": lambda: ((_t((b, n)), _t((n, n)), _t((n,))),
                                   {"num_hidden": n}),
        "Convolution": lambda: ((_t(img), _t((64, img[1], 3, 3)), _t((64,))),
                                {"kernel": (3, 3), "num_filter": 64,
                                 "pad": (1, 1)}),
        # nn
        "Activation": lambda: ((_t((n, n)),), {"act_type": "relu"}),
        "BatchNorm": lambda: ((_t(img), _t((img[1],)), _t((img[1],)),
                               _t((img[1],)), _t((img[1],), low=0.5, high=1.5)),
                              {}),
        "LayerNorm": lambda: ((_t((b, n)), _t((n,)), _t((n,))), {}),
        "Pooling": lambda: ((_t(img),), {"kernel": (2, 2), "stride": (2, 2),
                                         "pool_type": "max"}),
        "Dropout": lambda: ((_t((n, n)),), {"p": 0.5}),
        "Embedding": lambda: ((_ti((b, 64), n), _t((n, 128))),
                              {"input_dim": n, "output_dim": 128}),
        # indexing / ordering
        "take": lambda: ((_t((n, n)), _ti((b,), n)), {}),
        "topk": lambda: ((_t((b, n)),), {"k": 8}),
        "transpose": lambda: ((_t((n, n)),), {}),
        # optimizer update
        "sgd_mom_update": lambda: ((_t((n, n)), _t((n, n)), _t((n, n))),
                                   {"lr": 0.1, "momentum": 0.9}),
        "adam_update": lambda: ((_t((n, n)), _t((n, n)), _t((n, n)),
                                 _t((n, n), low=0.0, high=0.1)),
                                {"lr": 1e-3}),
        # detection / contrib-vision family
        "_contrib_box_nms": lambda: ((_t((b, n // 4, 6), low=0.0, high=1.0),),
                                     {"overlap_thresh": 0.5}),
        "_contrib_ROIAlign": lambda: (
            (_t(img), nd.concat(
                _ti((b, 1), img[0]).astype("float32"),
                _t((b, 4), low=0.0, high=float(img[3] - 1)), dim=1)),
            {"pooled_size": (7, 7)}),
        "_contrib_DeformableConvolution": lambda: (
            (_t(img), _t((img[0], 18, img[2], img[3])),
             _t((64, img[1], 3, 3)), _t((64,))),
            {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)}),
        # numpy-frontend contraction
        "_npi_einsum": lambda: ((_t((b, n // 4, 64)), _t((b, n // 4, 64))),
                                {"subscripts": "bik,bjk->bij"}),
    }


def _nbytes(arrs) -> int:
    total = 0
    for a in arrs:
        if isinstance(a, mx.nd.NDArray):
            total += int(np.prod(a.shape)) * np.dtype(
                str(a.dtype).replace("bfloat16", "float32")).itemsize // (
                    2 if "bfloat16" in str(a.dtype) else 1)
    return total


def run_performance_test(op_names, ctx=None, warmup=3, runs=25,
                         run_backward=False, large=True, suite=None):
    """Benchmark named ops; returns a list of result dicts (reference
    benchmark_utils.run_performance_test). ``ctx`` scopes tensor
    creation and execution (default: the current/default context)."""
    import contextlib
    suite = suite or _default_suite(large)
    results = []
    # at least one untimed run is mandatory: it triggers XLA compile and
    # materializes the outputs whose bytes feed gb_per_sec
    warmup = max(1, warmup)
    scope = ctx if ctx is not None else contextlib.nullcontext()
    with scope:
        for name in op_names:
            if name not in suite:
                raise KeyError(f"no default config for op {name!r}; "
                               f"known: {sorted(suite)}")
            args, kwargs = suite[name]()
            if hasattr(mx.nd, name):
                fn = getattr(mx.nd, name)
            else:
                # ops registered after the nd-namespace codegen pass
                # (_npi_* numpy internals) resolve through the registry
                from mxnet_tpu.ndarray.register import get_op, invoke

                def fn(*a, _op=get_op(name), **kw):
                    return invoke(_op, list(a), kw)
            fargs = [a for a in args
                     if isinstance(a, mx.nd.NDArray)
                     and "float" in str(a.dtype)]

            def call():
                out = fn(*args, **kwargs)
                (out[0] if isinstance(out, (list, tuple)) else out).wait_to_read()
                return out

            def call_bwd():
                for a in fargs:
                    a.attach_grad()
                with autograd.record():
                    out = fn(*args, **kwargs)
                    head = out[0] if isinstance(out, (list, tuple)) else out
                    s = head.sum()
                s.backward()
                # synchronize on the GRADIENTS, not the (already
                # materialized) loss — backward dispatch is async
                for a in fargs:
                    if a.grad is not None:
                        a.grad.wait_to_read()
                return out

            target = call_bwd if run_backward else call
            try:
                out = None
                for _ in range(warmup):
                    out = target()
            except Exception as e:  # pragma: no cover - config drift guard
                results.append({"op": name, "error": str(e)})
                continue
            outs = out if isinstance(out, (list, tuple)) else [out]
            times = []
            for _ in range(runs):
                t0 = time.perf_counter()
                target()
                times.append(time.perf_counter() - t0)
            avg = float(np.mean(times))
            res = {
                "op": name,
                "mode": "fwd+bwd" if run_backward else "fwd",
                "avg_us": round(avg * 1e6, 2),
                "p50_us": round(float(np.percentile(times, 50)) * 1e6, 2),
                "min_us": round(float(np.min(times)) * 1e6, 2),
                # HBM traffic estimate: inputs read + outputs written
                "gb_per_sec": round(
                    (_nbytes(args) + _nbytes(outs)) / avg / 1e9, 3),
            }
            results.append(res)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", help="comma-separated op names (default: all)")
    ap.add_argument("--backward", action="store_true",
                    help="measure forward+backward")
    ap.add_argument("--runs", type=int, default=25)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--small", action="store_true",
                    help="small shapes (CI / CPU)")
    ap.add_argument("--json", help="write results to this path")
    args = ap.parse_args(argv)

    suite = _default_suite(not args.small)
    names = args.ops.split(",") if args.ops else sorted(suite)
    results = run_performance_test(
        names, warmup=args.warmup, runs=args.runs,
        run_backward=args.backward, large=not args.small, suite=suite)
    for r in results:
        print(json.dumps(r))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
    return results


if __name__ == "__main__":
    main()
