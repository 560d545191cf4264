"""Benchmark: ResNet-50 training throughput, images/sec/chip.

BASELINE config #2 (the north-star metric). Runs the full jitted
training step (forward + backward + SGD-momentum update, bf16 compute /
f32 master math where it matters) on synthetic ImageNet-shaped data on
ONE chip and prints a single JSON line.

``vs_baseline`` is computed against the historical upstream-MXNet
fp32 claim of ~375 img/s/GPU (BASELINE.md: the reference mount was
empty, "published": {} — 375 is the midpoint of the remembered
360–390 range, flagged there as unverified).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMGS_PER_SEC = 375.0
# batch 128 measured fastest on v5e (sweep r2: 64→1846, 128→2223,
# 256→2193 img/s; NHWC knob ±0 — XLA layout assignment already optimal)
BATCH = int(os.environ.get("BENCH_BATCH", "128"))
IMAGE = 224
STEPS = int(os.environ.get("BENCH_STEPS", "20"))
WARMUP = 3
DTYPE = os.environ.get("BENCH_DTYPE", "bfloat16")
# Steps fused per dispatch (engine.chain_steps — the engine-bulking /
# async-pipelining analog): CHAIN steps run on-device per dispatch, so
# the host's per-dispatch gap is paid once per CHAIN steps. Throughput
# figures count BATCH*STEPS*CHAIN examples. The default comes from a
# sweep in an earlier environment on older code (not reproduced);
# whether chaining still pays on a locally attached chip is to be
# measured, not assumed.
CHAIN = max(1, int(os.environ.get("BENCH_CHAIN", "10")))
# timing windows per measurement: median-of-3 for the headline configs,
# 1 for the long-tail extras where a ±3% swing doesn't change any
# conclusion but 3x windows cost real driver-budget minutes (r4 lesson:
# the suite outgrew the driver's timeout and the headline train number
# was lost)
WINDOWS = max(1, int(os.environ.get("BENCH_WINDOWS", "3")))


def _device():
    """The accelerator this leg measures, as JAX reports it. A leg
    refuses to run anywhere but on a TPU: a CPU timing must never reach
    a bench line under the name of a device metric."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU only; this process sees "
            f"{devs[0].platform}:{devs[0].device_kind} (x{len(devs)})")
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _setup_cache():
    """Every leg starts here: refuse to run off-TPU, then turn on the
    persistent compile cache through the framework's one function
    (mxnet_tpu.compile_cache decides the directory from the
    environment; bench chooses none of its own)."""
    dev = _device()
    from mxnet_tpu import compile_cache

    st = compile_cache.configure()
    print(json.dumps({"bench_setup": dev, "compile_cache_dir": st["dir"],
                      **compile_cache.events_snapshot()}))
    sys.stdout.flush()


def _precompile(step, *args, **meta):
    """BENCH_PRECOMPILE=1: lower + compile the step WITHOUT executing
    it, so the executable lands in the persistent cache and the
    separately-launched measured leg starts warm instead of spending
    its per-config wall cap (the r5 rc=124) on the compile."""
    from mxnet_tpu import compile_cache

    t0 = time.perf_counter()
    step.lower(*args).compile()
    dt = time.perf_counter() - t0
    _report("precompile_seconds", dt, "seconds", 0.0,
            cache_dir=compile_cache.state().get("dir"), **meta)


# Per-chip peaks keyed by a tag of ``device_kind`` (lower-cased):
# (dense bf16 TFLOP/s, HBM GB/s). Source: Google Cloud TPU
# documentation, the "System architecture" page of each generation
# (v5e: 197 TFLOP/s, 819 GB/s). First match wins, so longer tags come
# first. A kind that matches no tag is an error, never a default.
_PEAKS = (("v6e", 918.0, 1640.0), ("v6", 918.0, 1640.0),
          ("v5p", 459.0, 2765.0), ("v5e", 197.0, 819.0),
          ("v5 lite", 197.0, 819.0), ("v4", 275.0, 1228.0),
          ("v3", 123.0, 900.0), ("v2", 45.0, 700.0))


def _peaks_for(kind):
    for tag, tflops, gbps in _PEAKS:
        if tag in kind.lower():
            return tflops, gbps
    raise KeyError(
        f"no peak FLOP/s / HBM bandwidth known for device kind {kind!r}: "
        "add it to bench._PEAKS with its source")


def _peak_tflops(kind=None):
    """Per-chip peak dense bf16 TFLOP/s of ``kind`` (default: the local
    accelerator; override with MXNET_TPU_PEAK_TFLOPS)."""
    from mxnet_tpu import envvars

    env = envvars.get("MXNET_TPU_PEAK_TFLOPS")
    if env:
        return env
    return _peaks_for(kind or _device()["device_kind"])[0]


def _peak_hbm_gbps(kind=None):
    """Per-chip peak HBM bandwidth GB/s of ``kind`` (default: the local
    accelerator; override with MXNET_TPU_PEAK_HBM_GBPS)."""
    from mxnet_tpu import envvars

    env = envvars.get("MXNET_TPU_PEAK_HBM_GBPS")
    if env:
        return env
    return _peaks_for(kind or _device()["device_kind"])[1]


def _step_cost(step, *args):
    """(flops, bytes_accessed) of one compiled step (XLA cost analysis).
    bytes_accessed counts every operand+output touch XLA models — an
    upper bound on true HBM traffic (re-reads that hit VMEM/fusion are
    still counted), so achieved-GB/s derived from it is conservative-
    high; good enough to tell "gather-bound" from "far off roofline"."""
    cost = step.lower(*args).compile().cost_analysis()
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)))


def _report(metric, value, unit, vs_baseline, flops_per_step=0.0,
            sec_per_step=0.0, bytes_per_step=0.0, **extras):
    """One JSON line for the driver; mfu measures against the chip's
    peak (VERDICT round-1: progress is vs the hardware, not a ghost
    GPU number). When bytes_per_step is known the achieved HBM GB/s
    and fraction of peak bandwidth print too, so memory-bound configs
    (Wide&Deep gathers) are judged against the right roofline.

    HBM honesty (VERDICT r5 #2): cost-model bytes_accessed counts
    fused re-reads and can exceed the physical roofline, so headline
    ``hbm_gbs``/``hbm_frac`` prefer xprof hardware-counter values when
    the extras carry them (BENCH_XPROF=1), and the cost-model fallback
    is ALWAYS flagged ``hbm_est: true`` — an unflagged hbm_frac > 1.0
    can no longer reach the record."""
    rec = {"metric": metric, "value": round(value, 2), "unit": unit,
           "vs_baseline": round(vs_baseline, 3), **_device()}
    peak = _peak_tflops()
    if flops_per_step and sec_per_step:
        rec["mfu"] = round(flops_per_step / sec_per_step / (peak * 1e12), 4)
        rec["tflops_per_sec"] = round(flops_per_step / sec_per_step / 1e12, 1)
    hbm_peak = _peak_hbm_gbps()
    if bytes_per_step and sec_per_step:
        gbs = bytes_per_step / sec_per_step / 1e9
        rec["hbm_gbs"] = round(gbs, 1)
        rec["hbm_est"] = True  # cost-model estimate, not a measurement
        rec["hbm_frac"] = round(gbs / hbm_peak, 4)
    rec.update(extras)
    if "hbm_frac_xprof" in rec:  # measured beats estimated
        rec["hbm_frac"] = rec["hbm_frac_xprof"]
        if "hbm_gbs_xprof" in rec:
            rec["hbm_gbs"] = rec["hbm_gbs_xprof"]
        rec["hbm_est"] = False
    if "telemetry" not in rec:
        # every leg's record carries its process's telemetry state
        # (nonzero counters + histogram counts); the suite summary
        # forwards it so one bench_suite_summary line shows what each
        # leg actually exercised
        from mxnet_tpu.telemetry import REGISTRY
        rec["telemetry"] = REGISTRY.snapshot_compact()
    if "slowest_traces" not in rec:
        # the tail-sampled span ring's slowest retained traces: when a
        # leg ran slower than expected, these name the exact requests/
        # epochs to open with telemetry_dump.py --trace <id>
        from mxnet_tpu.telemetry import spans as _spans
        slowest = _spans.slowest_traces(3)
        if slowest:
            rec["slowest_traces"] = [
                {"trace_id": t, "root": r, "ms": d}
                for t, r, d in slowest]
    if "resources" not in rec:
        # per-leg resource footprint: RSS/device-memory watermarks
        # (each leg is its own process, so the peak IS the leg's) —
        # a memory regression shows in bench_suite_summary, not in an
        # OOM three legs later
        from mxnet_tpu.telemetry import resources as _resources
        rec["resources"] = _resources.compact()
    if "profile_top" not in rec:
        # where the leg's HOST time went, from the always-on sampling
        # profiler (empty when MXNET_TPU_PROF=0)
        from mxnet_tpu.telemetry import profiling as _profiling
        if _profiling.PROFILER.running:
            rec["profile_top"] = [
                f"{t['frame']} {t['self_frac'] * 100:.0f}%"
                for t in _profiling.top_self(3)]
    print(json.dumps(rec))
    sys.stdout.flush()


def _make_momentum_sgd(loss_fn, lr):
    """Jitted momentum-SGD train step over (params, moms) pytrees.
    CHAIN>1 fuses that many steps into one dispatched executable
    (mxnet_tpu.engine.chain_steps).

    Cost accounting: XLA cost_analysis counts a lax.scan/while body
    ONCE regardless of trip count (verified empirically: the chained
    ResNet executable reports 2.86 TF — exactly the xprof-measured
    single-step flops), so the chained executable's cost IS the
    per-model-step cost. If an XLA upgrade ever switches to
    trip-multiplied counting, every measurement would read CHAIN-times
    over the physical bound and _guard_impossible would raise loudly
    rather than record inflated MFU."""
    import jax
    import jax.numpy as jnp

    def train_step(params, moms, *args):
        loss, grads = jax.value_and_grad(loss_fn)(params, *args)
        new_moms = jax.tree_util.tree_map(
            lambda m, g: 0.9 * m + g.astype(jnp.float32), moms, grads)
        new_params = jax.tree_util.tree_map(
            lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
            params, new_moms)
        return new_params, new_moms, loss

    if CHAIN > 1:
        from mxnet_tpu.engine import chain_steps
        return chain_steps(train_step, CHAIN, donate_argnums=(0, 1))
    return jax.jit(train_step, donate_argnums=(0, 1))


def _xent(flat, labels_flat):
    """Per-row softmax cross-entropy of (N, V) logits: the fused kernel
    where the op layer's one dispatch rule (pallas_ok_for) says so, the
    jnp twin only where it says so (MXNET_TPU_DISABLE_PALLAS A/Bs)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas as _pallas

    if _pallas.pallas_ok_for(flat):
        return _pallas.softmax_xent_fused(flat, labels_flat)
    logp = jax.nn.log_softmax(flat.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels_flat[:, None], axis=-1)[:, 0]


def _zeros_moms(params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _time_steps(step, params, moms, *args, flops_per_step=0.0,
                bytes_per_step=0.0):
    """Warmup then time STEPS iterations; returns (elapsed_sec). A
    time below the chip's physical bound raises (_guard_impossible)."""
    import jax

    def timed():
        nonlocal params, moms
        t0 = time.perf_counter()
        for _ in range(STEPS):
            params, moms, loss = step(params, moms, *args)
        jax.block_until_ready(loss)
        return time.perf_counter() - t0

    def timed_median():
        # median of WINDOWS windows: single windows swing a few %
        # run-to-run; the guard sees the median
        return _median(timed, WINDOWS)

    for _ in range(WARMUP):
        params, moms, loss = step(params, moms, *args)
    jax.block_until_ready(loss)
    return _guard_impossible(timed_median, flops_per_step, bytes_per_step)


def _median(timed, windows):
    """True median of ``windows`` timing runs (even counts average the
    two middle values — indexing [n//2] alone would report the slower
    one)."""
    if windows == 1:
        return timed()
    xs = sorted(timed() for _ in range(windows))
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def _guard_impossible(timed, flops_per_step, bytes_per_step=0.0):
    """Run ``timed()`` and refuse a result the chip cannot have
    produced: a time implying more than 1.5x its peak FLOP/s (or 8x its
    HBM bandwidth — cost-model bytes over-count fused re-reads, hence
    the wide slack) means the timing is broken, typically a window that
    ended before the device did. That is an error to fix, never a
    number to record and never a reason to time again."""
    dt = timed()
    bound = 0.0
    if flops_per_step > 0:
        bound = STEPS * flops_per_step / (1.5 * _peak_tflops() * 1e12)
    if bytes_per_step > 0:
        bound = max(bound, STEPS * bytes_per_step
                    / (8.0 * _peak_hbm_gbps() * 1e9))
    if dt < bound:
        raise RuntimeError(
            f"measured {STEPS} steps in {dt:.4f}s, below the physical "
            f"bound {bound:.4f}s of this chip ({_peak_tflops()} TFLOP/s, "
            f"{_peak_hbm_gbps()} GB/s): the timing window is broken")
    return dt


def main():
    import jax
    import jax.numpy as jnp

    _setup_cache()

    import mxnet_tpu as mx
    from mxnet_tpu import envvars
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    ctx = mx.current_context()
    s2d = os.environ.get("BENCH_S2D", "0") == "1"
    if os.environ.get("BENCH_DATA") in ("recordio", "pipeline"):
        # data-driven epoch legs step once per REAL batch — chaining
        # would replay one batch CHAIN times
        global CHAIN
        CHAIN = 1
    # BENCH_REMAT="2,3": per-block activation recompute on those stages
    # (jax.checkpoint in the traced step) — trades forward FLOPs for
    # backward HBM traffic on the bandwidth-bound bwd mega-fusions.
    # BENCH_REMAT_POLICY="names:conv_out" saves conv outputs and
    # recomputes only the elementwise BN/relu chain in backward.
    remat = tuple(int(s) for s in os.environ.get("BENCH_REMAT", "").split(",")
                  if s.strip())
    remat_policy = os.environ.get("BENCH_REMAT_POLICY") or None
    net = resnet50_v1(classes=1000, stem="s2d" if s2d else "conv",
                      remat_stages=remat, remat_policy=remat_policy)
    net.initialize(init=mx.initializer.Xavier(), ctx=ctx)
    if DTYPE != "float32":
        net.cast(DTYPE)
    warm = mx.nd.zeros((2, 3, IMAGE, IMAGE), ctx=ctx, dtype=DTYPE)
    with mx.autograd.predict_mode():
        net(warm)

    fn, params = functionalize(net, training=True, ctx=ctx)

    def loss_fn(params, rng, x, y):
        logits = fn(params, rng, x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    step = _make_momentum_sgd(loss_fn, 0.1)
    moms = _zeros_moms(params)
    rng = jax.random.PRNGKey(0)
    x = jnp.asarray(np.random.RandomState(0)
                    .rand(BATCH, 3, IMAGE, IMAGE).astype(np.float32)
                    .astype(np.dtype("float32")), dtype=DTYPE)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 1000, BATCH), jnp.int32)

    if os.environ.get("BENCH_INFER") in ("1", "int8"):
        # forward-only (inference) throughput — fwd runs ~35% MFU vs
        # ~21% for backward (transposed-conv grads), see BASELINE.md.
        # BENCH_INFER=int8: rewrite Dense/Conv2D to the s8xs8->s32 MXU
        # path (contrib.quantization) — v5e int8 peak is 2x bf16
        int8 = os.environ.get("BENCH_INFER") == "int8"
        # BOTH inference variants run predict-mode BN (training=False)
        # so the int8-vs-bf16 comparison measures the same forward
        if int8:
            from mxnet_tpu.contrib.quantization import quantize_net
            with mx.autograd.predict_mode():
                # CALIBRATED scales (static): dynamic per-batch ranges
                # add a min/max reduction per layer per step, measured
                # slower than bf16 (5596 vs 7218 img/s)
                calib = [[mx.nd.array(
                    np.random.RandomState(i).rand(8, 3, IMAGE, IMAGE)
                    .astype(np.float32), ctx=ctx, dtype=DTYPE)]
                    for i in range(4)]
                # BENCH_S8_IF=1: chain conv->relu->conv interfaces in
                # s8 (requantize epilogue) instead of bf16
                quantize_net(net, calib_data=calib, ctx=ctx,
                             s8_interfaces=os.environ.get(
                                 "BENCH_S8_IF") == "1")
                net(warm)  # re-trace materializes int8 weights
        fn, params = functionalize(net, training=False, ctx=ctx)
        if CHAIN > 1:
            # chain forward passes like the train path. A bare scan of
            # identical pure forwards is loop-invariant — XLA may hoist
            # or drop it — so thread a numerically-exact zero
            # (0 * sum(out)) through the input: every iteration then
            # depends on the previous one and must execute.
            def infer_fn(p, rng, x):
                def body(carry_x, _):
                    out = fn(p, rng, carry_x)
                    keep = (jnp.sum(out) * 0).astype(carry_x.dtype)
                    return carry_x + keep, jnp.sum(out)
                return jax.lax.scan(body, x, None, length=CHAIN)
        else:
            def infer_fn(p, rng, x):
                out = fn(p, rng, x)
                keep = (jnp.sum(out) * 0).astype(x.dtype)
                return x + keep, jnp.sum(out)
        # x threads through every call (donated, numerically equal)
        infer = jax.jit(infer_fn, donate_argnums=(2,))
        iflops, ibytes = _step_cost(infer, params, rng, x)
        def timed_infer():
            nonlocal x
            t0 = time.perf_counter()
            for _ in range(STEPS):
                x, out = infer(params, rng, x)
            jax.block_until_ready(out)
            return time.perf_counter() - t0

        for _ in range(WARMUP):
            x, out = infer(params, rng, x)
        jax.block_until_ready(out)
        dt = _guard_impossible(lambda: _median(timed_infer, WINDOWS),
                               iflops * CHAIN, ibytes * CHAIN)
        _report("resnet50_infer_images_per_sec_per_chip",
                BATCH * STEPS * CHAIN / dt,
                "images/sec/chip", 0.0, flops_per_step=iflops,
                sec_per_step=dt / STEPS / CHAIN, bytes_per_step=ibytes,
                batch=BATCH, dtype="int8" if int8 else DTYPE, chain=CHAIN)
        return

    flops, nbytes = _step_cost(step, params, moms, rng, x, y)

    if os.environ.get("BENCH_DATA") in ("recordio", "pipeline"):
        _resnet_from_recordio(loss_fn, params, moms, rng, flops)
        return

    extras = {}
    if os.environ.get("BENCH_XPROF") == "1":
        # BEFORE the timed loop: step donates params/moms, so the
        # capture runs on copies while the originals are still live
        extras = _xprof_true_hbm(step, (params, moms, rng, x, y))

    dt = _time_steps(step, params, moms, rng, x, y,
                     flops_per_step=flops * CHAIN,
                     bytes_per_step=nbytes * CHAIN)

    imgs_per_sec = BATCH * STEPS * CHAIN / dt
    _report("resnet50_train_images_per_sec_per_chip", imgs_per_sec,
            "images/sec/chip", imgs_per_sec / BASELINE_IMGS_PER_SEC,
            flops_per_step=flops, sec_per_step=dt / STEPS / CHAIN,
            bytes_per_step=nbytes, batch=BATCH, dtype=DTYPE,
            conv_nhwc=envvars.get("MXNET_TPU_CONV_NHWC"),
            s2d_stem=s2d, remat_stages=list(remat), chain=CHAIN, **extras)


def _xprof_true_hbm(step, args_):
    """BENCH_XPROF=1: measure TRUE HBM traffic of the step from an
    xprof capture (hlo_stats hbm_bw x self-time per fusion), because
    XLA cost-analysis ``bytes accessed`` counts fused re-reads and
    read >1.0 of the physical roofline on this config (BENCH_r04).
    Opt-in: a trace capture + parse costs ~15 s the driver's budget
    doesn't need to pay every run."""
    import tempfile

    import jax

    tdir = None
    try:
        tools_dir = os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools")
        if tools_dir not in sys.path:
            sys.path.insert(0, tools_dir)
        import xprof_roofline as xr

        import jax.numpy as jnp

        tdir = tempfile.mkdtemp(prefix="bench_xprof_")
        # copies feed the donating step so the caller's buffers survive
        safe = tuple(jax.tree_util.tree_map(jnp.copy, a) for a in args_[:2])
        out = step(*safe, *args_[2:])
        jax.block_until_ready(out)
        n = 3
        with jax.profiler.trace(tdir):
            for _ in range(n):
                out = step(*out[:2], *args_[2:])
            jax.block_until_ready(out)
        rows = list(xr._rows(xr._tool_data(tdir)))
        total_us = sum(xr._f(r, "total_self_time") for r in rows)
        hbm_bytes = sum(xr._f(r, "hbm_bw") * 1e9 *
                        xr._f(r, "total_self_time") * 1e-6 for r in rows)
        if not total_us:
            return {}
        gbps = hbm_bytes / (total_us * 1e-6) / 1e9
        peak = _peak_hbm_gbps()
        # per-model-step: the capture runs chained executables too, so
        # normalize by captured device time, not step count
        rec = {"hbm_gbs_xprof": round(gbps, 1),
               "device_ms_per_step_xprof":
                   round(total_us / 1000.0 / (n * CHAIN), 3)}
        if peak:
            rec["hbm_frac_xprof"] = round(gbps / peak, 4)
        return rec
    except Exception as e:  # profiling must never sink the bench
        print(f"# BENCH_XPROF failed: {e}", file=sys.stderr)
        return {}
    finally:
        if tdir:
            import shutil
            shutil.rmtree(tdir, ignore_errors=True)


def _resnet_from_recordio(loss_fn, params, moms, rng, flops):
    """End-to-end input-pipeline bench (SURVEY §7 hard part #6): feed the
    same jitted ResNet step from a generated JPEG RecordIO file through
    the multiprocess decode pipeline + device prefetch, and report
    img/s plus pipeline-vs-compute utilization (the reference's
    iter_image_recordio_2.cc role)."""
    import tempfile

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.data import DataLoader, DevicePrefetcher
    from mxnet_tpu.gluon.data.dataset import Dataset

    n_img = int(os.environ.get("BENCH_PIPELINE_IMAGES", str(BATCH * (STEPS + WARMUP))))
    workers = int(os.environ.get("BENCH_WORKERS", "8"))
    tmp = tempfile.mkdtemp(prefix="bench_rec_")
    rec_path = os.path.join(tmp, "synthetic.rec")
    idx_path = os.path.join(tmp, "synthetic.idx")
    rs = np.random.RandomState(0)
    rec = mx.recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    for i in range(n_img):
        img = rs.randint(0, 255, (IMAGE, IMAGE, 3), dtype=np.uint8)
        header = mx.recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write_idx(i, mx.recordio.pack_img(header, img, quality=90))
    rec.close()

    class RecDataset(Dataset):
        """JPEG decode in the worker process. Ships uint8 CHW — 4x less
        IPC traffic than float32 (the shared-memory lesson of
        iter_image_recordio_2.cc); normalization happens on-device in
        the jitted step."""

        def __init__(self):
            self._rec = None  # opened lazily per worker process

        def __len__(self):
            return n_img

        def __getitem__(self, i):
            if self._rec is None:
                self._rec = mx.recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
            header, img = mx.recordio.unpack_img(self._rec.read_idx(i))
            return img.transpose(2, 0, 1), np.float32(header.label)

    # pipeline choice: the native C++ batcher (threaded libjpeg decode,
    # CHW batches, no GIL/no IPC) when it builds, else the python
    # multiprocess DataLoader
    pipeline = os.environ.get("BENCH_PIPELINE", "native")
    batcher = None
    if pipeline == "native":
        from mxnet_tpu.io.native import (NativeImageBatcher,
                                         NativeIOUnavailable)
        try:
            batcher = NativeImageBatcher(
                rec_path, idx_path, batch_size=BATCH,
                data_shape=(3, IMAGE, IMAGE), num_threads=workers)
        except NativeIOUnavailable as e:
            print(f"# native batcher unavailable ({e}); the record "
                  "names pipeline=python", file=sys.stderr)
            pipeline = "python"
    if batcher is None:
        loader = DataLoader(RecDataset(), batch_size=BATCH, shuffle=False,
                            num_workers=workers, last_batch="discard")

    # uint8→dtype normalize + label cast live INSIDE the jitted step:
    # eager per-batch conversion ops would each be a separate dispatch
    import jax.numpy as jnp

    def loss_u8(p, rng, x_u8, y_f32):
        x = x_u8.astype(jnp.dtype(DTYPE)) * np.asarray(1.0 / 255.0,
                                                       np.dtype(DTYPE))
        return loss_fn(p, rng, x, y_f32.astype(jnp.int32))

    step = _make_momentum_sgd(loss_u8, 0.1)

    def batches():
        if batcher is not None:
            while True:
                out = batcher.next()
                if out is None:
                    break
                yield out
            batcher.reset()
        else:
            yield from loader

    def run_epoch(p, m):
        n_steps = 0
        loss = None
        # DevicePrefetcher overlaps H2D with compute for BOTH pipelines
        for xb, yb in DevicePrefetcher(batches(), depth=3):
            p, m, loss = step(p, m, rng, xb._data, yb._data)
            n_steps += 1
        if loss is not None:
            jax.block_until_ready(loss)
        return n_steps, p, m

    pipeline_mode = os.environ.get("BENCH_DATA") == "pipeline"
    extras = {}
    if pipeline_mode:
        # leg 1 — standalone decode rate, measured with the device idle
        for _ in batches():  # warm pass: worker spawn + readahead
            pass
        nb = 0
        t0 = time.perf_counter()
        for _ in batches():
            nb += 1
        t_dec = time.perf_counter() - t0
        if nb == 0:
            raise RuntimeError(
                f"pipeline bench produced no full batches "
                f"(BENCH_PIPELINE_IMAGES={n_img} < batch {BATCH}?)")
        decode_rate = nb * BATCH / t_dec
        # leg 2 — synthetic compute rate on a fixed device batch
        xs = jnp.zeros((BATCH, 3, IMAGE, IMAGE), jnp.uint8)
        ys = jnp.zeros((BATCH,), jnp.float32)
        p, m = params, moms
        for _ in range(3):
            p, m, loss = step(p, m, rng, xs, ys)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(10):
            p, m, loss = step(p, m, rng, xs, ys)
        jax.block_until_ready(loss)
        t_cmp = time.perf_counter() - t0
        compute_rate = 10 * BATCH / t_cmp
        params, moms = p, m
        try:
            usable_cores = len(os.sched_getaffinity(0))
        except AttributeError:
            usable_cores = os.cpu_count()
        decode_cores = min(workers, usable_cores)
        extras = {"decode_img_s": round(decode_rate, 1),
                  "compute_img_s": round(compute_rate, 1),
                  "host_cores": usable_cores,
                  "decode_ms_per_img_per_core":
                      round(1000.0 * decode_cores / decode_rate, 3)}

    # warmup epoch: compile + page cache (params are donated — thread
    # the returned state into the timed epoch)
    _, p, m = run_epoch(params, moms)
    t0 = time.perf_counter()
    n_steps, p, m = run_epoch(p, m)
    dt = time.perf_counter() - t0
    imgs_per_sec = n_steps * BATCH / dt
    if pipeline_mode:
        bound = min(extras["decode_img_s"], extras["compute_img_s"])
        extras["pipeline_utilization"] = round(imgs_per_sec / bound, 4)
    _report("resnet50_recordio_images_per_sec_per_chip", imgs_per_sec,
            "images/sec/chip", imgs_per_sec / BASELINE_IMGS_PER_SEC,
            flops_per_step=flops, sec_per_step=dt / max(n_steps, 1),
            batch=BATCH, dtype=DTYPE, workers=workers,
            pipeline=pipeline, pipeline_images=n_img, **extras)


def main_bert():
    """BERT-base MLM pretraining step, tokens/sec/chip (BASELINE #3).

    bf16 trunk, fused Pallas flash-attention/LayerNorm/softmax-CE path.
    No per-chip reference number exists (BASELINE.md: BERT lives in
    GluonNLP, mount empty) — vs_baseline reports 0.0.
    """
    import jax
    import jax.numpy as jnp

    _setup_cache()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.gluon.model_zoo import bert_base
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMHead

    # batch 64 measured fastest (sweep r2: 32→103k, 64→109k, 128→108.5k
    # tok/s at 36.4% MFU)
    batch = int(os.environ.get("BENCH_BATCH", "64"))
    seqlen = int(os.environ.get("BENCH_SEQLEN", "128"))
    vocab = 30522
    ctx = mx.current_context()

    net = bert_base(vocab_size=vocab, max_length=max(512, seqlen),
                    dropout=0.0)
    head = BERTMLMHead(vocab, 768)
    net.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
    head.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
    if DTYPE != "float32":
        net.cast(DTYPE)
        head.cast(DTYPE)

    ids = mx.nd.zeros((2, seqlen), ctx=ctx, dtype="int32")
    tt = mx.nd.zeros((2, seqlen), ctx=ctx, dtype="int32")
    with mx.autograd.predict_mode():
        head(net(ids, tt)[0])

    fn, params = functionalize(net, training=True, ctx=ctx)
    hfn, hparams = functionalize(head, training=True, ctx=ctx)

    # BENCH_PADDED=1: variable-length MLM batch (lengths uniform in
    # [S/2, S]) — valid_length rides the flash kernel's per-row
    # kv-length path and the loss masks padded positions. The real
    # pretraining shape (VERDICT r3 #2).
    # BENCH_PACKED=1: the SAME length distribution, first-fit PACKED
    # into rows of BENCH_PACK_ROWLEN (default 4*S) slots — segment_ids
    # ride the kernel's block-diagonal path, positions restart per
    # sequence, the loss masks padding. Total slot count matches the
    # padded leg (rows * row_len == batch * seqlen) so the two legs
    # spend comparable step budgets; the win shows up as
    # valid_tokens_per_sec.
    padded = os.environ.get("BENCH_PADDED", "0") == "1"
    packed = os.environ.get("BENCH_PACKED", "0") == "1"

    rng = jax.random.PRNGKey(0)
    npr = np.random.RandomState(0)
    ps = (params, hparams)

    if packed:
        from mxnet_tpu.io.packing import pack_sequences, packing_efficiency

        row_len = int(os.environ.get("BENCH_PACK_ROWLEN", str(4 * seqlen)))
        rows = max(1, batch * seqlen // row_len)
        # pack a 4x-oversampled stream first-fit, keep the ROWS fullest
        # rows: first-fit's only low-occupancy rows are the open tail
        # rows of the stream, which a continuous reader would keep
        # filling — the kept rows are its steady state (measured ~0.99
        # occupancy on the U[S/2, S] distribution)
        n_pool = 4 * rows * row_len // (3 * seqlen // 4)
        lens_pool = npr.randint(seqlen // 2, seqlen + 1, n_pool)
        seq_pool = [npr.randint(0, vocab, n).astype(np.int32)
                    for n in lens_pool]
        lab_pool = [npr.randint(0, vocab, n).astype(np.int32)
                    for n in lens_pool]
        pb = pack_sequences(seq_pool, row_len, extras=[lab_pool])
        order = np.argsort(-pb.valid_length)[:rows]
        ids = jnp.asarray(pb.data[order], jnp.int32)
        segs = jnp.asarray(pb.segment_ids[order], jnp.int32)
        pos = jnp.asarray(pb.positions[order], jnp.int32)
        lens = jnp.asarray(pb.valid_length[order], jnp.int32)
        labels = jnp.asarray(pb.extras[0][order], jnp.int32)
        tt = jnp.zeros((rows, row_len), jnp.int32)
        pack_eff = packing_efficiency(pb.segment_ids[order])

        def loss_fn(ps, rng, ids, tt, lens, segs, pos, labels):
            p1, p2 = ps
            seq, _ = fn(p1, rng, ids, tt, lens, None, segs, pos)
            logits = hfn(p2, rng, seq)
            loss = _xent(logits.reshape(-1, vocab), labels.reshape(-1))
            w = (segs > 0).astype(jnp.float32).reshape(-1)
            return (loss.astype(jnp.float32) * w).sum() / w.sum()

        args = (ids, tt, lens, segs, pos, labels)
    else:
        ids = jnp.asarray(npr.randint(0, vocab, (batch, seqlen)), jnp.int32)
        tt = jnp.zeros((batch, seqlen), jnp.int32)
        lens = jnp.asarray(npr.randint(seqlen // 2, seqlen + 1, batch)
                           if padded else np.full(batch, seqlen), jnp.int32)
        labels = jnp.asarray(npr.randint(0, vocab, (batch, seqlen)),
                             jnp.int32)

        def loss_fn(ps, rng, ids, tt, lens, labels):
            p1, p2 = ps
            if padded:
                seq, _ = fn(p1, rng, ids, tt, lens)
            else:
                seq, _ = fn(p1, rng, ids, tt)
            # model dtype logits: the CE kernel upcasts in VMEM
            loss = _xent(hfn(p2, rng, seq).reshape(-1, vocab),
                        labels.reshape(-1))
            if padded:
                w = (jnp.arange(seqlen)[None, :] < lens[:, None]) \
                    .astype(jnp.float32).reshape(-1)
                return (loss.astype(jnp.float32) * w).sum() / w.sum()
            return loss.mean()

        args = (ids, tt, lens, labels)

    step = _make_momentum_sgd(loss_fn, 1e-3)
    moms = _zeros_moms(ps)

    if os.environ.get("BENCH_PRECOMPILE") == "1":
        _precompile(step, ps, moms, rng, *args,
                    seqlen=seqlen, batch=batch, chain=CHAIN, dtype=DTYPE)
        return

    flops, nbytes = _step_cost(step, ps, moms, rng, *args)
    dt = _time_steps(step, ps, moms, rng, *args,
                     flops_per_step=flops * CHAIN,
                     bytes_per_step=nbytes * CHAIN)

    # slots/sec uses all positions (directly comparable to the unmasked
    # config — same flops basis); valid tokens/sec is the useful-work
    # rate on the padded/packed batch
    slots = rows * row_len if packed else batch * seqlen
    slots_per_sec = slots * STEPS * CHAIN / dt
    extras = {}
    if packed:
        extras = {"packed": True, "row_len": row_len, "rows": rows,
                  "packing_efficiency": round(pack_eff, 4),
                  "valid_tokens_per_sec": round(slots_per_sec * pack_eff, 2)}
    elif padded:
        valid_frac = float(np.asarray(lens).sum()) / (batch * seqlen)
        extras = {"padded": True, "valid_frac": round(valid_frac, 4),
                  "valid_tokens_per_sec": round(slots_per_sec * valid_frac,
                                                2)}
    _report("bert_base_train_tokens_per_sec_per_chip", slots_per_sec,
            "tokens/sec/chip", 0.0,
            flops_per_step=flops, sec_per_step=dt / STEPS / CHAIN,
            bytes_per_step=nbytes, batch=rows if packed else batch,
            seqlen=seqlen, dtype=DTYPE, chain=CHAIN, **extras)


def main_causal_lm():
    """Packed CAUSAL LM training step, tokens/sec/chip (ROADMAP
    follow-up: the causal segment kernel path was tested but never
    benchmarked). GPT-small-shaped trunk at the bert_base budget
    (L=12, H=768, A=12 over a 30522 vocab), always packed: the same
    U[S/2, S] length mix as the packed BERT leg, first-fit into
    BENCH_PACK_ROWLEN-slot rows, per-segment causal attention via the
    flash kernel's segment_ids + causal path, next-token labels
    shifted within each segment."""
    import jax
    import jax.numpy as jnp

    _setup_cache()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.io.packing import pack_sequences, packing_efficiency

    batch = int(os.environ.get("BENCH_BATCH", "64"))
    seqlen = int(os.environ.get("BENCH_SEQLEN", "512"))
    vocab = int(os.environ.get("BENCH_VOCAB", "30522"))
    units = int(os.environ.get("BENCH_LM_UNITS", "768"))
    layers = int(os.environ.get("BENCH_LM_LAYERS", "12"))
    heads = int(os.environ.get("BENCH_LM_HEADS", "12"))
    ctx = mx.current_context()

    class PackedCausalLM(mx.gluon.HybridBlock):
        """embed + per-segment positions -> causal encoder -> vocab."""

        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = mx.gluon.nn.Embedding(vocab, units)
                self.pos_embed = mx.gluon.nn.Embedding(seqlen, units)
                self.encoder = mx.gluon.nn.TransformerEncoder(
                    layers, units, 4 * units, heads, dropout=0.0,
                    attention_dropout=0.0, activation="gelu",
                    causal=True)
                self.decoder = mx.gluon.nn.Dense(vocab, flatten=False)

        def hybrid_forward(self, F, ids, positions, valid_length,
                           segment_ids):
            x = self.embed(ids) + self.pos_embed(positions)
            h = self.encoder(x, None, valid_length, segment_ids)
            return self.decoder(h)

    net = PackedCausalLM()
    net.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
    if DTYPE != "float32":
        net.cast(DTYPE)
    warm = mx.nd.zeros((2, seqlen), ctx=ctx, dtype="int32")
    with mx.autograd.predict_mode():
        net(warm, warm, mx.nd.array([seqlen, seqlen], ctx=ctx,
                                    dtype="int32"), warm)
    fn, params = functionalize(net, training=True, ctx=ctx)

    rng = jax.random.PRNGKey(0)
    npr = np.random.RandomState(0)
    row_len = int(os.environ.get("BENCH_PACK_ROWLEN", str(4 * seqlen)))
    rows = max(1, batch * seqlen // row_len)
    # same oversample-and-keep-fullest selection as the packed BERT leg
    n_pool = 4 * rows * row_len // (3 * seqlen // 4)
    lens_pool = npr.randint(seqlen // 2, seqlen + 1, n_pool)
    seq_pool = [npr.randint(0, vocab, n).astype(np.int32)
                for n in lens_pool]
    # next-token labels INSIDE each segment (the last position predicts
    # a fresh random token — same flops, honest LM shape)
    lab_pool = [np.concatenate([s[1:], npr.randint(0, vocab, 1)
                                .astype(np.int32)]) for s in seq_pool]
    pb = pack_sequences(seq_pool, row_len, extras=[lab_pool])
    order = np.argsort(-pb.valid_length)[:rows]
    ids = jnp.asarray(pb.data[order], jnp.int32)
    segs = jnp.asarray(pb.segment_ids[order], jnp.int32)
    pos = jnp.asarray(pb.positions[order], jnp.int32)
    lens = jnp.asarray(pb.valid_length[order], jnp.int32)
    labels = jnp.asarray(pb.extras[0][order], jnp.int32)
    pack_eff = packing_efficiency(pb.segment_ids[order])

    def loss_fn(params, rng, ids, pos, lens, segs, labels):
        logits = fn(params, rng, ids, pos, lens, segs)
        loss = _xent(logits.reshape(-1, vocab), labels.reshape(-1))
        w = (segs > 0).astype(jnp.float32).reshape(-1)
        return (loss.astype(jnp.float32) * w).sum() / w.sum()

    step = _make_momentum_sgd(loss_fn, 1e-3)
    moms = _zeros_moms(params)
    args = (ids, pos, lens, segs, labels)

    flops, nbytes = _step_cost(step, params, moms, rng, *args)
    dt = _time_steps(step, params, moms, rng, *args,
                     flops_per_step=flops * CHAIN,
                     bytes_per_step=nbytes * CHAIN)

    slots = rows * row_len
    slots_per_sec = slots * STEPS * CHAIN / dt
    _report("causal_lm_train_tokens_per_sec_per_chip", slots_per_sec,
            "tokens/sec/chip", 0.0,
            flops_per_step=flops, sec_per_step=dt / STEPS / CHAIN,
            bytes_per_step=nbytes, batch=rows, seqlen=seqlen, dtype=DTYPE,
            chain=CHAIN, packed=True, causal=True, row_len=row_len,
            rows=rows, packing_efficiency=round(pack_eff, 4),
            valid_tokens_per_sec=round(slots_per_sec * pack_eff, 2))


def main_serving():
    """Closed-loop packed continuous-batching serving bench
    (mxnet_tpu/serving): synthetic variable-length traffic from
    BENCH_SERVE_CLIENTS closed-loop clients against a BERT
    encoder/embedder, reporting requests/sec, client-observed
    p50/p95/p99 latency, valid_tokens_per_sec, and the engine's batch
    packing_efficiency. The engine pre-compiles its whole shape
    universe (warmup) so the measured window is steady-state serving,
    not tracing."""
    _setup_cache()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel, bert_serving_entry
    from mxnet_tpu.serving import ServingEngine

    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from serve_loadgen import run_load

    seqlen = int(os.environ.get("BENCH_SEQLEN", "512"))
    vocab = int(os.environ.get("BENCH_VOCAB", "30522"))
    units = int(os.environ.get("BENCH_SERVE_UNITS", "768"))
    layers = int(os.environ.get("BENCH_SERVE_LAYERS", "12"))
    heads = int(os.environ.get("BENCH_SERVE_HEADS", "12"))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "16"))
    reqs = int(os.environ.get("BENCH_SERVE_REQS", "16"))
    max_rows = int(os.environ.get("BENCH_SERVE_ROWS", "8"))
    buckets = tuple(int(b) for b in os.environ.get(
        "BENCH_SERVE_BUCKETS", f"{max(1, seqlen // 4)},{seqlen}")
        .split(","))
    ctx = mx.current_context()

    net = BERTModel(vocab_size=vocab, units=units, hidden_size=4 * units,
                    num_layers=layers, num_heads=heads, max_length=seqlen,
                    dropout=0.0, attention_dropout=0.0, use_pooler=False)
    net.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
    if DTYPE != "float32":
        net.cast(DTYPE)

    engine = ServingEngine(bert_serving_entry(net), ctx=ctx,
                           bucket_lens=buckets, max_rows=max_rows,
                           max_queue_depth=max(64, 8 * clients),
                           pool="mean")
    with engine:
        # scrape-side observability rides the measured run: the loadgen
        # cross-checks /metrics counter deltas against its own books
        metrics_url = engine.expose().url("/metrics")
        engine.warmup()
        # one throwaway closed-loop pass: page caches, thread spin-up
        run_load(engine, n_clients=min(4, clients), requests_per_client=2,
                 min_len=max(4, seqlen // 8), max_len=seqlen, vocab=vocab)
        # fresh stats: the reported packing/latency numbers must cover
        # ONLY the measured window, not the throwaway traffic
        engine.reset_stats()
        report = run_load(engine, n_clients=clients,
                          requests_per_client=reqs,
                          min_len=max(4, seqlen // 8), max_len=seqlen,
                          vocab=vocab, metrics_url=metrics_url)
    snap = report.pop("engine")
    assert report["completed"] == clients * reqs, report
    server = report.get("server", {})
    assert server.get("reconciled", True), server
    cost = report.get("cost", {})
    _report("bert_serving_requests_per_sec_per_chip",
            report["requests_per_sec"], "requests/sec/chip", 0.0,
            seqlen=seqlen, batch=max_rows, clients=clients,
            requests=report["completed"], dtype=DTYPE,
            p50_ms=report["p50_ms"], p95_ms=report["p95_ms"],
            p99_ms=report["p99_ms"],
            valid_tokens_per_sec=report["valid_tokens_per_sec"],
            packing_efficiency=snap["packing_efficiency"],
            serve_buckets=list(buckets),
            compute_p50_ms=snap["latency"]["compute"].get("p50_ms"),
            queue_p50_ms=snap["latency"]["queue"].get("p50_ms"),
            telemetry_reconciled=server.get("reconciled"),
            cost_reconciled=cost.get("reconciled"),
            device_s_per_1k_tokens=cost.get("device_s_per_1k_tokens"),
            slo_compliance=_slo_compliance(report),
            server_p50_ms_est=server.get("latency", {}).get("p50_ms_est"))


def _slo_compliance(report):
    """Error-budget remaining per declared objective off the loadgen's
    ``/slo`` fetch — the serving legs' one-line SLO answer (None when
    MXNET_TPU_SLO=0, or the engine predates the SLO engine)."""
    slo = report.get("slo")
    if not slo:
        return None
    return {name: row.get("error_budget_remaining")
            for name, row in sorted(slo.items())}


def _router_fleet_setup(clients_default, reqs_default):
    """Shared config + fresh-engine factory for the router-fronted
    serving legs (`bert_serving_router`, `bert_serving_restart`): a
    small BERT per engine, BENCH_* env overrides, one code path so the
    two legs cannot drift apart. ``make_engine(i)`` builds a FRESH
    model each call — a restart drill must pay a real re-trace,
    exactly what a process restart pays."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel, bert_serving_entry
    from mxnet_tpu.serving import ServingEngine

    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)

    cfg = {
        "n_engines": int(os.environ.get("BENCH_ROUTER_ENGINES", "2")),
        "seqlen": int(os.environ.get("BENCH_SEQLEN", "256")),
        "vocab": int(os.environ.get("BENCH_VOCAB", "30522")),
        "units": int(os.environ.get("BENCH_SERVE_UNITS", "256")),
        "layers": int(os.environ.get("BENCH_SERVE_LAYERS", "4")),
        "heads": int(os.environ.get("BENCH_SERVE_HEADS", "8")),
        "clients": int(os.environ.get("BENCH_SERVE_CLIENTS",
                                      str(clients_default))),
        "reqs": int(os.environ.get("BENCH_SERVE_REQS",
                                   str(reqs_default))),
        "max_rows": int(os.environ.get("BENCH_SERVE_ROWS", "8")),
    }
    cfg["buckets"] = tuple(int(b) for b in os.environ.get(
        "BENCH_SERVE_BUCKETS",
        f"{max(1, cfg['seqlen'] // 4)},{cfg['seqlen']}").split(","))
    ctx = mx.current_context()

    def make_engine(i):
        net = BERTModel(vocab_size=cfg["vocab"], units=cfg["units"],
                        hidden_size=4 * cfg["units"],
                        num_layers=cfg["layers"], num_heads=cfg["heads"],
                        max_length=cfg["seqlen"], dropout=0.0,
                        attention_dropout=0.0, use_pooler=False)
        net.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
        if DTYPE != "float32":
            net.cast(DTYPE)
        # i is an index (classic legs) or a full engine-id string (the
        # chaos drill's autoscaler spawns replacements by name)
        eid = f"e{i}" if isinstance(i, int) else str(i)
        return ServingEngine(bert_serving_entry(net), ctx=ctx,
                             bucket_lens=cfg["buckets"],
                             max_rows=cfg["max_rows"],
                             max_queue_depth=max(64, 8 * cfg["clients"]),
                             pool="mean", engine_id=eid)

    return cfg, make_engine


def main_serving_router():
    """Multi-engine router serving bench: BENCH_ROUTER_ENGINES
    (default 2) in-process engines behind a ServingRouter, the same
    closed-loop traffic as the single-engine leg driven at the ROUTER.
    Reports router req/s, per-engine request share (least-outstanding
    should keep it near-even), failover count (0 in the happy path —
    nonzero means an engine died mid-bench), and the loadgen's
    reconciliation of the router's AGGREGATED /metrics against client
    accounting. Defaults are smaller than the single-engine leg: the
    number under test is the router plane, not one more BERT forward."""
    _setup_cache()

    from mxnet_tpu.serving import ServingRouter

    cfg, make_engine = _router_fleet_setup(clients_default=16,
                                           reqs_default=16)
    from serve_loadgen import run_load

    n_engines, seqlen, vocab, clients, reqs = (
        cfg["n_engines"], cfg["seqlen"], cfg["vocab"], cfg["clients"],
        cfg["reqs"])

    import contextlib
    with contextlib.ExitStack() as stack:
        engines = [stack.enter_context(make_engine(i))
                   for i in range(n_engines)]
        router = stack.enter_context(ServingRouter(engines=engines))
        metrics_url = router.expose().url("/metrics")
        for eng in engines:
            eng.warmup()
        run_load(router, n_clients=min(4, clients),
                 requests_per_client=2, min_len=max(4, seqlen // 8),
                 max_len=seqlen, vocab=vocab)
        for eng in engines:
            eng.reset_stats()
        report = run_load(router, n_clients=clients,
                          requests_per_client=reqs,
                          min_len=max(4, seqlen // 8), max_len=seqlen,
                          vocab=vocab, metrics_url=metrics_url)
    report.pop("engine")       # the router metric line stands alone;
    # a failed assert below must not dump the whole fleet snapshot
    assert report["completed"] == clients * reqs, report
    server = report.get("server", {})
    assert server.get("reconciled", True), server
    # per-engine share from the /metrics DELTA (window-exact; the
    # router's dispatched counts also cover the warmup pass)
    per_engine = (server.get("per_engine_completed")
                  or report["per_engine"])
    total = max(1, sum(per_engine.values()))
    _report("bert_serving_router_requests_per_sec",
            report["requests_per_sec"], "requests/sec", 0.0,
            seqlen=seqlen, clients=clients, engines=n_engines,
            requests=report["completed"], dtype=DTYPE,
            p50_ms=report["p50_ms"], p95_ms=report["p95_ms"],
            p99_ms=report["p99_ms"],
            valid_tokens_per_sec=report["valid_tokens_per_sec"],
            per_engine={eid: round(n / total, 3)
                        for eid, n in sorted(per_engine.items())},
            failover=report["failovers"],
            engines_up=report["engines_up"],
            cost_reconciled=report.get("cost", {}).get("reconciled"),
            device_s_per_1k_tokens=report.get("cost", {})
            .get("device_s_per_1k_tokens"),
            slo_compliance=_slo_compliance(report),
            telemetry_reconciled=server.get("reconciled"),
            server_p50_ms_est=server.get("latency", {}).get("p50_ms_est"))

    # -- wire-vs-JSON A/B: the same fleet REMOTE-fronted --------------------
    # The in-process run above measures the router plane; this phase
    # measures the DISPATCH TRANSPORT. The engines expose() and the
    # router fronts them by URL, once over the binary wire (persistent
    # multiplexed connections, raw typed ndarrays) and once pinned to
    # the HTTP/JSON long-poll — same engines, same traffic, so the
    # delta is pure serialization+transport. The wire must win on both
    # serialized bytes/request and dispatch-overhead p50.
    from mxnet_tpu.serving.metrics import (wire_bytes_counter,
                                           wire_fallback_counter)

    byt = wire_bytes_counter()
    fall = wire_fallback_counter()

    def _bytes(transport):
        return sum(byt.labels(side="router", transport=transport,
                              direction=d).value for d in ("in", "out"))

    def _fallbacks():
        return sum(fall.labels(engine_id=f"e{i}").value
                   for i in range(n_engines))

    ab = {}
    with contextlib.ExitStack() as stack:
        engines = [stack.enter_context(make_engine(i))
                   for i in range(n_engines)]
        urls = []
        for eng in engines:
            srv = eng.expose(port=0)
            urls.append(f"http://{srv.host}:{srv.port}")
            eng.warmup()
        for transport, wire_flag in (("wire", True), ("json", False)):
            router = ServingRouter(
                {f"e{i}": url for i, url in enumerate(urls)},
                wire=wire_flag, poll_interval_s=0.2)
            with router:
                if wire_flag:
                    deadline = time.perf_counter() + 15.0
                    while time.perf_counter() < deadline and not all(
                            row.get("transport") == "wire"
                            for row in router.scoreboard().values()):
                        time.sleep(0.1)
                    assert all(row.get("transport") == "wire"
                               for row in router.scoreboard().values()), \
                        router.scoreboard()
                b0, f0 = _bytes(transport), _fallbacks()
                rep = run_load(router, n_clients=clients,
                               requests_per_client=reqs,
                               min_len=max(4, seqlen // 8),
                               max_len=seqlen, vocab=vocab)
                nbytes = _bytes(transport) - b0
                assert rep["completed"] == clients * reqs, rep
                over = router.snapshot()["dispatch_overhead"] \
                    .get(transport) or {}
                ab[transport] = {
                    "requests_per_sec": rep["requests_per_sec"],
                    "p50_ms": rep["p50_ms"], "p99_ms": rep["p99_ms"],
                    "bytes_per_request": round(
                        nbytes / max(1, rep["completed"]), 1),
                    "dispatch_overhead_p50_ms": over.get("p50_ms"),
                    "dispatch_overhead_p99_ms": over.get("p99_ms"),
                    # nonzero on the wire run = it limped through HTTP
                    "fallbacks": (int(_fallbacks() - f0)
                                  if wire_flag else None)}
    wire_ab, json_ab = ab["wire"], ab["json"]
    # the acceptance bar: binary framing beats decimal-text JSON on
    # the serialized payload AND on what the transport costs on top
    # of the engine wall
    assert wire_ab["bytes_per_request"] < json_ab["bytes_per_request"], ab
    assert (wire_ab["dispatch_overhead_p50_ms"]
            < json_ab["dispatch_overhead_p50_ms"]), ab
    _report("bert_serving_router_wire_requests_per_sec",
            wire_ab["requests_per_sec"], "requests/sec", 0.0,
            seqlen=seqlen, clients=clients, engines=n_engines,
            dtype=DTYPE, transport="wire", wire=wire_ab, json=json_ab,
            bytes_per_request_ratio=round(
                wire_ab["bytes_per_request"]
                / max(1e-9, json_ab["bytes_per_request"]), 4),
            dispatch_overhead_p50_speedup=round(
                json_ab["dispatch_overhead_p50_ms"]
                / max(1e-9, wire_ab["dispatch_overhead_p50_ms"]), 2))


def main_serving_multitenant():
    """Multi-tenant multi-model serving bench
    (`bert_serving_multitenant`): two named models on every engine of
    a 2-seat router fleet, driven to OVERLOAD by a weighted tenant mix
    (priority:standard:best-effort closed-loop clients), with a live
    hot-swap of one model mid-load.

    The acceptance shape: best-effort absorbs the shedding while
    priority takes none and holds the tightest p99; every named
    tenant's bill reconciles against the server's tenant-slice
    counters; and the mid-load ``swap_model`` loses ZERO requests and
    leaves the new version warm (a post-swap probe answers in
    compile-free milliseconds)."""
    _setup_cache()

    import contextlib
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel, bert_serving_entry
    from mxnet_tpu.serving import (ModelRegistry, ServingEngine,
                                   ServingRouter)

    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from serve_loadgen import parse_tenant_spec, run_load

    seqlen = int(os.environ.get("BENCH_SEQLEN", "128"))
    vocab = int(os.environ.get("BENCH_VOCAB", "30522"))
    units = int(os.environ.get("BENCH_SERVE_UNITS", "128"))
    layers = int(os.environ.get("BENCH_SERVE_LAYERS", "2"))
    heads = int(os.environ.get("BENCH_SERVE_HEADS", "4"))
    reqs = int(os.environ.get("BENCH_SERVE_REQS", "10"))
    max_rows = int(os.environ.get("BENCH_SERVE_ROWS", "2"))
    # small queue + rows ON PURPOSE: the tenant mix must overrun the
    # fleet (clients > queues + in-flight) so the WFQ eviction order
    # (best-effort first, priority never) is actually exercised, not
    # just plausible
    queue_depth = int(os.environ.get("BENCH_SERVE_QUEUE", "2"))
    tenants = parse_tenant_spec(os.environ.get(
        "BENCH_TENANTS", "priority:2,standard:4,best-effort:10"))
    p99_bound_ms = float(os.environ.get("BENCH_TENANT_P99_MS", "5000"))
    buckets = tuple(int(b) for b in os.environ.get(
        "BENCH_SERVE_BUCKETS",
        f"{max(1, seqlen // 4)},{seqlen}").split(","))
    model_ids = ("m-a", "m-b")
    ctx = mx.current_context()

    def make_entry():
        net = BERTModel(vocab_size=vocab, units=units,
                        hidden_size=4 * units, num_layers=layers,
                        num_heads=heads, max_length=seqlen, dropout=0.0,
                        attention_dropout=0.0, use_pooler=False)
        net.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
        if DTYPE != "float32":
            net.cast(DTYPE)
        return bert_serving_entry(net)

    with contextlib.ExitStack() as stack:
        engines = []
        for i in range(2):
            reg = ModelRegistry()
            entry = make_entry()
            for mid in model_ids:
                reg.register(mid, entry, version="v1")
            engines.append(stack.enter_context(ServingEngine(
                reg, ctx=ctx, bucket_lens=buckets, max_rows=max_rows,
                max_queue_depth=queue_depth, pool="mean",
                engine_id=f"e{i}")))
        router = stack.enter_context(ServingRouter(engines=engines))
        metrics_url = router.expose().url("/metrics")
        for eng in engines:
            eng.warmup()
        run_load(router, n_clients=4, requests_per_client=2,
                 min_len=max(4, seqlen // 8), max_len=seqlen,
                 vocab=vocab, model_ids=list(model_ids))
        for eng in engines:
            eng.reset_stats()

        # mid-load hot-swap: a fresh m-b v2 is warm-replayed and cut
        # over on BOTH seats while the tenant mix is in full flight
        swap = {"ms": None, "error": None}

        def swapper():
            time.sleep(0.5)
            try:
                entry2 = make_entry()
                t0 = time.perf_counter()
                for eng in engines:
                    eng.swap_model(entry2, model_id="m-b",
                                   version="v2")
                swap["ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            except Exception as e:       # surfaced in the assert below
                swap["error"] = repr(e)

        th = threading.Thread(target=swapper,
                              name="bench_hot_swap", daemon=True)
        th.start()
        report = run_load(router, requests_per_client=reqs,
                          min_len=max(4, seqlen // 8), max_len=seqlen,
                          vocab=vocab, metrics_url=metrics_url,
                          tenants=tenants, model_ids=list(model_ids))
        th.join(timeout=600.0)
        # post-swap warmth: one direct v2 probe per seat — warm means
        # NO compile on the user path (milliseconds, not seconds)
        probe_ms = []
        for eng in engines:
            assert eng.snapshot()["models"]["m-b"] == "v2", \
                eng.snapshot()["models"]
            t0 = time.perf_counter()
            eng.submit(np.arange(1, min(buckets) + 1, dtype=np.int32),
                       model_id="m-b").result(timeout=600.0)
            probe_ms.append(round((time.perf_counter() - t0) * 1e3, 3))
    report.pop("engine")
    trep = report["tenants"]
    pri = trep["t-priority"]
    be = trep["t-best-effort"]
    # zero-loss through the swap: nothing errored; shedding is the
    # WFQ's deliberate overload answer, and it lands on best-effort
    # while priority takes none
    assert swap["error"] is None, swap
    assert report["errors"] == 0, report
    assert be["shed"] > 0, trep
    assert pri["shed"] == 0, trep
    assert pri["p99_ms"] is not None and pri["p99_ms"] <= p99_bound_ms, \
        trep
    assert report.get("tenants_reconciled", True), \
        report.get("tenant_mismatches")
    _report("bert_serving_multitenant_requests_per_sec",
            report["requests_per_sec"], "requests/sec", 0.0,
            seqlen=seqlen, clients=len(tenants),
            requests=report["completed"], dtype=DTYPE, engines=2,
            models=len(model_ids),
            p50_ms=report["p50_ms"], p99_ms=report["p99_ms"],
            tenants={t: {k: row[k] for k in
                         ("class", "completed", "shed", "p50_ms",
                          "p99_ms", "client_tokens")}
                     for t, row in sorted(trep.items())},
            priority_p99_ms=pri["p99_ms"],
            best_effort_shed=be["shed"],
            tenants_reconciled=report.get("tenants_reconciled"),
            swap_ms=swap["ms"], post_swap_probe_ms=probe_ms,
            cost_reconciled=report.get("cost", {}).get("reconciled"),
            slo_compliance=_slo_compliance(report))


def main_decode_serving():
    """Autoregressive decode serving bench (`lm_decode_serving`): a
    paged-KV causal LM behind the continuous-batching
    ``DecodeEngine``, streamed tokens end to end.

    Three phases in one leg:

    1. **Headline (router-fronted):** BENCH_ROUTER_ENGINES decode
       engines behind a ``ServingRouter``; closed-loop clients consume
       token STREAMS. Reports generated tokens/s, client-observed TTFT
       and inter-token p50/p99, peak KV-page occupancy, slot churn
       (joins/leaves), and the server-side reconciliation (requests +
       cost ledger with canary exclusion). Every stream is verified
       byte-identical to its final result.
    2. **Iteration-level vs STATIC batching A/B at equal rows:** the
       same traffic against one engine scheduling Orca-style
       (joins at any iteration boundary) vs classic cohort batching
       (joins only into an empty batch). Iteration-level must WIN on
       tokens/s — with varied generation lengths the static cohort
       idles finished slots until its longest member drains.
    3. **Wire-vs-JSON streamed dispatch A/B:** one engine
       remote-fronted; the same streamed traffic once over partial
       RESULT frames on the binary wire, once over chunked-JSON-lines
       HTTP. The wire must win serialized bytes/request.
    4. **Prefix KV reuse A/B:** shared-system-prompt traffic
       (``prompt_reuse=0.9``) against one engine with the prefix cache
       ON vs OFF, both on the chunked-prefill path. Reuse must WIN
       TTFT p50 AND device-seconds per 1k generated tokens — shared
       full pages skip their prefill chunks entirely.
    5. **Chunked-prefill A/B:** one LONG prompt admitted into a batch
       of running decodes, prefill budget 64 vs 0 (whole-prompt dense
       step). Chunking must WIN the background streams' inter-token
       p99 — the dense arm stalls every running decode for the whole
       long prefill. Long-prompt TTFT is reported for both arms.
    6. **Seeded-sampling failover:** two wire-fronted seats behind a
       router; a seeded (temperature>0) streamed request's carrying
       connection is KILLED mid-stream. The per-request seed rides the
       dispatch payload, so the sibling's re-run resamples the exact
       sequence: the client stream must stay gap-free and
       duplicate-free and match a solo same-seed run byte-identically
       (identical seeds ⇒ identical sequences, any seat).
    """
    _setup_cache()

    import contextlib

    from mxnet_tpu.serving import (DecodeEngine, PagedCausalLM,
                                   ServingRouter)

    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from serve_loadgen import run_decode_load

    vocab = int(os.environ.get("BENCH_VOCAB", "2048"))
    units = int(os.environ.get("BENCH_DECODE_UNITS", "128"))
    layers = int(os.environ.get("BENCH_DECODE_LAYERS", "2"))
    heads = int(os.environ.get("BENCH_DECODE_HEADS", "4"))
    max_len = int(os.environ.get("BENCH_DECODE_MAXLEN", "256"))
    max_new = int(os.environ.get("BENCH_DECODE_NEW", "24"))
    rows = int(os.environ.get("BENCH_SERVE_ROWS", "8"))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    reqs = int(os.environ.get("BENCH_SERVE_REQS", "4"))
    n_engines = int(os.environ.get("BENCH_ROUTER_ENGINES", "2"))
    buckets = (16, 64)

    def make_engine(eid, iteration_level=True, model_wrap=None, **kw):
        lm = PagedCausalLM(vocab=vocab, units=units, layers=layers,
                           heads=heads,
                           max_len=kw.pop("max_len", max_len), seed=0)
        if model_wrap is not None:
            lm = model_wrap(lm)
        return DecodeEngine(lm,
                            prefill_bucket_lens=kw.pop("buckets",
                                                       buckets),
                            max_rows=rows, max_new_tokens=max_new,
                            iteration_level=iteration_level,
                            engine_id=eid, **kw)

    load_kw = dict(n_clients=clients, requests_per_client=reqs,
                   min_prompt=8, max_prompt=max(buckets), vocab=vocab,
                   min_new=max(2, max_new // 4), max_new=max_new)

    # -- phase 1: headline, router-fronted streamed decode ------------------
    with contextlib.ExitStack() as stack:
        engines = [stack.enter_context(make_engine(f"e{i}"))
                   for i in range(n_engines)]
        for eng in engines:
            eng.warmup()
        router = stack.enter_context(ServingRouter(engines=engines))
        metrics_url = router.expose().url("/metrics")
        # one throwaway pass (page caches, thread spin-up), then a
        # fresh measurement window
        run_decode_load(router, n_clients=min(4, clients),
                        requests_per_client=1, min_prompt=8,
                        max_prompt=max(buckets), vocab=vocab,
                        min_new=2, max_new=4)
        for eng in engines:
            eng.reset_stats()
        report = run_decode_load(router, metrics_url=metrics_url,
                                 watch_engines=engines, **load_kw)
    assert report["completed"] == clients * reqs, report
    assert report["stream_mismatches"] == 0, report
    server = report.get("server", {})
    assert server.get("reconciled", True), server
    # tail-latency attribution: every request's server-side critical
    # path rides the reply; the decompositions must sum to >=95% of
    # their own wall, the remainder explicitly unattributed
    from mxnet_tpu.telemetry import attribution as _attribution
    breakdown = report.get("breakdown")
    if _attribution.enabled():
        assert breakdown is not None, \
            "attribution enabled but no request carried a breakdown"
        assert breakdown["missing"] == 0, breakdown
        share = breakdown.get("attributed_share")
        assert share is not None and share >= 0.95, breakdown

    # -- phase 2: iteration-level vs static batching, equal rows ------------
    ab = {}
    for mode, iteration_level in (("iteration", True), ("static", False)):
        with make_engine(f"ab_{mode}",
                         iteration_level=iteration_level) as eng:
            eng.warmup()
            rep = run_decode_load(eng, watch_engines=[eng], **load_kw)
        assert rep["completed"] == clients * reqs, (mode, rep)
        assert rep["stream_mismatches"] == 0, (mode, rep)
        ab[mode] = {"tokens_per_sec": rep["tokens_per_sec"],
                    "ttft_p50_ms": rep["ttft_p50_ms"],
                    "inter_token_p99_ms": rep["inter_token_p99_ms"],
                    "kv_occupancy_peak": rep.get("kv_occupancy_peak"),
                    "slot_utilization":
                        rep["engine"]["decode"]["slot_utilization"]}
    # the acceptance bar: joins at iteration boundaries keep slots
    # busy; the static cohort idles finished rows until its longest
    # member drains
    assert (ab["iteration"]["tokens_per_sec"]
            > ab["static"]["tokens_per_sec"]), ab

    # -- phase 3: wire-vs-JSON streamed dispatch A/B ------------------------
    from mxnet_tpu.serving.metrics import wire_bytes_counter

    byt = wire_bytes_counter()

    def _bytes(transport):
        return sum(byt.labels(side="router", transport=transport,
                              direction=d).value for d in ("in", "out"))

    wire_ab = {}
    with make_engine("w0") as eng:
        srv = eng.expose(port=0)
        url = f"http://{srv.host}:{srv.port}"
        eng.warmup()
        for transport, wire_flag in (("wire", True), ("json", False)):
            router = ServingRouter({"w0": url}, wire=wire_flag,
                                   poll_interval_s=0.2)
            with router:
                if wire_flag:
                    deadline = time.perf_counter() + 15.0
                    while time.perf_counter() < deadline and not all(
                            row.get("transport") == "wire"
                            for row in router.scoreboard().values()):
                        time.sleep(0.1)
                    assert all(row.get("transport") == "wire"
                               for row in router.scoreboard().values()), \
                        router.scoreboard()
                b0 = _bytes(transport)
                rep = run_decode_load(router, n_clients=min(4, clients),
                                      requests_per_client=2,
                                      min_prompt=8,
                                      max_prompt=max(buckets),
                                      vocab=vocab,
                                      min_new=max(2, max_new // 4),
                                      max_new=max_new)
                nbytes = _bytes(transport) - b0
                assert rep["completed"] == min(4, clients) * 2, rep
                assert rep["stream_mismatches"] == 0, rep
                over = router.snapshot()["dispatch_overhead"] \
                    .get(transport) or {}
                wire_ab[transport] = {
                    "tokens_per_sec": rep["tokens_per_sec"],
                    "inter_token_p99_ms": rep["inter_token_p99_ms"],
                    "dispatch_overhead_p50_ms": over.get("p50_ms"),
                    "bytes_per_request": round(
                        nbytes / max(1, rep["completed"]), 1)}
    # the decode-transport bar: what the transport costs ON TOP of the
    # engine's generation wall. (Bytes are reported but not asserted:
    # per-token payloads are tiny dicts either way — the binary wire's
    # decode win is latency/overhead, unlike the encoder leg where raw
    # ndarray framing also wins the byte count.)
    assert (wire_ab["wire"]["dispatch_overhead_p50_ms"]
            < wire_ab["json"]["dispatch_overhead_p50_ms"]), wire_ab

    # -- phase 4: prefix KV reuse A/B (shared system prompts) ---------------
    long_len = int(os.environ.get("BENCH_DECODE_LONG_PROMPT", "192"))

    class _PrefillPaced:
        """Per-token prefill pacer, applied to BOTH arms of the prefix
        and chunking A/Bs: the bench model is small enough that a full
        dense prefill costs about one decode step, so without pacing
        the A/Bs measure dispatch overhead instead of the scheduling
        properties under test (a production-sized prefill runs
        proportional to its padded token count, which is exactly what
        the sleep models)."""

        def __init__(self, m, per_tok_s=0.5e-3):
            self._m, self._c = m, per_tok_s
            self.spec = m.spec

        def prefill(self, caches, ids, *a, **k):
            time.sleep(self._c * int(np.asarray(ids).shape[-1]))
            return self._m.prefill(caches, ids, *a, **k)

        def prefill_chunk(self, caches, ids, *a, **k):
            time.sleep(self._c * int(np.asarray(ids).shape[-1]))
            return self._m.prefill_chunk(caches, ids, *a, **k)

        def decode_step(self, *a, **k):
            return self._m.decode_step(*a, **k)

    # shared prefix = half the long bucket (several FULL pages, spanning
    # whole prefill chunks) — a hit must skip chunk-iterations, not just
    # trim one chunk's tail
    reuse_kw = dict(load_kw, min_prompt=long_len // 2,
                    max_prompt=long_len, prompt_reuse=0.9)
    reuse_ab = {}
    for mode, prefix_on in (("reuse", True), ("cold", False)):
        with make_engine(f"px_{mode}", prefix_cache=prefix_on,
                         model_wrap=_PrefillPaced,
                         max_len=max(max_len, 2 * long_len),
                         buckets=(16, long_len)) as eng:
            eng.warmup()
            murl = eng.expose(port=0).url("/metrics")
            # throwaway pass: spins client threads and (reuse arm)
            # seeds the prefix index with the shared system prompt —
            # the measured window then runs against a warm index
            run_decode_load(eng, n_clients=2, requests_per_client=1,
                            min_prompt=reuse_kw["min_prompt"],
                            max_prompt=reuse_kw["max_prompt"],
                            vocab=vocab, min_new=2, max_new=4,
                            prompt_reuse=1.0)
            rep = run_decode_load(eng, metrics_url=murl,
                                  watch_engines=[eng], **reuse_kw)
        assert rep["completed"] == clients * reqs, (mode, rep)
        assert rep["stream_mismatches"] == 0, (mode, rep)
        dev = rep["cost"]["client_device_s"]
        gen = max(1, rep["generated_tokens"])
        reuse_ab[mode] = {
            "ttft_p50_ms": rep["ttft_p50_ms"],
            "tokens_per_sec": rep["tokens_per_sec"],
            "device_s_per_1k_generated": round(dev * 1e3 / gen, 6),
            "prefix": rep.get("prefix")}
    # the acceptance bars: the reuse arm actually hit the index, and
    # skipping the shared pages' prefill chunks shows up both in
    # first-token latency and in device-seconds per generated token
    assert reuse_ab["reuse"]["prefix"]["hits"] > 0, reuse_ab
    assert (reuse_ab["reuse"]["ttft_p50_ms"]
            < reuse_ab["cold"]["ttft_p50_ms"]), reuse_ab
    assert (reuse_ab["reuse"]["device_s_per_1k_generated"]
            < reuse_ab["cold"]["device_s_per_1k_generated"]), reuse_ab

    # -- phase 5: chunked prefill A/B — long prompt into a running batch ----
    import threading

    from mxnet_tpu.serving.metrics import nearest_rank

    chunk_ab = {}
    for mode, budget in (("chunked", 64), ("dense", 0)):
        with make_engine(f"cp_{mode}", prefill_budget=budget,
                         model_wrap=_PrefillPaced,
                         max_len=max(max_len, 2 * long_len),
                         buckets=(16, long_len)) as eng:
            eng.warmup()
            rs = np.random.RandomState(7)
            long_prompt = rs.randint(1, vocab, long_len) \
                .astype(np.int32)
            gaps, lock = [], threading.Lock()
            n_bg, bg_new = min(4, rows - 1), 32
            first = [0]
            ready = threading.Event()

            def bg(cid):
                rsc = np.random.RandomState(100 + cid)
                toks = rsc.randint(1, vocab, 12).astype(np.int32)
                fut = eng.submit(toks, max_new_tokens=bg_new,
                                 stream=True)
                last = None
                for _ in fut.stream(timeout=600):
                    now = time.perf_counter()
                    with lock:
                        if last is None:
                            first[0] += 1
                            if first[0] == n_bg:
                                ready.set()
                        else:
                            gaps.append((now - last) * 1e3)
                    last = now
                fut.result(timeout=0)

            threads = [threading.Thread(
                target=bg, args=(c,), daemon=True,
                name=f"mxnet_tpu_bench_decode_bg{c}")
                for c in range(n_bg)]
            for t in threads:
                t.start()
            assert ready.wait(timeout=120), "background decode stalled"
            # the long prompt lands in a RUNNING batch: the dense arm
            # prefills it in one iteration-blocking step, the chunked
            # arm interleaves budget-sized slices between decode
            # iterations
            t0 = time.perf_counter()
            lfut = eng.submit(long_prompt, max_new_tokens=4,
                              stream=True)
            ttft = None
            for _ in lfut.stream(timeout=600):
                if ttft is None:
                    ttft = (time.perf_counter() - t0) * 1e3
            lfut.result(timeout=0)
            for t in threads:
                t.join()
            chunk_ab[mode] = {
                "bg_inter_token_p99_ms": round(
                    nearest_rank(sorted(gaps), 99), 3),
                "bg_gaps": len(gaps),
                "long_ttft_ms": round(ttft, 3),
                "prefill_chunks":
                    eng.decode_stats.snapshot()["prefill_chunks"]}
    assert chunk_ab["chunked"]["prefill_chunks"] > 0, chunk_ab
    # the acceptance bar: chunking bounds how long any running decode
    # waits behind the long prefill
    assert (chunk_ab["chunked"]["bg_inter_token_p99_ms"]
            < chunk_ab["dense"]["bg_inter_token_p99_ms"]), chunk_ab

    # -- phase 6: seeded sampling failover — replay is byte-identical -------
    class _Paced:
        """Decode-step pacer: slow generation enough that the kill
        lands mid-stream (same shim as the serving tests use)."""

        def __init__(self, m, delay_s=0.02):
            self._m, self._d = m, delay_s
            self.spec = m.spec

        def prefill(self, *a, **k):
            return self._m.prefill(*a, **k)

        def prefill_chunk(self, *a, **k):
            return self._m.prefill_chunk(*a, **k)

        def decode_step(self, *a, **k):
            time.sleep(self._d)
            return self._m.decode_step(*a, **k)

    sample = dict(temperature=0.8, top_k=40, top_p=0.95)
    seed_prompt = list(range(1, 9))
    s_engines = [make_engine(f"sd{i}", model_wrap=_Paced)
                 for i in range(2)]
    with s_engines[0], s_engines[1]:
        urls = {}
        for eng in s_engines:
            eng.warmup()
            srv = eng.expose(port=0)
            urls[eng.engine_id] = f"http://{srv.host}:{srv.port}"
        # identical seeds ⇒ identical sequences, on EITHER seat: the
        # sampling key is a pure function of (seed, position)
        solo = s_engines[0].infer(seed_prompt, max_new_tokens=12,
                                  seed=1234, **sample).tolist()
        twin = s_engines[1].infer(seed_prompt, max_new_tokens=12,
                                  seed=1234, **sample).tolist()
        assert solo == twin, (solo, twin)
        other = s_engines[0].infer(seed_prompt, max_new_tokens=12,
                                   seed=4321, **sample).tolist()
        with ServingRouter(urls, wire=True,
                           poll_interval_s=0.1) as s_router:
            deadline = time.perf_counter() + 30.0
            while time.perf_counter() < deadline and not all(
                    row.get("transport") == "wire"
                    for row in s_router.scoreboard().values()):
                time.sleep(0.1)
            fut = s_router.submit(seed_prompt, max_new_tokens=12,
                                  stream=True, seed=1234, **sample)
            seen, killed = [], [False]
            for part in fut.stream(timeout=120):
                seen.append(part)
                if len(seen) == 3 and not killed[0]:
                    killed[0] = True
                    busy = {eid for eid, row
                            in s_router.scoreboard().items()
                            if row.get("outstanding")}
                    for eng in s_engines:
                        if eng.engine_id in busy:
                            eng._wire.kill_connections()
            out = fut.result(timeout=0).tolist()
        assert killed[0]
        # the stream survived the mid-flight kill gap-free and
        # duplicate-free, and the failover re-run RESAMPLED the exact
        # sequence — the seed, not the seat, owns the randomness
        idxs = [p["index"] for p in seen]
        assert idxs == list(range(len(seen))), idxs
        assert [p["token"] for p in seen] == out, (seen, out)
        assert out == solo, (out, solo)
        assert other != solo, "distinct seeds produced equal sequences"
        failed_over = sum(e.stats.count("submitted")
                          for e in s_engines) >= 2
    seeded = {"stream_mismatches": 0 if [p["token"] for p in seen]
              == out else 1,
              "replayed_matches_solo": out == solo,
              "distinct_seed_differs": other != solo,
              "failover_reruns": failed_over}

    cost = report.get("cost", {})
    _report("lm_decode_serving_tokens_per_sec",
            report["tokens_per_sec"], "tokens/sec", 0.0,
            clients=clients, engines=n_engines, batch=rows,
            requests=report["completed"],
            generated_tokens=report["generated_tokens"], dtype=DTYPE,
            p50_ms=report["p50_ms"], p99_ms=report["p99_ms"],
            ttft_p50_ms=report["ttft_p50_ms"],
            ttft_p95_ms=report["ttft_p95_ms"],
            inter_token_p50_ms=report["inter_token_p50_ms"],
            inter_token_p99_ms=report["inter_token_p99_ms"],
            kv_occupancy=report.get("kv_occupancy_peak"),
            churn=report.get("churn"),
            per_engine=report.get("per_engine"),
            stream_mismatches=report["stream_mismatches"],
            static_tokens_per_sec=ab["static"]["tokens_per_sec"],
            iteration_speedup=round(
                ab["iteration"]["tokens_per_sec"]
                / max(1e-9, ab["static"]["tokens_per_sec"]), 3),
            decode_ab=ab, wire=wire_ab["wire"], json=wire_ab["json"],
            prefix_reuse_ab=reuse_ab,
            prefix_reuse_ttft_speedup=round(
                reuse_ab["cold"]["ttft_p50_ms"]
                / max(1e-9, reuse_ab["reuse"]["ttft_p50_ms"]), 3),
            chunked_prefill_ab=chunk_ab,
            chunked_prefill_p99_win=round(
                chunk_ab["dense"]["bg_inter_token_p99_ms"]
                / max(1e-9,
                      chunk_ab["chunked"]["bg_inter_token_p99_ms"]),
                3),
            seeded=seeded,
            attributed_share=(breakdown or {}).get("attributed_share"),
            unattributed_ms=(breakdown or {}).get("unattributed_ms"),
            stage_breakdown=breakdown,
            telemetry_reconciled=server.get("reconciled"),
            cost_reconciled=cost.get("reconciled"),
            device_s_per_1k_tokens=cost.get("device_s_per_1k_tokens"),
            slo_compliance=_slo_compliance(report))


def main_serving_restart():
    """Rolling-restart serving drill (the warm-restart acceptance
    leg): BENCH_ROUTER_ENGINES (default 2) engines behind a router
    under closed-loop load; mid-load one engine is KILLED (abort) —
    failover must requeue its in-flight work to siblings with zero
    request loss — and replaced twice: first COLD (fresh model, no
    warmup: the first request it serves pays trace+compile), then
    killed again and replaced WARM (fresh model, ``warmup`` replaying
    the router's fleet-union manifest against the persistent compile
    cache BEFORE the seat admits traffic). Reports the loadgen's
    observed time-to-first-token after each restart, the failover
    count, and asserts every submitted request completed."""
    _setup_cache()

    import contextlib
    import threading

    from mxnet_tpu.serving import ServingRouter

    # smaller closed-loop than the router leg: the number under test
    # is the restart/TTFT story, not sustained throughput
    cfg, make_engine = _router_fleet_setup(clients_default=8,
                                           reqs_default=24)
    from serve_loadgen import run_load

    n_engines, seqlen, vocab, clients, reqs = (
        cfg["n_engines"], cfg["seqlen"], cfg["vocab"], cfg["clients"],
        cfg["reqs"])

    total = clients * reqs
    victim = f"e{n_engines - 1}"
    drill = {}
    drill_err = []
    npr = np.random.RandomState(7)
    probe_tokens = npr.randint(1, vocab,
                               max(4, seqlen // 2)).astype(np.int32)

    def probe_ttft(eng):
        """Time-to-first-token of a just-(re)started engine: one
        direct request, wall-clocked — cold pays trace+compile, warm
        (manifest replayed) pays only the forward."""
        t0 = time.perf_counter()
        eng.submit(probe_tokens).result(timeout=600.0)
        return round((time.perf_counter() - t0) * 1e3, 3)

    with contextlib.ExitStack() as stack:
        engines = [stack.enter_context(make_engine(i))
                   for i in range(n_engines)]
        # replacement incarnations built UP FRONT (fresh params, never
        # traced) so the swap window under load is the restart itself,
        # not python model construction
        cold_eng = make_engine(n_engines - 1)
        warm_eng = make_engine(n_engines - 1)
        stack.callback(cold_eng.stop)
        stack.callback(warm_eng.stop)
        router = stack.enter_context(
            ServingRouter(engines=engines, poll_interval_s=0.2))
        metrics_url = router.expose().url("/metrics")
        for eng in engines:
            eng.warmup()

        def wait_completed(n, timeout_s=600.0):
            deadline = time.monotonic() + timeout_s
            while router.count("completed") < n \
                    and time.monotonic() < deadline:
                time.sleep(0.02)

        def controller():
            try:
                # phase 1: steady state reached -> kill + COLD restart
                # (no warmup: its first request pays trace+compile)
                wait_completed(max(1, total // 6))
                engines[-1].stop(drain=False)
                router.remove_engine(victim)
                cold_eng.start()
                drill["ttft_cold_ms"] = probe_ttft(cold_eng)
                router.add_engine(victim, cold_eng)
                # phase 2: kill the replacement too; WARM restart
                # replays the router's fleet manifest against the
                # persistent cache BEFORE admitting traffic
                wait_completed(max(2, total // 2))
                cold_eng.stop(drain=False)
                router.remove_engine(victim)
                warm_eng.start()
                warm_eng.warmup(manifest=router.warmup_manifest())
                drill["ttft_warm_ms"] = probe_ttft(warm_eng)
                router.add_engine(victim, warm_eng)
            except Exception as e:       # surface drill bugs loudly:
                drill_err.append(e)      # the leg must not hang silent

        ctl = threading.Thread(target=controller, daemon=True,
                               name="bench_restart_controller")
        ctl.start()
        report = run_load(router, n_clients=clients,
                          requests_per_client=reqs,
                          min_len=max(4, seqlen // 8), max_len=seqlen,
                          vocab=vocab, metrics_url=metrics_url)
        ctl.join(timeout=600.0)

    assert not drill_err, drill_err
    report.pop("engine")
    # ZERO LOST REQUESTS through two engine kills: every submitted
    # request completed (failover requeued the victim's work)
    assert report["completed"] == total, report
    assert report["errors"] == 0, report
    server = report.get("server", {})
    assert server.get("reconciled", True), server
    restarts = report.get("restarts") or []
    ttft_cold = drill.get("ttft_cold_ms")
    ttft_warm = drill.get("ttft_warm_ms")
    _report("bert_serving_restart_ttft_ms",
            ttft_warm if ttft_warm is not None else -1.0, "ms", 0.0,
            seqlen=seqlen, clients=clients, engines=n_engines,
            requests=report["completed"], dtype=DTYPE,
            ttft_cold_ms=ttft_cold, ttft_warm_ms=ttft_warm,
            restarts=restarts, failover=report["failovers"],
            lost=total - report["completed"],
            p50_ms=report["p50_ms"], p99_ms=report["p99_ms"],
            slo_compliance=_slo_compliance(report),
            telemetry_reconciled=server.get("reconciled"))


def main_serving_chaos():
    """Self-healing chaos drill leg (the ROADMAP 3a–c acceptance):
    BENCH_ROUTER_ENGINES (min 3) BERT engines behind TWO active/active
    routers under closed-loop load. The scripted faults and their
    asserted recoveries: an induced hot-spot sheds routing weight off
    the slow seat (per-seat share measurably moves), a seat kill is
    replaced manifest-warm by the autoscaler (TTFT-probed before it
    admits traffic), and a router kill hands every in-flight request
    to the surviving peer (journal adoption + client cid resubmit).
    Asserts SLO re-convergence, one correlated incident per induced
    fault, and ZERO lost requests. The suite entry pins the
    drill-speed judging clocks (window scale, eval period, latency
    objective) in its env."""
    _setup_cache()

    cfg, make_engine = _router_fleet_setup(clients_default=6,
                                           reqs_default=8)
    from serve_loadgen import run_chaos_drill

    n_engines = max(3, cfg["n_engines"])
    hot_ms = float(os.environ.get("BENCH_CHAOS_HOT_MS", "1500"))
    t0 = time.perf_counter()
    report = run_chaos_drill(
        make_engine, n_engines=n_engines, n_clients=cfg["clients"],
        hot_ms=hot_ms, phase_timeout_s=180.0, vocab=cfg["vocab"],
        min_len=max(4, cfg["seqlen"] // 8), max_len=cfg["seqlen"])
    wall = time.perf_counter() - t0
    assert report["lost"] == 0, report
    ph = report["phases"]
    _report("bert_serving_chaos_requests",
            float(report["completed"]), "requests", 0.0,
            seqlen=cfg["seqlen"], clients=cfg["clients"],
            engines=n_engines, dtype=DTYPE,
            lost=report["lost"],
            weight_min=ph["hotspot"]["weight_min"],
            hot_share=ph["hotspot"]["hot_share"],
            ttft_warm_ms=ph["seat_kill"]["ttft_ms"],
            manifest_shapes=ph["seat_kill"]["manifest_shapes"],
            adopted=ph["router_kill"]["adopted"],
            incidents=len(report["incidents"]),
            client_failovers=report["client_failovers"],
            drill_wall_s=round(wall, 1))


def main_lstm():
    """LSTM LM training step, tokens/sec/chip (BASELINE #4).

    The classic MXNet word-LM config (example/rnn/word_lm on
    WikiText-2): embed 650 → 2×LSTM(650) → tied-size decoder over a
    33k vocab; fused scan RNN op (cuDNN-RNN analog). No reference
    per-chip number (mount empty) — vs_baseline 0.0.
    """
    import jax
    import jax.numpy as jnp

    _setup_cache()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functionalize

    # batch 1024 measured fastest after the round-4 logits fixes
    # (sweep: 128→364k, 256→414k, 512→473k, 1024→520k, 2048→526k
    # tok/s — the 650-wide cell matmuls + vocab decoder fill the MXU
    # with batch; reference cuDNN word_lm used 32-80, but throughput
    # benches batch up the same way)
    batch = int(os.environ.get("BENCH_BATCH", "1024"))
    seqlen = int(os.environ.get("BENCH_SEQLEN", "35"))
    vocab, emb, hid, layers = 33278, 650, 650, 2
    ctx = mx.current_context()

    class WordLM(mx.gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = mx.gluon.nn.Embedding(vocab, emb)
                self.rnn = mx.gluon.rnn.LSTM(hid, num_layers=layers,
                                             layout="NTC")
                self.decoder = mx.gluon.nn.Dense(vocab, flatten=False)

        def hybrid_forward(self, F, x):
            seq = self.rnn(self.embed(x))
            # flatten BEFORE the 33k-vocab decoder: reshaping the small
            # (N, T, H) tensor is free, while reshaping (N, T, V) after
            # costs two 300 MB tile-repack copies (T=35 pads to 40
            # sublanes in the tiled layout) — measured 3 ms of a
            # 14.4 ms step
            return self.decoder(seq.reshape((-1, seq.shape[-1])))

    net = WordLM()
    net.initialize(init=mx.initializer.Xavier(), ctx=ctx)
    if DTYPE != "float32":
        net.cast(DTYPE)
    warm = mx.nd.zeros((2, seqlen), ctx=ctx, dtype="int32")
    with mx.autograd.predict_mode():
        net(warm)
    fn, params = functionalize(net, training=True, ctx=ctx)

    def loss_fn(params, rng, ids, labels):
        # keep logits in the model dtype (bf16): the CE kernel upcasts
        # per-tile in VMEM and emits bf16 dlogits — the f32
        # materialization of the (N*T, 33k) logits was measured at
        # ~6 ms of a 17.5 ms step (reshape/convert data movement)
        logits = fn(params, rng, ids)
        return _xent(logits.reshape(-1, vocab), labels.reshape(-1)).mean()

    step = _make_momentum_sgd(loss_fn, 1.0)
    moms = _zeros_moms(params)
    rng = jax.random.PRNGKey(0)
    npr = np.random.RandomState(0)
    ids = jnp.asarray(npr.randint(0, vocab, (batch, seqlen)), jnp.int32)
    labels = jnp.asarray(npr.randint(0, vocab, (batch, seqlen)), jnp.int32)

    flops, nbytes = _step_cost(step, params, moms, rng, ids, labels)
    dt = _time_steps(step, params, moms, rng, ids, labels,
                     flops_per_step=flops * CHAIN,
                     bytes_per_step=nbytes * CHAIN)

    tok_per_sec = batch * seqlen * STEPS * CHAIN / dt
    _report("lstm_lm_train_tokens_per_sec_per_chip", tok_per_sec,
            "tokens/sec/chip", 0.0,
            flops_per_step=flops, sec_per_step=dt / STEPS / CHAIN,
            bytes_per_step=nbytes, batch=batch, seqlen=seqlen,
            dtype=DTYPE, chain=CHAIN)


def main_widedeep():
    """Wide&Deep CTR training, examples/sec/chip (BASELINE #5).

    Criteo-shaped synthetic: 26 categorical fields + multi-hot wide
    features + 13 continuous. The sparse showcase (reference
    example/sparse/wide_deep); embedding gathers + fused MLP.
    """
    import jax
    import jax.numpy as jnp

    _setup_cache()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.gluon.model_zoo import wide_deep

    # b8192 default (r4 sweep: 2048→266k, 8192→443k, 32768→537k,
    # 131072→556k ex/s — the gather-bound step amortizes fixed cost
    # with batch; large-batch CTR training is standard industrially)
    batch = int(os.environ.get("BENCH_BATCH", "8192"))
    wide_dim, n_fields, field_dim = 100000, 26, 10000
    n_wide, n_cont = 50, 13
    ctx = mx.current_context()

    net = wide_deep(wide_dim=wide_dim, num_fields=n_fields,
                    field_dim=field_dim, embed_dim=16,
                    fused_fields=os.environ.get("BENCH_WD_FUSED", "1") == "1")
    net.initialize(init=mx.initializer.Xavier(), ctx=ctx)

    npr = np.random.RandomState(0)
    warm = (mx.nd.zeros((2, n_wide), ctx=ctx, dtype="int32"),
            mx.nd.zeros((2, n_fields), ctx=ctx, dtype="int32"),
            mx.nd.zeros((2, n_cont), ctx=ctx))
    with mx.autograd.predict_mode():
        net(*warm)
    fn, params = functionalize(net, training=True, ctx=ctx)

    def loss_fn(params, rng, wx, cx, ct, y):
        logits = fn(params, rng, wx, cx, ct).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    step = _make_momentum_sgd(loss_fn, 0.05)
    moms = _zeros_moms(params)
    rng = jax.random.PRNGKey(0)
    wx = jnp.asarray(npr.randint(0, wide_dim, (batch, n_wide)), jnp.int32)
    cx = jnp.asarray(npr.randint(0, field_dim, (batch, n_fields)), jnp.int32)
    ct = jnp.asarray(npr.rand(batch, n_cont), jnp.float32)
    y = jnp.asarray(npr.randint(0, 2, batch), jnp.int32)

    flops, nbytes = _step_cost(step, params, moms, rng, wx, cx, ct, y)
    dt = _time_steps(step, params, moms, rng, wx, cx, ct, y,
                     flops_per_step=flops * CHAIN,
                     bytes_per_step=nbytes * CHAIN)

    ex_per_sec = batch * STEPS * CHAIN / dt
    _report("wide_deep_train_examples_per_sec_per_chip", ex_per_sec,
            "examples/sec/chip", 0.0,
            flops_per_step=flops, sec_per_step=dt / STEPS / CHAIN,
            bytes_per_step=nbytes, batch=batch, dtype=DTYPE,
            chain=CHAIN)


# The five BASELINE acceptance configs (+ long-seq/padded/packed BERT
# and predict-mode inference), each run in its OWN subprocess, one
# after another: a leg has the chip, the device memory and the
# process-wide telemetry registries to itself. A chip belongs to one
# process at a time, so the parent (main_suite) must never touch JAX.
#
# ORDER IS PRIORITY (r4 lesson: the driver's wall-clock budget truncated
# the suite and the ResNet-50 TRAIN headline — scheduled last — was lost
# from the round's record). The headline runs FIRST so it is always
# captured; its JSON line is RE-EMITTED as the very last stdout line so
# the driver's parsed-last-line headline stays the north-star metric,
# preceded by a bench_suite_summary line carrying EVERY config's result.
# Long-tail extras run with a single timing window (BENCH_WINDOWS=1).
_SUITE = (
    # headline; BENCH_XPROF sources its hbm_frac from hardware counters
    # (~15 s) so the north-star line is measured, not cost-modeled
    ("resnet50_train", "resnet50", {"BENCH_XPROF": "1"}),
    ("bert_seq128", "bert", {}),
    ("lstm", "lstm", {}),
    # chain=16 measured fastest for the gather-bound step (625.7k vs
    # 618.1k ex/s at chain=10; r5 A/B)
    ("widedeep", "widedeep", {"BENCH_CHAIN": "16"}),
    ("resnet50_infer", "resnet50", {"BENCH_INFER": "1"}),
    ("bert_seq512", "bert", {"BENCH_SEQLEN": "512", "BENCH_BATCH": "64",
                             "BENCH_WINDOWS": "1"}),
    ("bert_seq512_padded", "bert",
     {"BENCH_SEQLEN": "512", "BENCH_BATCH": "64", "BENCH_PADDED": "1",
      "BENCH_WINDOWS": "1"}),
    # packed leg: same U[S/2, S] length distribution as the padded leg,
    # first-fit into 2048-slot rows; 256x256 flash tiles so the
    # segment-range block skip actually drops cross-sequence tiles
    # (at the default 512x2048 tiling every pair shares a segment)
    ("bert_seq512_packed", "bert",
     {"BENCH_SEQLEN": "512", "BENCH_BATCH": "64", "BENCH_PACKED": "1",
      "BENCH_WINDOWS": "1", "MXNET_TPU_FLASH_BLOCK_Q": "256",
      "MXNET_TPU_FLASH_BLOCK_K": "256"}),
    # packed CAUSAL LM (ROADMAP follow-up): the kernel's causal segment
    # path under a real training step; same tiling/length mix as the
    # packed BERT leg so the two numbers compare directly
    ("lm_seq512_packed_causal", "causal_lm",
     {"BENCH_SEQLEN": "512", "BENCH_BATCH": "64", "BENCH_WINDOWS": "1",
      "MXNET_TPU_FLASH_BLOCK_Q": "256", "MXNET_TPU_FLASH_BLOCK_K": "256"}),
    # closed-loop packed continuous-batching serving (mxnet_tpu/serving)
    ("bert_serving", "serving", {"BENCH_WINDOWS": "1"}),
    # 2 engines behind the front-door router: req/s, per-engine share,
    # failover count, aggregated-/metrics reconciliation
    ("bert_serving_router", "serving_router", {"BENCH_WINDOWS": "1"}),
    # multi-tenant multi-model: 2 models × 3 WFQ tenant classes driven
    # to overload behind the router — priority p99 holds while
    # best-effort sheds, per-tenant bills reconcile, and a mid-load
    # hot-swap loses nothing and lands warm
    ("bert_serving_multitenant", "serving_multitenant",
     {"BENCH_WINDOWS": "1"}),
    # autoregressive DECODE serving: paged-KV causal LM, iteration-
    # level continuous batching, streamed tokens router-fronted —
    # tokens/s + TTFT + inter-token p50/p99 + KV occupancy + churn,
    # with the iteration-vs-static and wire-vs-JSON A/Bs inline
    ("lm_decode_serving", "decode_serving", {"BENCH_WINDOWS": "1"}),
    # rolling-restart drill: kill an engine mid-load, cold vs warm
    # (manifest-replay) time-to-first-token, zero-loss failover
    ("bert_serving_restart", "serving_restart", {"BENCH_WINDOWS": "1"}),
    # self-healing chaos drill: hot-spot weight shed + seat-kill
    # autoscaler replacement + two-router kill/adopt, zero lost
    # requests; env pins the drill-speed judging clocks
    ("bert_serving_chaos", "serving_chaos",
     {"BENCH_WINDOWS": "1", "BENCH_SERVE_CLIENTS": "6",
      "MXNET_TPU_SLO_WINDOW_SCALE": "0.01",
      "MXNET_TPU_SLO_EVAL_S": "0.2",
      "MXNET_TPU_SLO_LATENCY_MS": "700",
      "MXNET_TPU_CANARY_INTERVAL_S": "0.5"}),
    # seq2048 BEFORE seq1024 (it was the r5 rc=124 casualty) and with a
    # shorter chain/step budget: chain=4 compiles a 4-step scan instead
    # of 10 — the 420 s per-config cap was lost to trace+compile time,
    # not to the measurement itself. A DRY PRE-COMPILE leg runs first:
    # it only lowers+compiles (no execution), priming the persistent
    # cache in its own 420 s window so the measured leg starts warm
    # instead of burning its cap (the rc=124 mode) on the compile.
    ("bert_seq2048_precompile", "bert",
     {"BENCH_SEQLEN": "2048", "BENCH_BATCH": "8", "BENCH_WINDOWS": "1",
      "BENCH_CHAIN": "4", "BENCH_STEPS": "10", "BENCH_PRECOMPILE": "1"}),
    ("bert_seq2048", "bert",
     {"BENCH_SEQLEN": "2048", "BENCH_BATCH": "8", "BENCH_WINDOWS": "1",
      "BENCH_CHAIN": "4", "BENCH_STEPS": "10"}),
    ("bert_seq1024", "bert", {"BENCH_SEQLEN": "1024", "BENCH_BATCH": "32",
                              "BENCH_WINDOWS": "1"}),
    # LAST: the e2e input-pipeline diagnostic is bound by the host's
    # cores (BASELINE.md) — real model numbers outrank it under the
    # budget. 640 images (5 batches) keep the leg short incl. the JPEG
    # generation, so the budget guard no longer drops it.
    ("resnet50_pipeline", "resnet50",
     {"BENCH_DATA": "pipeline", "BENCH_WINDOWS": "1",
      "BENCH_PIPELINE_IMAGES": "640"}),
)


# summary keys worth carrying per config (compact: the driver's captured
# tail must hold the WHOLE suite in one line)
_SUMMARY_KEYS = ("metric", "value", "unit", "mfu", "hbm_frac", "hbm_est",
                 "valid_frac", "valid_tokens_per_sec", "packing_efficiency",
                 "seqlen", "batch", "failed", "causal", "clients",
                 "p50_ms", "p99_ms", "telemetry_reconciled", "telemetry",
                 "slowest_traces", "per_engine", "failover", "engines_up",
                 "ttft_cold_ms", "ttft_warm_ms", "lost", "resources",
                 "profile_top", "cost_reconciled",
                 "device_s_per_1k_tokens", "slo_compliance",
                 "weight_min", "hot_share", "manifest_shapes",
                 "adopted", "incidents", "ttft_p50_ms",
                 "inter_token_p50_ms", "inter_token_p99_ms",
                 "kv_occupancy", "churn", "stream_mismatches",
                 "static_tokens_per_sec", "iteration_speedup",
                 "tenants", "priority_p99_ms", "best_effort_shed",
                 "tenants_reconciled", "swap_ms", "post_swap_probe_ms")


def _compact(rec):
    return {k: rec[k] for k in _SUMMARY_KEYS if k in rec}


def main_suite():
    """Default `python bench.py`: emit ALL acceptance configs as JSON
    lines (VERDICT r2 #8: the record is the whole suite, not just
    ResNet). Wall-clock budget guard (BENCH_BUDGET_S, default
    1200 s): when the budget is spent, remaining configs are SKIPPED —
    recorded in the summary (no silent truncation) — instead of the
    driver's timeout killing the process mid-config. A config failure
    prints to stderr, records an explicit {"value": null, "failed":
    true} row, and the suite continues; the exit code is non-zero if
    ANY config failed.

    The LAST TWO stdout lines are the round's record (VERDICT r5 #1a):
    a `bench_suite_summary` line carrying every headline metric keyed
    by config name, then the headline config's own line re-emitted —
    or, if the headline failed, an explicit failed-headline record so
    the driver can never mistake a stray line for the north-star
    number.

    This parent imports numpy and the standard library only — never
    jax or mxnet_tpu, before or between children: a parent that has
    touched JAX holds the chip, and every leg would then fail or hang.
    """
    import subprocess

    # 1200 s + the last config's 420 s cap + headline slack keeps the
    # WHOLE process under ~30 min — the r4 driver cutoff class — even
    # cold-cache; priority ordering guarantees the core five configs
    budget = float(os.environ.get("BENCH_BUDGET_S", "1200"))
    t_start = time.perf_counter()
    headline_line = None
    results = {}
    skipped = []
    device = None

    def launch(env, timeout):
        try:
            r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, capture_output=True, text=True,
                               timeout=max(timeout, 60.0))
        except subprocess.TimeoutExpired as e:
            out = e.stdout or ""
            if isinstance(out, bytes):
                out = out.decode(errors="replace")
            err = e.stderr or ""
            if isinstance(err, bytes):
                err = err.decode(errors="replace")
            sys.stderr.write(err)  # the WHY of the timeout lives here
            if out and not out.endswith("\n"):
                out += "\n"  # a truncated JSON fragment must not glue
                # onto the next line (the driver parses the LAST line)
            sys.stdout.write(out)
            sys.stdout.flush()
            return 124, out
        sys.stderr.write(r.stderr)
        sys.stdout.write(r.stdout)
        sys.stdout.flush()
        return r.returncode, r.stdout

    for i, (name, model, extra) in enumerate(_SUITE):
        remaining = budget - (time.perf_counter() - t_start)
        if i > 0 and remaining < 90.0:
            skipped.append(name)
            continue
        env = dict(os.environ, BENCH_MODEL=model, **extra)
        # headline gets a generous slice (a cold-cache compile of the
        # whole step comes first); each extra is capped at 7 min so one
        # slow config cannot starve everything behind it of the
        # remaining budget (r5 review: seq2048 running long would kill
        # the legs after it EVERY run, not just under pressure)
        r, out = launch(env, min(remaining, 420.0) if i
                        else max(remaining, 600.0))
        if r != 0:
            print(f"# bench config {name} failed rc={r}", file=sys.stderr)
        metric_line = None
        for line in out.splitlines():
            if line.startswith('{"metric"'):
                metric_line = line
        rec = json.loads(metric_line) if metric_line and r == 0 else None
        if rec is not None:
            results[name] = _compact(rec)
            device = device or {k: rec[k] for k in (
                "platform", "device_kind", "device_count")}
        else:
            # explicit null record — a failed config must never leave
            # its slot to be filled by whatever printed last
            results[name] = {"value": None, "failed": True, "rc": r}
        if i == 0 and rec is not None:
            headline_line = metric_line

    print(json.dumps({"metric": "bench_suite_summary",
                      "value": len(results), "unit": "configs",
                      "vs_baseline": 0.0, "device": device,
                      "results": results, "skipped": skipped}))
    if headline_line:
        # duplicate of the first config's line, by design: the driver
        # parses the LAST JSON line as the round's headline
        print(headline_line)
    else:
        # headline failed: an EXPLICIT failed record as the final line
        # (ADVICE r5) — never let a stray line become the parsed
        # headline
        print(json.dumps({
            "metric": "resnet50_train_images_per_sec_per_chip",
            "value": None, "unit": "images/sec/chip", "vs_baseline": 0.0,
            "failed": True}))
    sys.stdout.flush()
    failed = sorted(n for n, r in results.items() if r.get("failed"))
    if failed:
        print(f"# bench suite: {len(failed)} config(s) failed: {failed}",
              file=sys.stderr)
    raise SystemExit(1 if failed else 0)


def _dispatch():
    _model = os.environ.get("BENCH_MODEL")
    if _model is not None:
        # every measured leg runs under the always-on sampling
        # profiler + resource sweep (MXNET_TPU_PROF=0 opts out): the
        # per-leg record then carries RSS/device-mem watermarks and
        # the top host-time frames
        from mxnet_tpu.telemetry import profiling as _profiling
        _profiling.ensure_started()
    if _model is None:
        main_suite()
    elif _model == "bert":
        main_bert()
    elif _model == "causal_lm":
        main_causal_lm()
    elif _model == "decode_serving":
        main_decode_serving()
    elif _model == "serving":
        main_serving()
    elif _model == "serving_router":
        main_serving_router()
    elif _model == "serving_multitenant":
        main_serving_multitenant()
    elif _model == "serving_restart":
        main_serving_restart()
    elif _model == "serving_chaos":
        main_serving_chaos()
    elif _model == "lstm":
        main_lstm()
    elif _model == "widedeep":
        main_widedeep()
    else:
        main()


if __name__ == "__main__":
    _dispatch()
