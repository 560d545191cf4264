#!/usr/bin/env python3
"""The readings behind sdar_30b_a3b.train_bd_8k's limits, in ONE process:
the program against the reference on every seed (the lower readings); on the
first ``--controls`` seeds the float8 control (``--witness`` adds the
bfloat16 reference); and, on the first ``--faults`` seeds, faults planted in
the reference (``FAULTS``), each against the sound reference, which is what a
program with that fault would read:

- ``fault_sees_own_clean_block``: a noisy query sees the clean keys of its
  own block too (blk(s) <= blk(t));
- ``fault_noisy_causal``: a noisy query sees the noisy keys of its block up
  to itself only (causal inside the block, not bidirectional);
- ``fault_no_inverse_t``: a masked token's loss weighs 1, not 1/t_b;
- ``fault_half_batch``: half of the batch left out. The batch is ONE row, so
  the row's second half of loss terms is left out and the first half's
  doubled (the mean over what is kept), as ``calibrate.py``'s half batch is
  the mean over the rows kept.

One JSON line a reading, as ``calibrate.py`` prints them. Not part of a
benchmark run; ``tests/chip_benchmark/test_sdar_cell.py`` plants the same
faults at rehearsal width.

The two faults of the mask change a handful of keys among the thousands a
query sees at 8,192 tokens, which the whole-leaf norms of the comparison
average away. ``--mask-witness`` reads them where they show: ONE layer's
block-diffusion attention at the cell's shape (the configuration's
``mask_witness``), the program's kernel in bfloat16 against the reference's
mask (``reference/sdar.py``'s ``_visible``) in float32 on three stretches of
query rows, and again against each mask fault; each number is the worst
row's, held to the configuration's ``mask_witness`` limits
(``mask_witness``'s docstring).

    python benchmark/chip/calibrate_sdar_30b_a3b.py --seeds 1,2 --controls 2 \\
        [--faults 1] [--fault-names fault_no_inverse_t] [--rehearse]
    python benchmark/chip/calibrate_sdar_30b_a3b.py --seeds 1,2 --mask-witness
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

CELL = "sdar_30b_a3b.train_bd_8k"
MASK_FAULTS = ("fault_sees_own_clean_block", "fault_noisy_causal")
FAULTS = MASK_FAULTS + ("fault_no_inverse_t", "fault_half_batch")


def _sees_own_clean_block(t, s, half, block):
    import jax.numpy as jnp

    t_noisy, s_noisy = t >= half, s >= half
    bt = jnp.where(t_noisy, t - half, t) // block
    bs = jnp.where(s_noisy, s - half, s) // block
    clean = jnp.logical_and(jnp.logical_not(s_noisy), bs <= bt)
    return jnp.where(t_noisy, jnp.where(s_noisy, bs == bt, bs <= bt), clean)


def _visible_of(name):
    """The reference's mask (``name`` "program") or one with the mask fault
    ``name`` planted."""
    import jax.numpy as jnp

    real = importlib.import_module("reference.sdar")._visible

    def noisy_causal(t, s, half, block):
        own = jnp.logical_and(t >= half, s >= half)
        return jnp.logical_and(real(t, s, half, block),
                               jnp.logical_or(jnp.logical_not(own), s <= t))

    return {"program": real, "fault_sees_own_clean_block": _sees_own_clean_block,
            "fault_noisy_causal": noisy_causal}[name]


def planted(run, name):
    """The reference's readings with the fault ``name`` (of ``FAULTS``)
    planted: ``run`` is the cell's ``loops.gluon_train_lean.Run``; the
    reference module is restored after."""
    import jax.numpy as jnp

    ref_mod = importlib.import_module("reference.sdar")
    real_visible, real_weights = ref_mod._visible, ref_mod._loss_weights

    def half_batch(w):
        n = w.shape[1]
        return jnp.concatenate([2.0 * w[:, :n // 2], jnp.zeros_like(w[:, n // 2:])], 1)

    try:
        if name in MASK_FAULTS:
            ref_mod._visible = _visible_of(name)
        elif name == "fault_no_inverse_t":
            ref_mod._loss_weights = lambda w: (w > 0).astype(w.dtype)
        elif name == "fault_half_batch":
            ref_mod._loss_weights = half_batch
        else:
            raise ValueError(f"no planted fault {name!r}: {FAULTS}")
        return run.reference()
    finally:
        ref_mod._visible, ref_mod._loss_weights = real_visible, real_weights


def mask_witness(seed, half, heads, kv_heads, dim, block, rows, interpret=None):
    """{side: numbers} for one layer's block-diffusion attention over 2 *
    ``half`` positions: q (1, ``heads``, 2 half, ``dim``), k and v (1,
    ``kv_heads``, ...) drawn N(0, 1) from ``seed`` in bfloat16, and an output
    cotangent drawn on three stretches of ``rows`` query rows (the clean
    copy's first, the noisy copy's first and its middle; zero elsewhere, so
    that dk and dv are those rows' part). The program's kernel
    (``flash_attention_block_diffusion``) against the reference's attention in
    float32 over the same rows under the sound mask ("program") and under each
    of ``MASK_FAULTS``. Numbers: ``out_row_gap`` and ``dq_row_gap``, the worst
    (head, row)'s |program - reference| / |reference| of o and dq;
    ``dkv_gap``, the larger of |dk - dk_ref| / |dk_ref| and the same of dv
    over the whole arrays. A row's gap is against the larger of its
    reference's size and the median row's, as ``compare.py``'s leaves are (a
    noisy query alone in its block under a causal fault has dq = 0)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.pallas import flash_attention_block_diffusion

    n, group, f32 = 2 * half, heads // kv_heads, jnp.float32
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32), jnp.bfloat16)

    q, k, v = draw(1, heads, n, dim), draw(1, kv_heads, n, dim), draw(1, kv_heads, n, dim)
    starts = (0, half, half + half // 2)
    picked = np.zeros(n, np.float32)
    for first in starts:
        picked[first:first + rows] = 1.0
    do = draw(1, heads, n, dim) * jnp.asarray(picked, jnp.bfloat16)[:, None]

    @jax.jit
    def program(q, k, v, do):
        o, vjp = jax.vjp(lambda *a: flash_attention_block_diffusion(
            *a, block, None, interpret), q, k, v)
        return (o,) + vjp(do)

    o, dq, dk, dv = (a.astype(f32) for a in program(q, k, v, do))
    kk = jnp.repeat(k.astype(f32), group, axis=1)
    vv = jnp.repeat(v.astype(f32), group, axis=1)

    def rows_of(visible, first):
        def attend(q_rows, kk, vv):
            seen = visible(first + jnp.arange(rows)[:, None], jnp.arange(n)[None, :],
                           half, block)
            s = jnp.einsum("bhqd,bhkd->bhqk", q_rows, kk) * dim ** -0.5
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, vv)
        return jax.jit(lambda q_rows, kk, vv, do_rows: (
            lambda out, vjp: (out,) + vjp(do_rows))(*jax.vjp(attend, q_rows, kk, vv)))

    def row_gap(a, b):
        size = jnp.linalg.norm(b, axis=-1)
        gap = jnp.linalg.norm(a - b, axis=-1) / jnp.maximum(size, jnp.median(size))
        return float(jnp.max(gap))

    out = {}
    for side in ("program",) + MASK_FAULTS:
        visible = _visible_of(side)
        dk_ref = dv_ref = 0.0
        gaps = {"out_row_gap": 0.0, "dq_row_gap": 0.0}
        with jax.default_matmul_precision("highest"):
            for first in starts:
                cut = slice(first, first + rows)
                o_r, dq_r, dkk, dvv = rows_of(visible, first)(
                    q[:, :, cut].astype(f32), kk, vv, do[:, :, cut].astype(f32))
                gaps["out_row_gap"] = max(gaps["out_row_gap"], row_gap(o[:, :, cut], o_r))
                gaps["dq_row_gap"] = max(gaps["dq_row_gap"], row_gap(dq[:, :, cut], dq_r))
                dk_ref = dk_ref + dkk.reshape(1, kv_heads, group, n, dim).sum(2)
                dv_ref = dv_ref + dvv.reshape(1, kv_heads, group, n, dim).sum(2)
        gaps["dkv_gap"] = max(
            float(jnp.linalg.norm(dk - dk_ref) / jnp.linalg.norm(dk_ref)),
            float(jnp.linalg.norm(dv - dv_ref) / jnp.linalg.norm(dv_ref)))
        out[side] = gaps
    return out


def main(argv=None):
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--fault-names", default=",".join(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--mask-witness", action="store_true")
    args = ap.parse_args(argv)
    harness.place_compile_cache()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import compare
    import mxnet_tpu.compile_cache as program_cache
    from spans import Spans

    program_cache.configure()
    cell, cfg, traffic, shape = harness.find_cell(
        harness.load_benchmark(), CELL, args.rehearse)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.mask_witness:
        w = cfg["mask_witness"]
        for seed in seeds:
            t0 = time.perf_counter()
            for side, nums in mask_witness(
                    seed, shape["seq_len"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"], cfg["block_length"],
                    w["rows"], interpret=True if args.rehearse else None).items():
                ok, _ = compare.judge(nums, w["limits"])
                print(json.dumps({"workload": CELL, "seed": seed, "side": side,
                                  "mask_witness": nums, "correct": ok,
                                  "seconds": time.perf_counter() - t0}), flush=True)
        return 0
    loop = importlib.import_module(f"loops.{traffic['loop']}")
    run = loop.Run(cfg, traffic, shape, cell["chips"], seeds[0], Spans(False),
                   rehearse=args.rehearse)
    run.build()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if i:
            run.reseed(seed)
        run.check_steps()
        ref = run.reference()
        sides = {"program": lambda: run.readings}
        if i < args.controls:
            sides["control_fp8"] = lambda: run.reference(precision="fp8")
            if args.witness:
                sides["witness_bf16"] = lambda: run.reference(precision="bf16")
        if i < args.faults:
            for name in args.fault_names.split(","):
                sides[name] = lambda name=name: planted(run, name)
        for side, read in sides.items():   # each line as soon as it is read
            readings = read()
            nums, where = compare.numbers(readings, ref)
            print(json.dumps({"workload": CELL, "seed": seed, "side": side,
                              "numbers": nums, "leaf": where,
                              "losses": readings["losses"], "ref_losses": ref["losses"],
                              "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
