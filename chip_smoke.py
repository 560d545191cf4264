#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the main path once through the entry points a user calls, at
the full width of the models the repo supports (weights random, from
``--seed``), in ONE process — a chip belongs to one process, so no
phase runs in a child — and checks what comes out by the repo's own
means. Any failed check raises; nothing downgrades a phase to a warning.

    python chip_smoke.py            # one TPU chip: trainer (BERT-base,
                                    # ResNet-50), BERT serving, paged decode
    python chip_smoke.py --chips 4  # ONLY the path across chips: Gluon
                                    # data-parallel BERT-base over four
                                    # contexts vs the same steps on one,
                                    # then __graft_entry__.dryrun_multichip

It refuses to start unless ``jax.devices()[0].platform == "tpu"``.
Every line it prints is one JSON object; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and earlier lines carry, per phase, seconds to compile and per step,
losses, tokens streamed, Pallas ``tpu_custom_call`` counts and
``memory_stats()``. A time printed here is a smoke reading of one run,
not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import sys
import time
from collections import Counter

import numpy as np

VOCAB = 30522           # BERT-base WordPiece vocabulary
DECODE_VOCAB = 50257    # GPT-2 BPE vocabulary
# Plain SGD at lr 3.0 on the MEAN token loss: a 30522-way softmax over
# random labels yields tiny mean gradients, and the bf16 model reports
# its summed loss in bf16 — steps of 0.0625 nats/token at batch 64x512.
# Measured on the chip: this falls one such step per training step
# (10.5 → 10.19 in six); lr 0.05 + momentum moved one step in six.
BERT_SGD = {"learning_rate": 3.0, "momentum": 0.0}


def emit(**rec):
    print(json.dumps(rec), flush=True)


def kernel_calls(hlo_text):
    """Pallas kernels in a compiled module's text: {kernel name: count}
    over its ``tpu_custom_call`` instructions (every pallas_call of
    mxnet_tpu.ops.pallas carries a stable ``mxtpu_*`` name)."""
    calls = Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="[^"]*?(mxtpu_\w+)', line)
        calls[m.group(1) if m else "unnamed"] += 1
    return dict(calls)


def require_kernels(calls, *prefixes):
    for p in prefixes:
        if not any(k.startswith(p) for k in calls):
            raise AssertionError(
                f"no tpu_custom_call of kernel {p}* in the compiled "
                f"program — found {calls}: the jnp twin is on the path")


_COMPILES = {"installed": False, "backend_compiles": 0}


def compiles_so_far():
    """Programs this process has had to obtain so far: backend compiles
    (jax's own duration event — every one, however small) plus
    executables fetched from the persistent cache. A serving loop after
    warm-up must add nothing to either."""
    import jax

    from mxnet_tpu import compile_cache

    if not _COMPILES["installed"]:
        def on_duration(event, duration_secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES["backend_compiles"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _COMPILES["installed"] = True
    return {"backend_compiles": _COMPILES["backend_compiles"],
            "persistent_hits":
                compile_cache.events_snapshot()["persistent_hits"]}


def compiles_since(before):
    return {k: v - before[k] for k, v in compiles_so_far().items()}


def memory(devices):
    """Per-device allocator readings (bytes): in use now / peak so far."""
    out = []
    for d in devices:
        st = d.memory_stats()
        out.append({"id": d.id, "in_use": int(st["bytes_in_use"]),
                    "peak": int(st["peak_bytes_in_use"])})
    return out


def check_losses(losses, expect_first, name):
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if expect_first is not None and abs(losses[0] - expect_first) > 0.5:
        raise AssertionError(
            f"{name}: first loss {losses[0]:.3f} is not ~{expect_first:.3f} "
            "(random weights should start at the uniform-guess loss)")
    if not losses[-1] < losses[0] - 0.05:
        raise AssertionError(f"{name}: loss did not fall: {losses}")


# ---------------------------------------------------------------------------
# phase a: the Gluon trainer at BERT-base width
# ---------------------------------------------------------------------------

def build_mlm(ctx, vocab=VOCAB, max_length=512, **bert_kw):
    """BERT-base + MLM head + fused cross-entropy as ONE hybridized
    block (``bert_kw`` shrinks it for CPU rehearsals only). Returns the block; its output is the batch's
    SUMMED token loss, shape (1,)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import bert_base
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMHead

    class MLM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = bert_base(vocab_size=vocab, max_length=max_length,
                                     dropout=0.0, **bert_kw)
                self.head = BERTMLMHead(vocab, bert_kw.get("units", 768))

        def hybrid_forward(self, F, ids, token_types, labels,
                           valid_length=None, segment_ids=None,
                           positions=None):
            seq, _ = self.net(ids, token_types, valid_length, None,
                              segment_ids, positions)
            logits = self.head(seq)
            return F.softmax_cross_entropy(
                F.reshape(logits, shape=(-1, vocab)),
                F.reshape(labels, shape=(-1,)))

    model = MLM()
    model.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
    model.cast("bfloat16")
    # remat: record() keeps every residual of the forward alive until
    # backward() — 16.5 GB at batch 64 x 512, more than one v5e chip
    # holds. With it the backward recomputes the forward from its inputs.
    model.hybridize(remat=True)
    return model


def train_steps(model, trainer, batches, n_tokens):
    """One Gluon step per batch: record → backward → Trainer.step.
    ``batches`` yields per-step lists (one entry per context) of
    input tuples. Returns (mean token loss per step, seconds per step);
    each step ends on a host read of the loss, so the time is the
    step's, not the enqueue's."""
    from mxnet_tpu import autograd

    losses, secs = [], []
    for per_ctx in batches:
        t0 = time.perf_counter()
        with autograd.record():
            outs = [model(*inputs) for inputs in per_ctx]
        for o in outs:
            o.backward()
        trainer.step(n_tokens)
        total = sum(float(o.asnumpy().astype(np.float32)[0]) for o in outs)
        secs.append(time.perf_counter() - t0)
        losses.append(total / n_tokens)
    return losses, secs


def mlm_batch(rs, batch, seqlen, vocab, ctxs):
    """A fixed (ids, token_types, labels) batch split over ``ctxs``."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.utils import split_and_load

    ids = rs.randint(0, vocab, (batch, seqlen)).astype(np.int32)
    labels = rs.randint(0, vocab, (batch, seqlen)).astype(np.int32)
    tt = np.zeros((batch, seqlen), np.int32)
    parts = [split_and_load(mx.nd.array(a, ctx=ctxs[0], dtype="int32"), ctxs)
             for a in (ids, tt, labels)]
    return [tuple(p[i] for p in parts) for i in range(len(ctxs))]


def phase_bert_trainer(seed, batch=64, seqlen=512, steps=6, vocab=VOCAB,
                       packed_rows=16, packed_len=2048, ctx=None,
                       **bert_kw):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.io.packing import pack_sequences

    ctx = ctx or mx.tpu(0)
    mx.random.seed(seed)
    rs = np.random.RandomState(seed)
    t0 = time.perf_counter()
    model = build_mlm(ctx, vocab=vocab, **bert_kw)
    trainer = gluon.Trainer(model.collect_params(), "sgd", dict(BERT_SGD))
    fixed = mlm_batch(rs, batch, seqlen, vocab, [ctx])
    n_tok = batch * seqlen
    losses, secs = train_steps(model, trainer, [fixed] * steps, n_tok)
    check_losses(losses, math.log(vocab), "bert_trainer")
    emit(phase="bert_trainer", batch=batch, seqlen=seqlen, dtype="bfloat16",
         losses=[round(x, 4) for x in losses], ln_vocab=round(
             math.log(vocab), 4),
         first_step_s=round(secs[0], 2),
         step_s=[round(s, 4) for s in secs[1:]],
         build_and_steps_s=round(time.perf_counter() - t0, 2))

    # the same step, lowered once: are the kernels — not their jnp
    # twins — in the program the chip's compiler produced?
    t0 = time.perf_counter()
    fn, params = functionalize(model, training=True, ctx=ctx)
    args = [a._data for a in fixed[0]]

    def step(p, rng, *inputs):
        return jax.value_and_grad(
            lambda q: fn(q, rng, *inputs).astype(jnp.float32).sum())(p)

    compiled = jax.jit(step).lower(
        params, jax.random.PRNGKey(0), *args).compile()
    calls = kernel_calls(compiled.as_text())
    require_kernels(calls, "mxtpu_layer_norm_fwd", "mxtpu_layer_norm_bwd",
                    "mxtpu_flash_fwd", "mxtpu_flash_bwd",
                    "mxtpu_softmax_xent_fwd", "mxtpu_softmax_xent_bwd")
    emit(phase="bert_trainer_kernels", tpu_custom_call=calls,
         total=sum(calls.values()),
         lower_compile_s=round(time.perf_counter() - t0, 2))
    del compiled, fn, params

    # one step with variable valid_length (the flash kernel's per-row
    # kv-length path) ...
    t0 = time.perf_counter()
    lens = mx.nd.array(rs.randint(seqlen // 2, seqlen + 1, batch),
                       ctx=ctx, dtype="int32")
    l_pad, _ = train_steps(model, trainer, [[fixed[0] + (lens,)]], n_tok)
    if not math.isfinite(l_pad[0]):
        raise AssertionError(f"valid_length step loss {l_pad}")
    emit(phase="bert_trainer_valid_length", loss=round(l_pad[0], 4),
         seconds=round(time.perf_counter() - t0, 2))

    # ... and one on PACKED rows with segment_ids at the DEFAULT flash
    # tiles: the case the chip's compiler refused before this script
    # existed (the segment backward overran scoped VMEM)
    t0 = time.perf_counter()
    pool = [rs.randint(0, vocab, n).astype(np.int32) for n in rs.randint(
        seqlen // 2, seqlen + 1, 4 * packed_rows * packed_len // seqlen)]
    pb = pack_sequences(pool, packed_len)
    keep = np.argsort(-pb.valid_length)[:packed_rows]

    def dev(a):
        return mx.nd.array(a[keep], ctx=ctx, dtype="int32")

    packed = (dev(pb.data), dev(np.zeros_like(pb.data)),
              dev(rs.randint(0, vocab, pb.data.shape).astype(np.int32)),
              dev(pb.valid_length), dev(pb.segment_ids), dev(pb.positions))
    l_pack, _ = train_steps(model, trainer, [[packed]],
                            packed_rows * packed_len)
    if not math.isfinite(l_pack[0]):
        raise AssertionError(f"packed step loss {l_pack}")
    emit(phase="bert_trainer_packed", rows=packed_rows, row_len=packed_len,
         flash_tiles="default", loss=round(l_pack[0], 4),
         seconds=round(time.perf_counter() - t0, 2),
         memory=memory(jax.local_devices()[:1]))


# ---------------------------------------------------------------------------
# phase b: the north-star model
# ---------------------------------------------------------------------------

def phase_resnet_trainer(seed, batch=128, image=224, classes=1000, steps=4,
                         ctx=None):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    ctx = ctx or mx.tpu(0)
    mx.random.seed(seed)
    rs = np.random.RandomState(seed)
    t0 = time.perf_counter()
    net = resnet50_v1(classes=classes)
    net.initialize(init=mx.initializer.Xavier(), ctx=ctx)
    net.cast("bfloat16")
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.005, "momentum": 0.9})
    x = mx.nd.array(rs.rand(batch, 3, image, image).astype(np.float32),
                    ctx=ctx, dtype="bfloat16")
    y = mx.nd.array(rs.randint(0, classes, batch), ctx=ctx, dtype="int32")
    losses, secs = [], []
    for _ in range(steps):
        t1 = time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch)
        losses.append(float(loss.asnumpy().astype(np.float32).mean()))
        secs.append(time.perf_counter() - t1)
    check_losses(losses, None, "resnet_trainer")
    emit(phase="resnet_trainer", batch=batch, image=image, dtype="bfloat16",
         losses=[round(v, 4) for v in losses],
         first_step_s=round(secs[0], 2),
         step_s=[round(s, 4) for s in secs[1:]],
         seconds=round(time.perf_counter() - t0, 2),
         memory=memory(jax.local_devices()[:1]))


# ---------------------------------------------------------------------------
# phase c: servers answer requests
# ---------------------------------------------------------------------------

def phase_bert_serving(seed, seqlen=512, vocab=VOCAB, units=768, layers=12,
                       heads=12, max_rows=8, n_requests=12, ctx=None):
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel, bert_serving_entry
    from mxnet_tpu.serving import ServingEngine

    ctx = ctx or mx.tpu(0)
    mx.random.seed(seed)
    rs = np.random.RandomState(seed)
    net = BERTModel(vocab_size=vocab, units=units, hidden_size=4 * units,
                    num_layers=layers, num_heads=heads, max_length=seqlen,
                    dropout=0.0, attention_dropout=0.0, use_pooler=False)
    net.initialize(init=mx.initializer.Normal(0.02), ctx=ctx)
    net.cast("bfloat16")
    engine = ServingEngine(bert_serving_entry(net), ctx=ctx,
                           bucket_lens=(max(1, seqlen // 4), seqlen),
                           max_rows=max_rows, pool="mean")
    with engine:
        t0 = time.perf_counter()
        engine.warmup()
        warm_s = time.perf_counter() - t0
        warm = engine.snapshot()
        before = compiles_so_far()
        t0 = time.perf_counter()
        lens = rs.randint(max(4, seqlen // 8), seqlen + 1, n_requests)
        futs = [engine.submit(rs.randint(0, vocab, n).astype(np.int32))
                for n in lens]
        outs = [f.result(timeout=300) for f in futs]
        serve_s = time.perf_counter() - t0
        snap = engine.snapshot()
        jax_post = compiles_since(before)
    for o in outs:
        o = np.asarray(o, np.float32)
        if o.shape != (units,) or not np.isfinite(o).all():
            raise AssertionError(f"bad pooled embedding {o.shape}")
    c, c0 = snap["counters"], warm["counters"]
    if c["completed"] - c0["completed"] < n_requests \
            or c["failed"] or c["expired"]:
        raise AssertionError(f"requests lost: {c}")
    in_flight = c["submitted"] - sum(
        c[k] for k in ("completed", "failed", "rejected_queue_full",
                       "rejected_too_long", "rejected_stopped",
                       "rejected_unknown_model", "expired", "cancelled"))
    if in_flight != 0:
        raise AssertionError(f"engine counters do not reconcile: {c}")
    post = {k: snap["compile_cache"][k] - warm["compile_cache"][k]
            for k in ("miss", "persistent_hit")}
    if any(post.values()) or any(jax_post.values()):
        raise AssertionError(
            f"compiles after warm-up: engine {post}, jax {jax_post}")
    emit(phase="bert_serving", requests=n_requests,
         counters=c,
         warmup_s=round(warm_s, 2), warmup_shapes=warm["manifest_shapes"],
         serve_s=round(serve_s, 3), post_warmup_compiles=post,
         post_warmup_jax=jax_post,
         compile_cache=snap["compile_cache"],
         packing_efficiency=snap.get("packing_efficiency"))


def phase_decode_serving(seed, vocab=DECODE_VOCAB, units=768, layers=12,
                         heads=12, max_len=64, max_rows=2, prompt_len=32,
                         new_tokens=16, n_prompts=3):
    import jax

    from mxnet_tpu import compile_cache
    from mxnet_tpu.serving import DecodeEngine, PagedCausalLM

    rs = np.random.RandomState(seed)
    lm = PagedCausalLM(vocab=vocab, units=units, layers=layers, heads=heads,
                       max_len=max_len, seed=seed, dtype="bfloat16")
    engine = DecodeEngine(lm, prefill_bucket_lens=(prompt_len,),
                          max_rows=max_rows, max_new_tokens=new_tokens)
    prompts = [rs.randint(0, vocab, n).astype(np.int32) for n in rs.randint(
        prompt_len // 2, prompt_len + 1, n_prompts)]
    with engine:
        t0 = time.perf_counter()
        engine.warmup()
        warm_s = time.perf_counter() - t0
        warm = engine.snapshot()

        # the paged kernel must be IN the decode step the chip compiled:
        # lower the model's own jitted step (weights and pool as shapes)
        # at the widest bucket the warm-up just visited
        def shapes(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

        rows, width = max_rows, engine.pool.pages_for(max_len)
        i32, f32 = np.int32, np.float32
        compiled = lm._decode.lower(
            shapes(lm.params), shapes(engine.pool.caches),
            np.zeros(rows, i32), np.zeros(rows, i32),
            np.zeros((rows, width), i32), np.zeros(rows, f32),
            np.zeros(rows, i32), np.ones(rows, f32),
            np.zeros(rows, i32)).compile()
        calls = kernel_calls(compiled.as_text())
        require_kernels(calls, "mxtpu_paged_flash_fwd")
        before = compiles_so_far()

        def generate():
            t1 = time.perf_counter()
            futs = [engine.submit(p, max_new_tokens=new_tokens, stream=True,
                                  temperature=0.8, top_k=40, top_p=0.95,
                                  seed=seed + i)
                    for i, p in enumerate(prompts)]
            streams = [[int(part["token"]) for part in f.stream(timeout=300)]
                       for f in futs]
            for f, s in zip(futs, streams):
                # parts are advisory, the result authoritative
                if s != [int(t) for t in f.result(timeout=300)]:
                    raise AssertionError("streamed tokens != final result")
            return streams, time.perf_counter() - t1

        first, first_s = generate()
        second, second_s = generate()
        snap = engine.snapshot()
        jax_post = compiles_since(before)
    for s in first:
        if len(s) < new_tokens or not all(0 <= t < vocab for t in s):
            raise AssertionError(f"short or out-of-range stream: {s}")
    if first != second:
        raise AssertionError("seeded streams differ between two runs in "
                             f"one process:\n{first}\n{second}")
    post = {k: snap["compile_cache"][k] - warm["compile_cache"][k]
            for k in ("miss", "persistent_hit")}
    if any(post.values()) or any(jax_post.values()):
        raise AssertionError(
            f"compiles after warm-up: engine {post}, jax {jax_post}")
    emit(phase="decode_serving", prompts=n_prompts,
         tokens_streamed=[len(s) for s in first], seeded_rerun_identical=True,
         decode_step_tpu_custom_call=calls,
         warmup_s=round(warm_s, 2), warmup_shapes=warm["manifest_shapes"],
         generate_s=[round(first_s, 3), round(second_s, 3)],
         post_warmup_compiles=post, post_warmup_jax=jax_post,
         decode=snap["decode"]["tokens"], kv=snap["kv"],
         memory=memory(jax.local_devices()[:1]))


# ---------------------------------------------------------------------------
# --chips 4: the path across chips, and what it is compared with
# ---------------------------------------------------------------------------

def phase_bert_data_parallel(seed, ctxs, batch=64, seqlen=512, steps=3,
                             vocab=VOCAB, **bert_kw):
    """Gluon data parallel over ``ctxs`` (kvstore='device': the fused
    compiled all-reduce) against the same steps on ``ctxs[0]`` alone
    from the same seed."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu import kvstore as kvstore_mod
    from mxnet_tpu.parallel import comm

    n_tok = batch * seqlen
    per_key = []
    real_reduce = kvstore_mod.KVStore._reduce

    def counting_reduce(self, arrays, key=None):
        per_key.append(key)
        return real_reduce(self, arrays, key=key)

    def run(run_ctxs):
        mx.random.seed(seed)
        rs = np.random.RandomState(seed)
        model = build_mlm(run_ctxs, vocab=vocab, **bert_kw)
        trainer = gluon.Trainer(model.collect_params(), "sgd",
                                dict(BERT_SGD), kvstore="device")
        fixed = mlm_batch(rs, batch, seqlen, vocab, run_ctxs)
        losses, secs = [], []
        for _ in range(steps):
            l, s = train_steps(model, trainer, [fixed], n_tok)
            losses += l
            secs += s
            params = model.collect_params().values()
            for p in params:        # replicas bit-identical, each at home
                datas = p.list_data()
                ref = datas[0].asnumpy()
                for c, d in zip(run_ctxs, datas):
                    if d._data.device != c.jax_device:
                        raise AssertionError(
                            f"{p.name}: replica for {c} lives on "
                            f"{d._data.device}")
                    if not np.array_equal(ref, d.asnumpy()):
                        raise AssertionError(
                            f"{p.name}: replica on {c} diverged")
        return losses, secs

    kvstore_mod.KVStore._reduce = counting_reduce
    try:
        t0 = time.perf_counter()
        dp_losses, dp_secs = run(ctxs)
        dp_s = time.perf_counter() - t0
    finally:
        kvstore_mod.KVStore._reduce = real_reduce
    hlo = comm.last_hlo_text()
    if not hlo or "all-reduce" not in hlo:
        raise AssertionError("Trainer.step did not reduce through the "
                             "compiled all-reduce")
    if per_key:
        raise AssertionError(
            f"the fused pushpull was declined: {len(per_key)} per-key "
            "reduces (the eager add-tree path) ran")
    mem = memory([c.jax_device for c in ctxs])
    if len({m["id"] for m in mem}) != len(ctxs) \
            or any(m["peak"] < (1 << 20) for m in mem):
        raise AssertionError(f"a replica's device holds nothing: {mem}")
    gc.collect()

    t0 = time.perf_counter()
    one_losses, one_secs = run(ctxs[:1])
    one_s = time.perf_counter() - t0
    check_losses(dp_losses, math.log(vocab), "bert_data_parallel")
    worst = max(abs(a - b) for a, b in zip(dp_losses, one_losses))
    if worst > 0.05:
        raise AssertionError(
            f"{len(ctxs)}-chip and 1-chip losses disagree by {worst}: "
            f"{dp_losses} vs {one_losses}")
    emit(phase="bert_data_parallel", chips=len(ctxs), batch=batch,
         seqlen=seqlen, losses=[round(x, 4) for x in dp_losses],
         one_chip_losses=[round(x, 4) for x in one_losses],
         max_loss_gap=round(worst, 5), all_reduce_in_hlo=True,
         per_key_reduces=0, replicas_identical=True,
         step_s=[round(s, 3) for s in dp_secs],
         one_chip_step_s=[round(s, 3) for s in one_secs],
         seconds=[round(dp_s, 2), round(one_s, 2)], memory=mem)


def phase_dryrun_multichip(n):
    """__graft_entry__.dryrun_multichip on the REAL devices: dp×tp step,
    Gluon DP, ring attention, sharded embedding, ZeRO-1 and the
    composite step, each against its own golden."""
    import __graft_entry__ as entry

    os.environ["MXNET_TPU_DRYRUN_REAL"] = "1"
    t0 = time.perf_counter()
    entry.dryrun_multichip(n)
    emit(phase="dryrun_multichip", devices=n,
         seconds=round(time.perf_counter() - t0, 2))


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): every one-chip phase. 4: only the "
                         "path across four chips and its one-chip "
                         "comparison.")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax.devices()[0] is "
                 f"{devs[0].platform}:{devs[0].device_kind}")
    if len(devs) < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} TPU devices; "
                 f"this process sees {len(devs)}")

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache

    t_start = time.perf_counter()
    cache = compile_cache.configure()
    if not cache["configured"]:
        sys.exit("the persistent compile cache is gated off "
                 "(MXNET_TPU_COMPILE_CACHE=0)")
    n_cached = len(os.listdir(cache["dir"])) \
        if os.path.isdir(cache["dir"]) else 0
    emit(phase="setup", platform=devs[0].platform,
         device_kind=devs[0].device_kind, devices=len(devs),
         chips_used=args.chips, jax=jax.__version__, seed=args.seed,
         compile_cache_dir=cache["dir"], cache_entries_at_start=n_cached,
         **compile_cache.events_snapshot())

    if args.chips == 1:
        phases = (phase_bert_trainer, phase_resnet_trainer,
                  phase_bert_serving, phase_decode_serving)
        for phase in phases:
            phase(args.seed)
            gc.collect()
    else:
        phase_bert_data_parallel(
            args.seed, [mx.tpu(i) for i in range(args.chips)])
        gc.collect()
        phase_dryrun_multichip(args.chips)

    emit(phase="done", seconds=round(time.perf_counter() - t_start, 2),
         compile_cache_dir=cache["dir"],
         cache_entries_at_end=len(os.listdir(cache["dir"])),
         **compile_cache.events_snapshot())
    emit(ok=True, device={"platform": devs[0].platform,
                          "kind": devs[0].device_kind,
                          "count": args.chips})


if __name__ == "__main__":
    main()
