"""Decode fast path: prefix KV cache reuse, chunked prefill through
the iteration loop, and seeded sampling.

Covers the ISSUE-16 acceptance surface:

- prefix-index COW/refcount invariants at the pool level: divergence
  exactly at a page boundary shares read-only with zero copies; a
  partial page is NEVER shared (COW-attached, never registered);
  refcount-zero recycle under register/release churn with the index
  yielding to live sequences on demand; ``check_isolated`` over owner
  SETS (shared pages at the same table index everywhere);
- write-frontier copy-on-write: ``prepare_write`` privatizes a pinned
  or multi-owner page, the source stays cached/shared;
- the shared-prefix SOLO-PARITY golden at the engine level: a prompt
  served through the warm prefix cache is byte-identical to its cold
  run, and the pool drains to zero live pages with the prefix pages
  still cached;
- chunked prefill: a long prompt admitted into a running batch is
  sliced through the iteration loop (prefill_chunks counter moves),
  short streams keep flowing, and replays stay byte-identical;
- seeded sampling: greedy default byte-stable, identical seeds give
  identical sequences, distinct seeds diverge; out-of-range sampling
  params raise the typed :class:`InvalidSamplingError` at submit on
  the engine, the router, and as HTTP 400 on ``/submit``.
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu  # noqa: F401  (configures jax for the CPU mesh)


def _mk_model(**kw):
    from mxnet_tpu.serving import PagedCausalLM

    args = dict(vocab=64, units=32, layers=2, heads=4, max_len=128,
                seed=7)
    args.update(kw)
    return PagedCausalLM(**args)


def _mk_engine(model=None, **kw):
    from mxnet_tpu.serving import DecodeEngine

    args = dict(prefill_bucket_lens=(8, 16), max_rows=4, page_size=8,
                n_pages=24, max_new_tokens=6)
    args.update(kw)
    return DecodeEngine(model if model is not None else _mk_model(),
                        **args)


def _mk_pool(engine_id, **kw):
    from mxnet_tpu.serving import PagedKVPool

    args = dict(page_size=8, n_pages=12, prefix_cache=True,
                prefix_pages=8)
    args.update(kw)
    return PagedKVPool(2, 4, 16, engine_id=engine_id, **args)


def _accounted(pool):
    """used + cached + free must cover the pool exactly, always."""
    occ = pool.occupancy()
    assert (occ["pages_used"] + occ["pages_cached"] + occ["pages_free"]
            == occ["pages_total"]), occ
    return occ


# ---------------------------------------------------------------------------
# prefix index: sharing, divergence, COW, recycle
# ---------------------------------------------------------------------------
def test_prefix_divergence_at_page_boundary_shares_readonly():
    pool = _mk_pool("px_t0")
    toks_a = np.arange(1, 21, dtype=np.int32)        # 20 tokens
    pool.ensure("a", toks_a.size)
    # only the two FULL pages index; the 4-token tail page never does
    assert pool.register_prefix("a", toks_a) == 2
    refs = pool.page_refcounts()
    tail = pool.table("a")[2]
    assert not refs[tail]["pinned"]

    # b shares page 0 byte-for-byte, diverges at EXACTLY the boundary
    toks_b = np.concatenate([toks_a[:8],
                             np.arange(50, 58)]).astype(np.int32)
    matched, copies = pool.match_prefix("b", toks_b)
    assert matched == 8 and copies == []
    shared = pool.table("a")[0]
    assert pool.table("b")[0] == shared
    assert pool.owners_of(shared) == {"a", "b"}
    # sole-owner view refuses to name a shared page
    assert pool.owner_of(shared) is None
    pool.check_isolated()
    occ = _accounted(pool)
    assert occ["pages_shared"] >= 1

    st = pool.prefix_stats()
    assert st["hits"] == 1 and st["tokens_reused"] == 8
    pool.release("a")
    pool.release("b")
    pool.check_isolated()
    assert _accounted(pool)["pages_used"] == 0


def test_prefix_partial_page_is_cowed_never_shared():
    pool = _mk_pool("px_t1")
    toks_a = np.arange(1, 17, dtype=np.int32)        # 2 full pages
    pool.ensure("a", toks_a.size)
    pool.register_prefix("a", toks_a)

    # c matches page 0 fully and the first 4 slots of page 1, then
    # diverges MID-page: the match must come back as a private copy
    toks_c = np.concatenate([toks_a[:12],
                             np.arange(60, 64)]).astype(np.int32)
    matched, copies = pool.match_prefix("c", toks_c)
    assert matched == 12
    assert len(copies) == 1
    src, dst = copies[0]
    assert src == pool.table("a")[1]
    assert dst == pool.table("c")[1] and dst != src
    # the partially-matching SOURCE page is never in c's owner set
    assert "c" not in pool.owners_of(src)
    assert pool.owners_of(dst) == {"c"}
    pool.copy_pages(copies)
    pool.check_isolated()

    # prompt-end mid-page takes the same partial-COW arm
    toks_d = toks_a[:13]
    matched_d, copies_d = pool.match_prefix("d", toks_d)
    # limit is prompt_len - 1: the last token always prefills (its
    # logits produce the first generated token)
    assert matched_d == 12 and len(copies_d) == 1
    assert copies_d[0][0] == pool.table("a")[1]
    pool.copy_pages(copies_d)
    pool.check_isolated()

    for owner in ("a", "c", "d"):
        pool.release(owner)
    assert _accounted(pool)["pages_used"] == 0


def test_prefix_refcount_zero_recycle_under_churn():
    pool = _mk_pool("px_t2", n_pages=10, prefix_pages=6)
    base = np.arange(1, 17, dtype=np.int32)
    pool.ensure("s0", base.size)
    pool.register_prefix("s0", base)

    # churn: joiners share the cached prefix, then leave in a
    # different order than they came
    joined = []
    for i in range(4):
        owner = f"j{i}"
        matched, copies = pool.match_prefix(owner, base)
        assert matched == 15          # 1 full page + 7-slot COW tail
        pool.copy_pages(copies)
        joined.append(owner)
        pool.check_isolated()
        _accounted(pool)
        # the COW tail pages exhaust the pool unless refcount-zero
        # recycle keeps returning them
        pool.release(owner)
        pool.check_isolated()
    pool.release("s0")
    occ = _accounted(pool)
    assert occ["pages_used"] == 0
    # the registered pages survive their sequences (cached, pinned)
    assert occ["pages_cached"] == 2

    # live allocation reclaims cached pages on demand: the index can
    # never starve admission
    pool.ensure("big", pool.n_pages * pool.page_size)
    occ = _accounted(pool)
    assert occ["pages_used"] == pool.n_pages
    assert occ["pages_cached"] == 0
    assert pool.prefix_stats()["evictions"] >= 2
    pool.release("big")
    occ = _accounted(pool)
    assert occ["pages_free"] == pool.n_pages
    pool.check_isolated()


def test_prepare_write_cows_frozen_pages():
    pool = _mk_pool("px_t3")
    toks = np.arange(1, 17, dtype=np.int32)
    pool.ensure("a", toks.size)
    pool.register_prefix("a", toks)

    # a pinned page at the write frontier: a's own page 1 is indexed,
    # so writing into it must first privatize it
    src_dst = pool.prepare_write("a", 8)
    assert src_dst is not None
    src, dst = src_dst
    assert pool.table("a")[1] == dst and dst != src
    pool.copy_pages([src_dst])
    # the source page survives as a cached index entry
    assert pool.page_refcounts()[src]["pinned"]
    assert pool.owners_of(src) == frozenset()
    pool.check_isolated()

    # a multi-owner page: b shares page 0; b writing into it COWs,
    # a keeps the original
    matched, copies = pool.match_prefix("b", toks)
    pool.copy_pages(copies)
    page0 = pool.table("a")[0]
    assert pool.owners_of(page0) == {"a", "b"}
    pair = pool.prepare_write("b", 0)
    assert pair is not None and pair[0] == page0
    pool.copy_pages([pair])
    assert pool.owners_of(page0) == {"a"}
    assert pool.table("b")[0] == pair[1]
    # a PRIVATE unpinned page is the no-op fast path
    assert pool.prepare_write("b", 0) is None
    pool.check_isolated()
    pool.release("a")
    pool.release("b")
    assert _accounted(pool)["pages_used"] == 0


def test_prefix_disabled_pool_is_inert():
    pool = _mk_pool("px_t4", prefix_cache=False)
    toks = np.arange(1, 17, dtype=np.int32)
    pool.ensure("a", toks.size)
    assert pool.register_prefix("a", toks) == 0
    assert pool.match_prefix("b", toks) == (0, [])
    assert pool.prefix_stats()["enabled"] is False
    pool.release("a")
    occ = _accounted(pool)
    assert occ["pages_cached"] == 0 and occ["pages_used"] == 0


# ---------------------------------------------------------------------------
# engine level: shared-prefix solo parity, chunked prefill, sampling
# ---------------------------------------------------------------------------
def test_prefix_hit_is_byte_identical_to_cold_run():
    prompt = list(range(1, 14))                      # 13 tokens
    with _mk_engine() as eng:
        cold = eng.infer(prompt, max_new_tokens=6).tolist()
        occ = eng.pool.occupancy()
        # drained: no live pages, the prompt's full page stays cached
        assert occ["pages_used"] == 0
        assert occ["pages_cached"] >= 1
        hit = eng.infer(prompt, max_new_tokens=6).tolist()
        assert hit == cold
        st = eng.pool.prefix_stats()
        assert st["hits"] >= 1
        assert st["tokens_reused"] >= 8
        eng.pool.check_isolated()
        # the scheduler-state bundle carries the index + refcounts
        state = eng.scheduler_state()
        assert state["prefix"]["hits"] >= 1
        assert isinstance(state["page_refcounts"], dict)


def test_warmup_compiles_the_cow_page_copy():
    """The copy-on-write page copy is a compiled program of the
    serving loop too: warm-up must visit it, or the first prefix hit
    on a part-shared page compiles in front of live traffic (seen on
    the chip: one compile after a "complete" warm-up)."""
    from mxnet_tpu.serving import kvcache

    with _mk_engine(n_pages=37, prefill_bucket_lens=(8,),
                    max_rows=1) as eng:     # 37: a geometry of its own
        step = kvcache._copy_step(bool(eng.pool._donate))
        before = step._cache_size()
        eng.warmup(shapes=[])               # nothing but the copy
        assert step._cache_size() == before + 1
        with eng._forward_lock:
            eng.pool.copy_pages([(0, 1)])
        assert step._cache_size() == before + 1


def test_chunked_prefill_interleaves_with_running_decode():
    import time

    with _mk_engine(prefill_bucket_lens=(8, 64), prefill_budget=8,
                    max_rows=4, n_pages=32, max_new_tokens=8) as eng:
        short = [3, 2, 1]
        f1 = eng.submit(short, max_new_tokens=8, stream=True)
        it = f1.stream(timeout=60)
        first = next(it)                  # decode is live
        assert "token" in first
        # a LONG prompt (8 budget-sized chunks) joins the running batch
        long_p = list(range(1, 61))
        f2 = eng.submit(long_p, max_new_tokens=4)
        rest = [p["token"] for p in it]
        out1 = np.asarray(f1.result(timeout=0)).tolist()
        assert [first["token"]] + rest == out1
        out2 = np.asarray(f2.result(timeout=60)).tolist()
        assert len(out2) == 4
        snap = eng.decode_stats.snapshot()
        assert snap["prefill_chunks"] >= 8
        assert snap["prefill_chunk_tokens"] >= 60
        # deadline for the stats scrape thread is irrelevant; what
        # matters is the pool drained and stayed consistent
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and eng.pool.occupancy()["pages_used"]:
            time.sleep(0.01)
        assert eng.pool.occupancy()["pages_used"] == 0
        eng.pool.check_isolated()
        # replay: same prompt through the now-warm prefix cache is
        # byte-identical (greedy)
        assert eng.infer(long_p, max_new_tokens=4).tolist() == out2
        assert eng.pool.prefix_stats()["hits"] >= 1


def test_static_mode_keeps_dense_prefill():
    with _mk_engine(iteration_level=False) as eng:
        assert eng._prefill_budget == 0
        assert eng.pool.prefix_enabled is False
        out = eng.infer([1, 2, 3, 4, 5], max_new_tokens=4)
        assert len(out) == 4
        assert eng.decode_stats.snapshot()["prefill_chunks"] == 0


def test_seeded_sampling_deterministic():
    prompt = [5, 6, 7]
    with _mk_engine(max_new_tokens=8) as eng:
        g1 = eng.infer(prompt).tolist()
        g2 = eng.infer(prompt).tolist()
        assert g1 == g2                   # greedy default, byte-stable
        kw = dict(temperature=2.0, top_k=0, top_p=1.0)
        s1 = eng.infer(prompt, seed=77, **kw).tolist()
        s2 = eng.infer(prompt, seed=77, **kw).tolist()
        assert s1 == s2                   # identical seeds, identical
        s3 = eng.infer(prompt, seed=78, **kw).tolist()
        # 8 near-uniform draws from a 64-token vocab: a collision of
        # the whole sequence would be a once-per-2^48 event
        assert s3 != s1
        # truncation composes with the seed the same way
        t1 = eng.infer(prompt, temperature=0.9, top_k=16, top_p=0.9,
                       seed=5).tolist()
        t2 = eng.infer(prompt, temperature=0.9, top_k=16, top_p=0.9,
                       seed=5).tolist()
        assert t1 == t2


def test_sampling_validation_typed_errors():
    from mxnet_tpu.serving import (InvalidSamplingError, ServingRouter,
                                   validate_sampling)

    # the validator itself: normalization + refusals
    assert validate_sampling(None, None, None, None) == (None,) * 4
    assert validate_sampling(0.0, 0, 1.0, 3) == (0.0, 0, 1.0, 3)
    for bad in ((-0.5, None, None, None),
                (float("nan"), None, None, None),
                (None, -2, None, None),
                (None, None, 0.0, None),
                (None, None, 1.5, None)):
        with pytest.raises(InvalidSamplingError):
            validate_sampling(*bad)

    with _mk_engine() as eng:
        for kw in ({"temperature": -1.0}, {"top_k": -3},
                   {"top_p": 0.0}, {"top_p": 2.0}):
            with pytest.raises(InvalidSamplingError):
                eng.submit([1, 2, 3], **kw)
        # the router refuses BEFORE journaling/dispatch, same type
        with ServingRouter(engines=[eng]) as router:
            with pytest.raises(InvalidSamplingError):
                router.submit([1, 2, 3], temperature=-1.0)
        # HTTP surface: a typed 400, not a 500 from inside a step
        srv = eng.expose()
        req = urllib.request.Request(
            srv.url("/submit"),
            data=json.dumps({"tokens": [1, 2, 3],
                             "temperature": -1.0}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
        body = json.loads(ei.value.read().decode())
        assert body["error_type"] == "InvalidSamplingError"


def test_seeded_sampling_rides_the_router_relay():
    """A seeded streamed request through a router-fronted seat: parts
    match the final result and a same-seed solo run — the dispatch
    payload carries the seed, so replay is seat-independent."""
    from mxnet_tpu.serving import ServingRouter

    kw = dict(temperature=1.5, top_k=0, top_p=1.0)
    with _mk_engine(max_new_tokens=8) as eng:
        solo = eng.infer([9, 8, 7], seed=321, **kw).tolist()
        with ServingRouter(engines=[eng]) as router:
            fut = router.submit([9, 8, 7], max_new_tokens=8,
                                stream=True, seed=321, **kw)
            parts = [p["token"] for p in fut.stream(timeout=60)]
            out = np.asarray(fut.result(timeout=0)).tolist()
        assert parts == out == solo
