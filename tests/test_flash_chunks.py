"""The flash kernel on the chunks its static masks leave live, in interpret
mode on the CPU.

A kv tile that the call's static masks (causal, block diffusion) leave wholly
live runs its whole width with no static mask built; a partly live one runs
only its chunks of ``block_q`` columns from the first that holds a live pair
to the last. Tiles of 128 query rows x 512 keys here, so that a tile has four
chunks: every
arm (the forward, the fused backward, the split dq / dkv pair), causal with
and without a ``q_offset``, block diffusion at blocks of 1, 4 and 48 (a block
that straddles chunks), grouped key/value heads, causal with a pair mask whose
rows see no key in some chunks, against a dense softmax over the same mask and
against the same call forced to whole tiles (``_plan_tiles`` giving no plan:
the one masked body the kernel traced before chunks, on the same walk). The tallies
``flash_chunks`` / ``flash_chunks_run`` at the cells' shapes.

Tolerances: float32 inputs, so the kernels' dots run at ``HIGHEST``; what is
left is summation order: 1e-6 of the largest value against the whole-tile
call (the same products, the dead chunks' zeros left out), 1e-5 against the
dense softmax."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx

flash_mod = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
F32 = jnp.float32
TILES = (128, 512)


def _close(got, want, tol):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) / scale < tol


def _dense(q, k, v, mask, scale):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _striped(sq, skv, q_offset):
    """Causal pairs where a query's 64-row band and a key's 128-column chunk
    agree in parity, and each query's own key: every chunk of a diagonal
    tile is dead for half the rows that see the chunk before it."""
    t, s = np.arange(sq)[:, None] + q_offset, np.arange(skv)[None, :]
    return (s <= t) & (((t // 64) % 2 == (s // 128) % 2) | (s == t))


# case: (kind, q_offset or diffusion block); the arm sets the length: 1,024
# keys are two tiles (the fused backward), 2,048 four (the dq / dkv pair).
# Causal and block diffusion run in both arms, the pair mask in the 8k
# cells' (dq / dkv)
_CASES = {"causal": ("causal", 0), "causal_offset": ("causal", 200),
          "bdiff_1": ("bdiff", 1), "bdiff_4": ("bdiff", 4), "bdiff_48": ("bdiff", 48),
          "causal_pair_mask": ("pairs", 0)}
_ARMS = {"fused": 1024, "split": 2048}
_RUNS = [("causal", "fused"), ("causal_offset", "split"), ("bdiff_1", "fused"),
         ("bdiff_4", "split"), ("bdiff_48", "split"), ("causal_pair_mask", "split")]


def _call(kind, arg, scale, pair=None):
    if kind == "bdiff":
        return lambda q, k, v: flash_mod.flash_attention_block_diffusion(
            q, k, v, arg, scale, True)
    return lambda q, k, v: flash_mod.flash_attention(
        q, k, v, scale, True, arg if kind == "causal" else 0, True, None, None, None,
        pair)


@pytest.mark.parametrize("case,arm", _RUNS)
def test_chunks_are_the_dense_softmax_and_the_whole_tile_call(monkeypatch, case, arm):
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", TILES[0])
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", TILES[1])
    kind, arg = _CASES[case]
    skv = _ARMS[arm]
    sq = skv - arg if kind == "causal" else skv
    if kind == "bdiff":
        mask = np.asarray(flash_mod.block_diffusion_mask(skv, arg))
    elif kind == "causal":
        mask = np.arange(skv)[None, :] <= np.arange(sq)[:, None] + arg
    else:
        mask = _striped(sq, skv, 0)
    pair = jnp.asarray(mask, jnp.int8)[None] if kind == "pairs" else None
    key = jax.random.PRNGKey(len(case) + skv)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), s, F32) for i, s in
                  enumerate([(1, 2, sq, 128), (1, 1, skv, 128), (1, 1, skv, 128),
                             (1, 2, sq, 128)]))
    q, k = q * 0.3, k * 0.3
    scale = 128 ** -0.5
    call = _call(kind, arg, scale, pair)

    def both(call):
        """The output and the three gradients, as one compiled program."""
        return jax.jit(lambda q, k, v: (call(q, k, v), jax.grad(
            lambda *a: (call(*a) * w).sum(), (0, 1, 2))(q, k, v)))(q, k, v)

    plan_tiles = flash_mod._plan_tiles
    plans = []
    monkeypatch.setattr(flash_mod, "_plan_tiles",
                        lambda *a, **kw: plans.append(plan_tiles(*a, **kw)) or plans[-1])
    chunked = both(call)
    # the forwards and the backward all split their tiles into 128-wide chunks
    assert len(plans) == 3 and all(p is not None and p[1] == TILES[0]
                                   for p, _ in plans)
    # the same walk, no plan
    monkeypatch.setattr(flash_mod, "_plan_tiles",
                        lambda *a, **kw: (None, plan_tiles(*a, **kw)[1]))
    _close(chunked, both(call), 1e-6)
    _close(chunked, both(lambda q, k, v: _dense(q, k, v, mask, scale)), 1e-5)


def _chunk_share(causal, length, bdiff=None):
    """100 x flash_chunks_run / flash_chunks of one call at a cell's tiles."""
    bq, bk = flash_mod._pick_blocks(length, length)
    before = mx.profiler.counters(device=False)
    flash_mod._plan_tiles(1, causal, None, 0, length, bq, bk, -(-length // bq),
                          -(-length // bk), bdiff)
    after = mx.profiler.counters(device=False)
    grew = {n: after[n] - before[n] for n in ("flash_chunks", "flash_chunks_run")}
    return 100.0 * grew["flash_chunks_run"] / grew["flash_chunks"]


def test_the_chunks_run_at_the_cells_size_are_the_masks_count():
    """Causal at 8,192 in 512 x 2,048 tiles: 40 live tiles a head-row, 24 of
    them whole (96 chunks) and 16 on the diagonal with i % 4 + 1 live chunks
    (40): 136 of 160. Block diffusion at 2 x 8,192, blocks of 4: 288 of the
    96 live tiles' 384 chunks."""
    assert _chunk_share(True, 8192) == 85.0
    assert _chunk_share(False, 16384, (8192, 4)) == 75.0


def test_a_call_with_nothing_to_skip_keeps_its_one_body():
    """No static mask (BERT's), or a window's 512-wide tiles (one chunk a
    tile, none of them wholly live): no plan, every chunk counted run."""
    for causal, window, length in ((False, None, 512), (True, 512, 8192)):
        bq, bk = flash_mod._pick_blocks(length, length, window)
        plan, _ = flash_mod._plan_tiles(1, causal, window, 0, length, bq, bk,
                                        -(-length // bq), -(-length // bk))
        assert plan is None
    assert _chunk_share(False, 512) == 100.0


def test_a_hybridized_call_carries_the_chunk_tallies_on_its_build_span(monkeypatch):
    """A block's causal flash call at 1,024 positions (tiles of 512 x 1,024,
    two chunks; one head): the build span carries the chunks of its live
    tiles and those run, 3 of 4 for the forward."""
    from mxnet_tpu.profiler import _EVENTS

    class Causal(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, q):
            return F.flash_attention(q, q, q, causal=True)

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    block = Causal()
    block.hybridize()
    q = mx.nd.array(np.random.default_rng(0).standard_normal((1, 1, 1024, 128)),
                    dtype="float32")
    mx.profiler.set_state("run")
    try:
        block(q).wait_to_read()
    finally:
        mx.profiler.set_state("stop")
    span = [e["args"] for e in _EVENTS if e["name"] == "mxtpu/cachedop/build"][-1]
    assert flash_mod._pick_blocks(1024, 1024) == (512, 1024)
    assert (span["flash_chunks"], span["flash_chunks_run"]) == (4, 3)

