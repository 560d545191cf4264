"""The flash kernels' walk over the tiles their static masks leave live, in
interpret mode on the CPU.

Where a call's static masks (causal, a window, the kv tile's padding, block
diffusion) leave a tile dead, the forward, dq and dkv kernels walk a table of
the live steps instead of the (q tiles x kv tiles) rectangle (``_plan_tiles``'
walk, ``_walk_table``). Tiles of 128 query rows x 512 keys here (384 under the
window), so that every mask leaves dead steps; each mask against a dense softmax over the same mask, arm by arm: the forward's o
and lse, dq, and dk and dv. An anchor step (a kv tile no query reaches)
writes zeros. The tally ``flash_grid_steps`` at the cells' shapes and on a
build span.

Tolerances: float32 inputs, so the kernels' dots run at ``HIGHEST``; 1e-5 of
the largest value against the dense softmax, as ``tests/test_flash_chunks.py``."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
import test_flash_two_widths as two_widths
import test_sdar_blocks as sdar_blocks
from test_flash_chunks import _close, _dense, _striped

flash_mod = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
F32 = jnp.float32
TALLIES = ("flash_tiles", "flash_tiles_live", "flash_grid_steps")


def _grew(run):
    """The three tallies' increments over ``run()``."""
    before = mx.profiler.counters(device=False)
    run()
    after = mx.profiler.counters(device=False)
    return {n: after[n] - before[n] for n in TALLIES}


def test_a_walk_table_lists_each_outer_tiles_live_steps_in_order():
    """Outer tile 0 sees inner tile 0, tile 1 none (an anchor on the inner
    tile fetched before it), tile 2 inner tiles 1 and 2; with a group of two
    the live inner tiles repeat, the second time after the 3 inner tiles."""
    live = np.array([[1, 0, 0], [0, 0, 0], [0, 1, 1]], bool)
    first, last, on = flash_mod._FIRST, flash_mod._LAST, flash_mod._LIVE
    assert flash_mod._walk_table(live) == (
        (0, 1, 2, 2), (0, 0, 1, 2),
        (on | first | last, first | last, on | first, on | last))
    outer, inner, flags = flash_mod._walk_table(live, group=2)
    assert outer == (0, 0, 1, 2, 2, 2, 2)
    assert inner == (0, 3, 3, 1, 2, 4, 5)
    assert flags == (on | first, on | last, first | last, on | first, on, on,
                     on | last)


# mask: (kind, argument, queries, keys); block diffusion with two query heads
# a key/value head, the others with one
_MASKS = {"causal": ("causal", 0, 2048, 2048),
          "causal_offset": ("causal", 200, 1848, 2048),
          "window": ("window", 300, 2048, 2048),
          "bdiff_grouped": ("bdiff", 4, 2048, 2048),
          "causal_pair_mask": ("pairs", 0, 2048, 2048),
          "causal_kv_padding": ("causal", 0, 1900, 1900)}
_ARMS = ("forward", "dq", "dkv")


def _mask_of(kind, arg, sq, skv):
    if kind == "bdiff":
        return np.asarray(flash_mod.block_diffusion_mask(skv, arg))
    if kind == "pairs":
        return _striped(sq, skv, 0)
    t, s = np.arange(sq)[:, None] + (arg if kind == "causal" else 0), np.arange(skv)[None]
    mask = s <= t
    return mask & (t - s < arg) if kind == "window" else mask


def _call(kind, arg, scale, pair):
    if kind == "bdiff":
        return lambda q, k, v: flash_mod.flash_attention_block_diffusion(
            q, k, v, arg, scale, True)
    return lambda q, k, v: flash_mod.flash_attention_with_lse(
        q, k, v, scale, True, arg if kind == "causal" else 0, True, None, None,
        arg if kind == "window" else None, pair)


@pytest.mark.parametrize("arm", _ARMS)
@pytest.mark.parametrize("mask_name", sorted(_MASKS))
def test_the_walk_is_the_dense_softmax(monkeypatch, mask_name, arm):
    kind, arg, sq, skv = _MASKS[mask_name]
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", 128)
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", 512)
    mask = _mask_of(kind, arg, sq, skv)
    pair = jnp.asarray(mask, jnp.int8)[None] if kind == "pairs" else None
    heads = 2 if kind == "bdiff" else 1
    key = jax.random.PRNGKey(sq + len(mask_name))
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), s, F32) for i, s in
                  enumerate([(1, 2, sq, 128), (1, 2 // heads, skv, 128),
                             (1, 2 // heads, skv, 128), (1, 2, sq, 128)]))
    q, k = q * 0.3, k * 0.3
    scale = 128 ** -0.5
    call = _call(kind, arg, scale, pair)

    def out(q, k, v):
        o = call(q, k, v)
        return o if kind == "bdiff" else o[0]

    def lse(q, k, v):
        group = q.shape[1] // k.shape[1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, 1),
                       precision="highest") * scale
        return jax.nn.logsumexp(jnp.where(mask, s, -1e30), axis=-1)

    dense = lambda q, k, v: _dense(q, k, v, mask, scale)  # noqa: E731
    if arm == "forward":
        grew = _grew(lambda: jax.block_until_ready(call(q, k, v)))
        got = call(q, k, v)
        want = dense(q, k, v) if kind == "bdiff" else (dense(q, k, v), lse(q, k, v))
    else:
        wrt = (0,) if arm == "dq" else (1, 2)
        grads = lambda f: jax.grad(  # noqa: E731
            lambda *a: (f(*a) * w).sum(), wrt)(q, k, v)
        grew = _grew(lambda: jax.block_until_ready(grads(out)))
        got, want = grads(out), grads(dense)
    # the call walked its table of live tiles: fewer steps than the
    # rectangle, no fewer than the live tiles
    assert grew["flash_tiles_live"] <= grew["flash_grid_steps"] < grew["flash_tiles"]
    _close(got, want, 1e-5)


def test_a_kv_tile_past_every_querys_reach_gets_zero_gradients(monkeypatch):
    """512 queries over 2,048 keys, causal from key 0: kv tiles 1-3 (of 512
    keys) are past every query, so their dkv walk is one anchor step each,
    which writes dk = dv = 0 exactly."""
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", 128)
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", 512)
    key = jax.random.PRNGKey(3)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), s, F32) for i, s in
                  enumerate([(1, 1, 512, 128), (1, 1, 2048, 128),
                             (1, 1, 2048, 128), (1, 1, 512, 128)]))
    mask = np.arange(2048)[None] <= np.arange(512)[:, None]
    scale = 128 ** -0.5

    def grads(f):
        return jax.grad(lambda *a: (f(*a) * w).sum(), (0, 1, 2))(q * 0.3, k * 0.3, v)
    live = flash_mod._tile_lives((True, 0, 2048, None, None), 128, 512, 4, 4).any(-1)
    outer, inner, flags = flash_mod._walk_table(live.T)
    assert outer == (0, 0, 0, 0, 1, 2, 3)
    assert [f & flash_mod._LIVE for f in flags[4:]] == [0, 0, 0]
    got = grads(lambda q, k, v: flash_mod.flash_attention(q, k, v, scale, True, 0, True))
    _, dk, dv = got
    assert not np.any(np.asarray(dk[:, :, 512:])) and not np.any(np.asarray(dv[:, :, 512:]))
    _close(got, grads(lambda q, k, v: _dense(q, k, v, mask, scale)), 1e-5)


def _sdar_trace():
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
    jax.make_jaxpr(jax.grad(lambda q, k, v: flash_mod.flash_attention_block_diffusion(
        q, k, v, 4).astype(F32).sum(), (0, 1, 2)))(q, kv, kv)


@pytest.mark.parametrize("cell,share", [("sdar", 37.5), ("joyai", 62.5),
                                        ("phi_window", 12.109375), ("bert", 100.0)])
def test_the_grid_steps_at_the_cells_shapes_are_the_live_tiles(monkeypatch, cell, share):
    """One layer-row's call, forward and backward, traced at the cell's shape:
    SDAR's block diffusion (96 live tiles of 256 a head), JoyAI's causal
    latent attention (40 of 64) and Phi's window of 512 (31 of 256: the
    diagonal and the tiles below it) walk their live tiles alone; BERT's call
    (one tile, nothing dead) walks its rectangle."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setitem(two_widths._OP_CALLS, "joyai", sdar_blocks._MLA)
    grew = _grew(_sdar_trace if cell == "sdar"
                 else lambda: two_widths._op_jaxpr(cell, "gradient"))
    assert grew["flash_tiles"] > 0
    assert grew["flash_grid_steps"] == grew["flash_tiles_live"]
    assert 100.0 * grew["flash_grid_steps"] / grew["flash_tiles"] == share


def test_a_hybridized_call_carries_the_grid_steps_on_its_build_span(monkeypatch):
    """A block's causal flash call at 1,024 positions in tiles of 128 x 512
    (8 x 2; one head): q tiles 0-3 see no key of kv tile 1, so the forward
    walks 12 of the rectangle's 16 steps, and the build span says so."""
    from mxnet_tpu.profiler import _EVENTS

    class Causal(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, q):
            return F.flash_attention(q, q, q, causal=True)

    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", 128)
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", 512)
    block = Causal()
    block.hybridize()
    q = mx.nd.array(np.random.default_rng(1).standard_normal((1, 1, 1024, 128)),
                    dtype="float32")
    mx.profiler.set_state("run")
    try:
        block(q).wait_to_read()
    finally:
        mx.profiler.set_state("stop")
    span = [e["args"] for e in _EVENTS if e["name"] == "mxtpu/cachedop/build"][-1]
    assert (span["flash_tiles"], span["flash_tiles_live"],
            span["flash_grid_steps"]) == (16, 12, 12)
