"""Main-path Pallas kernels COMPILE for a TPU v5e, at real widths.

The chip's compiler is installed here and compiles for a chip that is
described, not attached: this is what interpret mode cannot check —
tile alignment, scoped-VMEM budgets, Mosaic's refusals. (It found the
packed S=2048 flash backward overrunning VMEM at the default tiles.)
A compile that passes is a compile: nothing runs, and nothing here is
a chip measurement.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every xdist worker
imports every test file. All compile cases live in THIS one file for
the same reason (a second file could land on another worker, whose
fixture would skip it).
"""
import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops.pallas import (experts_held, flash_attention, kda_chunked,
                                  layer_norm_fused, paged_flash_attention,
                                  softmax_xent_fused)

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip (the
    # next run warns and recompiles): keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# the package re-exports the function under the submodule's name
flash_mod = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")


def flash_caps(monkeypatch, block_q, block_k):
    """Other tiles than the defaults' (several blocks at a small S):
    set the module's tile caps for one test."""
    monkeypatch.setattr(flash_mod, "_BLOCK_Q_CAP", block_q)
    monkeypatch.setattr(flash_mod, "_BLOCK_K_CAP", block_k)


def _compiled_text(fn, one_chip, *shapes):
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes]
    return jax.jit(fn).lower(*avals).compile().as_text()


def _assert_kernels(text, *names):
    assert "tpu_custom_call" in text
    for name in names:
        assert name in text, f"kernel {name} not in the compiled program"


def _sum(x):
    return x.astype(jnp.float32).sum()


_FLASH = {
    # name: (q shape, causal, kv_lens, segment_ids, (block_q, block_k))
    "b64_s512": ((64, 12, 512, 64), False, False, False, None),
    "b64_s512_kv_lens": ((64, 12, 512, 64), False, True, False, None),
    "b8_s2048": ((8, 12, 2048, 64), False, False, False, None),
    "packed_s2048": ((16, 12, 2048, 64), False, False, True, None),
    "packed_s2048_causal": ((16, 12, 2048, 64), True, False, True, None),
    "packed_s2048_256x256": ((16, 12, 2048, 64), False, False, True,
                             (256, 256)),
    "packed_s2048_causal_256x256": ((16, 12, 2048, 64), True, False, True,
                                    (256, 256)),
    # one width of 256 at 8k (latent attention's call at two widths:
    # test_flash_two_widths_compile)
    "mla_s8192_d256_causal": ((1, 32, 8192, 256), True, False, False, None),
}


@pytest.mark.parametrize("case", sorted(_FLASH))
def test_flash_attention_fwd_bwd_compiles(one_chip, monkeypatch, case):
    shape, causal, use_lens, use_segs, tiles = _FLASH[case]
    if tiles:
        flash_caps(monkeypatch, *tiles)
    b, _, s, _ = shape

    def step(q, k, v, lens, segs):
        return jax.grad(lambda q, k, v: _sum(flash_attention(
            q, k, v, None, causal, 0, False,
            lens if use_lens else None, segs if use_segs else None)),
            argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(step, one_chip, (shape, BF16), (shape, BF16),
                          (shape, BF16), ((b,), jnp.int32),
                          ((b, s), jnp.int32))
    _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd")


@pytest.mark.parametrize("policy, rebuilds", [
    (None, False), ("nothing_saveable", True)])
def test_remat_keeps_flash_residuals_in_the_compiled_step(
        one_chip, policy, rebuilds):
    """Two checkpointed attention layers at the `bert_base` cell's shape,
    under the policy a block marked ``remat=True`` gets
    (`gluon/block.py` `_remat_policy`): the compiled program runs the
    forward kernel once a layer where the names are kept and again in the
    backward where nothing is (this one program lets XLA share the last
    layer's rebuild with its forward; the cell's backward is a program of
    its own), and the backward kernel once a layer either way."""
    from mxnet_tpu.gluon.block import _remat_policy

    ckpt_policy, _ = _remat_policy({"remat_policy": policy})

    def layer(w, x):
        q, k, v = (t.reshape(64, 512, 12, 64).transpose(0, 2, 1, 3)
                   for t in jnp.split(x @ w, 3, axis=-1))
        o = flash_attention(q, k, v, None, False, 0, False)
        return x + o.transpose(0, 2, 1, 3).reshape(x.shape)

    def step(w1, w2, x):
        def loss(w1, w2):
            h = x
            for w in (w1, w2):
                h = jax.checkpoint(layer, policy=ckpt_policy)(w, h)
            return _sum(h)
        return jax.grad(loss, argnums=(0, 1))(w1, w2)

    text = _compiled_text(step, one_chip, ((768, 2304), BF16),
                          ((768, 2304), BF16), ((64, 512, 768), BF16))
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    forward_calls = sum("mxtpu_flash_fwd" in c for c in calls)
    assert forward_calls > 2 if rebuilds else forward_calls == 2
    assert sum("mxtpu_flash_bwd_fused" in c for c in calls) == 2


def test_layer_norm_fwd_bwd_compiles(one_chip):
    def step(x, g, b):
        return jax.grad(lambda x, g, b: _sum(
            layer_norm_fused(x, g, b, 1e-12, False)),
            argnums=(0, 1, 2))(x, g, b)

    text = _compiled_text(step, one_chip, ((32768, 768), BF16),
                          ((768,), BF16), ((768,), BF16))
    _assert_kernels(text, "mxtpu_layer_norm_fwd", "mxtpu_layer_norm_bwd")


@pytest.mark.parametrize("dtype", [BF16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_softmax_xent_fwd_bwd_compiles(one_chip, dtype):
    """float32 logits reach the kernel too (`contrib/amp/lists.py` keeps
    the op in float32): a tile sized for bfloat16 alone overran the
    backward's scoped VMEM there."""
    def step(logits, labels):
        return jax.grad(lambda x: _sum(
            softmax_xent_fused(x, labels, False)))(logits)

    text = _compiled_text(step, one_chip, ((8192, 30522), dtype),
                          ((8192,), jnp.int32))
    _assert_kernels(text, "mxtpu_softmax_xent_fwd", "mxtpu_softmax_xent_bwd")


def _bert_head():
    """`benchmark/chip/models/bert_base.py`'s head and loss: the head's 3-D
    logits reshaped to 2-D by the caller."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMHead

    class MLM(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.head = BERTMLMHead(30522, 768, prefix="head_")

        def hybrid_forward(self, F, seq, labels):
            return F.softmax_cross_entropy(
                F.reshape(self.head(seq), shape=(-1, 30522)),
                F.reshape(labels, shape=(-1,)))

    return MLM()


def _kimi_head():
    from mxnet_tpu.gluon.model_zoo.lm_head import _LMHead
    return _LMHead(20480, 2304, "bfloat16", None)


def _phi_head():
    from mxnet_tpu.gluon.model_zoo.phi4_flash import _Head
    return _Head(25008, 2560, "bfloat16", None)


_HEADS = {
    # cell: (the block, its input, the vocabulary, two programs a step?)
    "bert_base": (_bert_head, (64, 512, 768), 30522, True),
    "kimi_linear_48b_a3b": (_kimi_head, (2, 2048, 2304), 20480, False),
    "phi4_mini_flash": (_phi_head, (2, 2048, 2560), 25008, False),
}


def _turned_logits(text, size):
    """The `copy` and `transpose` instructions of a compiled program whose
    result has ``size`` elements."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) == size:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("cell", sorted(_HEADS))
def test_vocabulary_head_compiles_without_a_copy_of_the_logits(
        one_chip, monkeypatch, cell):
    """The three cells' head BLOCKS with their loss, forward and backward at
    the cell's shape: no instruction turns the logits (2 GB in `bert_base`,
    three times a step before PR 32). The kernel reads them vocabulary-major,
    which XLA writes only behind a 2-D product: a head that multiplies 3-D,
    or a kernel that reads them token-major, fails here.

    Kimi's and Phi's stretch runs under `jax.checkpoint` in one program, as
    under `remat_per_layer`. BERT's is staged as `ndarray/register.invoke`
    stages a recorded CachedOp: `jax.vjp` over the jitted block, so a
    forward program that hands its residuals to a backward program; a
    residual's layout between the two is the device's choice, not the
    kernel's."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.ops.pallas import _util

    make, x_shape, vocab, two_programs = _HEADS[cell]
    block = make()
    block.initialize(init=mx.initializer.Zero())
    if two_programs:  # its Dense layers learn their widths from a call
        block(mx.nd.zeros((1, 8, x_shape[-1])), mx.nd.zeros((1, 8)))
    block.cast("bfloat16")
    fn, params = functionalize(block, training=True)
    # this process's arrays are the CPU's: steer the ops to the kernels
    monkeypatch.setattr(_util, "pallas_enabled", lambda: True)
    monkeypatch.setattr(_util, "_platforms", lambda data: {"tpu"})

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = ({k: aval(v.shape, v.dtype) for k, v in params.items()},
            aval(x_shape, BF16), aval(x_shape[:2], jnp.int32))
    key = jax.random.PRNGKey(0)

    def head(p, x, labels):
        return _sum(fn(p, key, x, labels))

    if two_programs:
        kept = {}

        def forward(p, x, labels):
            loss, pullback = jax.vjp(jax.jit(head), p, x, labels)
            residuals, kept["tree"] = jax.tree_util.tree_flatten(pullback)
            return loss, residuals

        def backward(residuals, g):
            return jax.tree_util.tree_unflatten(kept["tree"], residuals)(g)

        first = jax.jit(forward).lower(*args)
        residuals = [aval(r.shape, r.dtype) for r in first.out_info[1]]
        texts = [first.compile().as_text(),
                 jax.jit(backward).lower(residuals, aval((), jnp.float32))
                 .compile().as_text()]
    else:
        step = jax.grad(jax.checkpoint(head), argnums=(0, 1))
        texts = [jax.jit(step).lower(*args).compile().as_text()]

    _assert_kernels("".join(texts), "mxtpu_softmax_xent_fwd",
                    "mxtpu_softmax_xent_bwd")
    for text in texts:
        assert not _turned_logits(text, vocab * x_shape[0] * x_shape[1])


@pytest.mark.parametrize("sq", [1, 64], ids=["decode", "chunked_prefill"])
def test_paged_flash_attention_compiles(one_chip, sq):
    fn = functools.partial(paged_flash_attention, interpret=False)
    pool = ((2049, 12, 16, 64), BF16)
    text = _compiled_text(fn, one_chip, ((32, 12, sq, 64), BF16), pool, pool,
                          ((32, 64), jnp.int32), ((32,), jnp.int32))
    _assert_kernels(text, "mxtpu_paged_flash_fwd")


def test_kda_walk_fwd_bwd_compiles(one_chip):
    """One row of Kimi Linear's KDA at published widths: 32 heads x 128,
    8,192 tokens, chunks of 64: the chunk terms' kernels (tiles of two
    chunks, the inverse's hand-split three-pass products) and the walk's."""
    def step(q, k, v, g, beta):
        return jax.grad(lambda *a: _sum(kda_chunked(
            *a, chunk_size=64, use_kernel=True, interpret=False)),
            argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    heads = (1, 32, 8192, 128)
    text = _compiled_text(step, one_chip, (heads, BF16), (heads, BF16),
                          (heads, BF16), (heads, jnp.float32),
                          (heads[:3], BF16))
    _assert_kernels(text, "mxtpu_kda_chunk_fwd", "mxtpu_kda_chunk_bwd",
                    "mxtpu_kda_fwd", "mxtpu_kda_bwd")


def test_grouped_expert_matmul_fwd_bwd_compiles(one_chip):
    """8 held experts 1,024 wide on hidden 2,304, 8,192 tokens routed 8 of
    256: the grouped matmul, its transposed-weight twin and the weight
    gradient's kernel."""
    tokens, hidden, width, held, top_k = 8192, 2304, 1024, 8, 8

    def step(x, ids, weights, gate_up, down):
        return jax.grad(lambda x, w, gu, dn: _sum(experts_held(
            x, ids, w, gu, dn, 0, use_kernel=True, interpret=False)[0]),
            argnums=(0, 1, 2, 3))(x, weights, gate_up, down)

    text = _compiled_text(step, one_chip, ((tokens, hidden), BF16),
                          ((tokens, top_k), jnp.int32),
                          ((tokens, top_k), jnp.float32),
                          ((held, 2 * width, hidden), BF16),
                          ((held, hidden, width), BF16))
    _assert_kernels(text, "mxtpu_moe_gmm", "mxtpu_moe_tgmm")


@pytest.mark.parametrize("window", [512, None], ids=["window_512", "full"])
def test_flash_window_and_grouped_heads_compile(one_chip, window):
    """One row of differential attention's half at 8,192 tokens: 20 query
    heads read 10 key/value heads, 128 wide (q.k padded from 64), with the
    512-token window (narrowed walks) and without."""
    def step(q, k, v):
        return jax.grad(lambda q, k, v: _sum(flash_attention(
            q, k, v, 0.125, True, 0, False, None, None, window)),
            argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(step, one_chip, ((1, 20, 8192, 128), BF16),
                          ((1, 10, 8192, 128), BF16), ((1, 10, 8192, 128), BF16))
    _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd_dq",
                    "mxtpu_flash_bwd_dkv")


def test_selective_scan_fwd_bwd_compiles(one_chip):
    """One row of Phi-4-mini-flash's Mamba layers: 8,192 tokens, 5,120
    channels, 16 states."""
    from mxnet_tpu.ops.pallas import selective_scan

    rows, length, channels, states = 1, 8192, 5120, 16

    def step(u, delta, a_log, b, c, skip):
        return jax.grad(lambda *a: _sum(selective_scan(
            *a, use_kernel=True, interpret=False)), argnums=tuple(range(6)))(
            u, delta, a_log, b, c, skip)

    text = _compiled_text(step, one_chip, ((rows, length, channels), BF16),
                          ((rows, length, channels), BF16),
                          ((channels, states), BF16), ((rows, length, states), BF16),
                          ((rows, length, states), BF16), ((channels,), BF16))
    _assert_kernels(text, "mxtpu_ssm_fwd", "mxtpu_ssm_bwd")


@pytest.mark.parametrize("length", [8192, 2048], ids=["split_8192", "fused_2048"])
def test_flash_pair_mask_compiles(one_chip, length):
    """One row of Keye-VL-2.0's attention: 32 query heads read 4 key/value
    heads, 128 wide, with an int8 pair mask as an operand (1 MB tiles at 512 x
    2048) and its tile summary in SMEM; both backward arms."""
    def step(q, k, v, mask):
        return jax.grad(lambda q, k, v: _sum(flash_attention(
            q, k, v, None, True, 0, False, None, None, None, mask)),
            argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(step, one_chip, ((1, 32, length, 128), BF16),
                          ((1, 4, length, 128), BF16), ((1, 4, length, 128), BF16),
                          ((1, length, length), jnp.int8))
    if length > 4096:
        _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd_dq",
                        "mxtpu_flash_bwd_dkv")
    else:
        _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd_fused")


def test_the_indexers_selection_compiles_at_8192(one_chip):
    """The selection over one row's 8,192 x 8,192 float32 scores: the Pallas
    kernel ``mxtpu_dsa_topk``, 128 query rows a grid step, their scores in
    once (4 MB) and their ordered int32 keys in a VMEM scratch of the same
    size; up to 32 counting passes inside the kernel, each over 512-column
    chunks up to the block's last causal column; the int8 mask out once,
    straight into (B, S, S). No loop of XLA's counting fusions is left."""
    from mxnet_tpu.ops.pallas import dsa

    text = _compiled_text(
        lambda s: dsa.topk_mask(s, 2048, use_kernel=True, interpret=False),
        one_chip, ((1, 8192, 8192), jnp.float32))
    _assert_kernels(text, "mxtpu_dsa_topk")
    assert "s8[1,8192,8192]" in text
    assert not re.search(r"\bwhile\(|reduce", text)


def test_the_indexers_kernels_compile_at_8192(one_chip):
    """One row of Keye-VL-2.0's indexer (16 heads of 64, one key head) at
    8,192 tokens, 512 x 1024 float32 pair tiles resident over the heads; and
    the head mean of its attention's probabilities (32 / 4 heads of 128),
    query-row-blocked XLA."""
    from mxnet_tpu.ops.pallas import dsa

    length = 8192

    def scores(q, k, w):
        return jax.value_and_grad(lambda *a: _sum(jnp.where(
            jnp.tril(jnp.ones((length, length), bool)),
            dsa.index_scores(*a, use_kernel=True, interpret=False), 0.0)),
            argnums=(0, 1, 2))(q, k, w)

    text = _compiled_text(scores, one_chip, ((1, 16, length, 64), BF16),
                          ((1, length, 64), BF16), ((1, length, 16), BF16))
    _assert_kernels(text, "mxtpu_dsa_index_fwd", "mxtpu_dsa_index_bwd_dq",
                    "mxtpu_dsa_index_bwd_dk")
    text = _compiled_text(
        lambda q, k, lse, mask: dsa.head_mean_probs(
            q, k, lse, mask, sm_scale=128 ** -0.5),
        one_chip, ((1, 32, length, 128), BF16), ((1, 4, length, 128), BF16),
        ((1, 32, length), jnp.float32), ((1, length, length), jnp.int8))
    assert "f32[1,8192,8192]" in text


@pytest.mark.parametrize("channels, bias, dtype", [
    (4096, False, BF16), (5120, True, BF16), (4096, True, jnp.float32)],
    ids=["kimi_4096", "phi_5120_bias", "float32_4096_bias"])
def test_short_convolution_fwd_bwd_compiles(one_chip, channels, bias, dtype):
    """One row of a Kimi KDA mixer's q, k or v (4,096 channels, no bias) and
    of a Phi-4-mini-flash Mamba layer's u (5,120, bias), and a float32 row
    (its blocks are twice the bytes in VMEM): 8,192 tokens, four taps, SiLU.
    The pair reads its windows at sublane offsets K - 1 .. 1 off a tile's
    start, which interpret mode does not check."""
    from mxnet_tpu.ops.pallas import conv1d

    def step(x, w, b):
        def loss(x, w, b):
            return _sum(conv1d.causal_conv1d(x, w, b if bias else None, "silu",
                                             interpret=False))
        return jax.value_and_grad(loss, (0, 1, 2) if bias else (0, 1))(x, w, b)

    assert conv1d.tiles((1, 8192, channels), 4, dtype) == (1024, 256, 64, 512)
    text = _compiled_text(step, one_chip, ((1, 8192, channels), dtype),
                          ((channels, 4), dtype), ((channels,), dtype))
    _assert_kernels(text, "mxtpu_conv1d_fwd", "mxtpu_conv1d_bwd")


@pytest.mark.parametrize("length", [8192, 2048], ids=["split_8192", "fused_2048"])
def test_flash_two_widths_compile(one_chip, length):
    """One row of latent attention at two head widths: 32 heads, q.k 192
    padded to 256, v at its own 128, so the v, o, do and dv blocks, the
    forward's accumulator and the backward's dv sum are 128 wide; both
    backward arms."""
    def step(q, k, v):
        return jax.grad(lambda q, k, v: _sum(flash_attention(
            q, k, v, 192 ** -0.5, True, 0, False)), argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(step, one_chip, ((1, 32, length, 256), BF16),
                          ((1, 32, length, 256), BF16), ((1, 32, length, 128), BF16))
    if length > 4096:
        _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd_dq",
                        "mxtpu_flash_bwd_dkv")
    else:
        _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd_fused")
    assert f"bf16[32,{length},128]" in text


@pytest.mark.parametrize("length, block", [(16384, 4), (4096, 4), (16384, 48)],
                         ids=["split_16384", "fused_4096", "split_16384_block48"])
def test_flash_block_diffusion_compiles(one_chip, length, block):
    """One row of block-diffusion attention: a clean and a noised copy of 8,192
    (or 2,048) positions side by side, 32 query heads on 4 key/value heads of
    128, the static mask written from iotas in every arm (a block of 48 is no
    power of two: its block index is a division, not a shift)."""
    from mxnet_tpu.ops.pallas import flash_attention_block_diffusion

    def step(q, k, v):
        return jax.grad(lambda q, k, v: _sum(flash_attention_block_diffusion(
            q, k, v, block, None, False)), argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(step, one_chip, ((1, 32, length, 128), BF16),
                          ((1, 4, length, 128), BF16), ((1, 4, length, 128), BF16))
    if length > 4096:
        _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd_dq",
                        "mxtpu_flash_bwd_dkv")
    else:
        _assert_kernels(text, "mxtpu_flash_fwd", "mxtpu_flash_bwd_fused")
