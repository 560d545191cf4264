"""The Kimi Linear blocks against their plain references, on the CPU at small
sizes: the chunked gated delta rule (op and mixer) against the token-by-token
recurrence, the MLA mixer, the expert layer that is told which experts it
holds (skewed routing included: nothing dropped), the shares test (the
partial sums of all shares add up to the uncut layer), the flash op at
unequal q.k and v widths, and the model-zoo decoder through
record / backward / Trainer.step."""
import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops.pallas import kda, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, CHIP)
    yield importlib.import_module("reference.kimi_linear")
    sys.path.remove(CHIP)


def exact(f):
    return f


CFG = dict(
    hidden_size=32, num_hidden_layers=5, vocab_size=64, rms_norm_eps=1e-5,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=2, head_dim=16, short_conv_kernel_size=4),
    num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=48,
    first_k_dense_replace=1, num_experts=8, router_experts=8,
    num_experts_per_token=2, moe_intermediate_size=16, num_shared_experts=1,
    routed_scaling_factor=2.446, moe_renormalize=True, experts_held=[0, 8],
    kda_chunk_size=16)


# ---- (a) the chunked gated delta rule against the recurrence --------------------

def _kda_inputs(seed, s, dtype=np.float32, strong=False, b=2, h=2, d=16):
    r = np.random.default_rng(seed)
    q, k, v = (r.normal(size=(b, h, s, d)) for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.abs(r.normal(size=(b, h, s, d))) * (3.0 if strong else 0.3)
    beta = r.uniform(size=(b, h, s))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(g, np.float32), jnp.asarray(beta, dtype))


@pytest.mark.parametrize("use_kernel", [False, True], ids=["scan_twin", "pallas_interpret"])
@pytest.mark.parametrize("chunk,s", [(16, 40), (32, 64), (64, 100), (64, 250), (64, 300)])
def test_chunked_kda_matches_the_token_recurrence_float32(chunk, s, use_kernel):
    """Ragged lengths at three chunk sizes; on the kernel path also two tiles
    a grid step (250 tokens: four chunks, two to a tile) and a tile of one
    chunk (300: five chunks, which no pair divides)."""
    args = _kda_inputs(chunk + s, s, strong=(s % 3 == 1))
    probe = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape), jnp.float32)

    def chunked(*a):
        return jnp.sum(probe * kda.kda_chunked(*a, chunk_size=chunk,
                                               use_kernel=use_kernel, interpret=True))

    def stepwise(*a):
        return jnp.sum(probe * kda.kda_recurrent(*a))

    out = kda.kda_chunked(*args, chunk_size=chunk, use_kernel=use_kernel, interpret=True)
    want = kda.kda_recurrent(*args)
    assert float(jnp.max(jnp.abs(out - want))) <= 1e-4
    got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(*args)
    ref_g = jax.grad(stepwise, argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v log_decay beta".split(), got, ref_g):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * max(1.0, float(jnp.max(jnp.abs(b)))), name


@pytest.mark.parametrize("use_kernel", [False, True], ids=["scan_twin", "pallas_interpret"])
def test_chunked_kda_bfloat16_within_three_percent(use_kernel):
    """bfloat16 operands, float32 decay and state: within 3% of the largest
    float32 value, output and gradients (bf16 carries 8 bits: 0.4% a product,
    compounded over the chunk's solve and the state's walk)."""
    args32 = _kda_inputs(7, 64)
    args16 = tuple(a if a.dtype == jnp.float32 and i == 3 else a.astype(jnp.bfloat16)
                   for i, a in enumerate(args32))
    probe = jnp.asarray(np.random.default_rng(2).normal(size=args32[2].shape), jnp.float32)

    def loss(fn, *a):
        return jnp.sum(probe * fn(*a).astype(jnp.float32))

    chunked = lambda *a: kda.kda_chunked(*a, chunk_size=32, use_kernel=use_kernel,
                                         interpret=True)
    want = kda.kda_recurrent(*args32)
    out = chunked(*args16).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(out - want))) <= 0.03 * float(jnp.max(jnp.abs(want)))
    got = jax.grad(lambda *a: loss(chunked, *a), argnums=(0, 1, 2, 3, 4))(*args16)
    ref_g = jax.grad(lambda *a: loss(kda.kda_recurrent, *a), argnums=(0, 1, 2, 3, 4))(*args32)
    for a, b in zip(got, ref_g):
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) \
            <= 0.03 * float(jnp.max(jnp.abs(b)))


def test_kda_chunk_size_must_be_a_power_of_two():
    with pytest.raises(ValueError):
        kda.kda_chunked(*_kda_inputs(0, 48), chunk_size=24)


# ---- blocks against the reference's functions ------------------------------------

def _block_against(block, prefix, ref_fn, x_np, tol=1e-4):
    """Output, input gradient and every parameter's gradient of ``block``
    against ``ref_fn(weights, x)`` (weights named below ``prefix``)."""
    block.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
    x = mx.nd.array(x_np)
    x.attach_grad()
    block(x)                                    # finishes deferred shapes
    r = np.random.default_rng(3)
    for name, p in block.collect_params().items():
        if p.grad_req != "null" or "running_bias" in name:
            p.set_data(mx.nd.array(r.normal(size=p.shape).astype(np.float32) * 0.3))
    probe_np = r.normal(size=block(x).shape).astype(np.float32)
    with autograd.record():
        y = block(x)
        loss = (y * mx.nd.array(probe_np)).sum()
    loss.backward()
    params = block.collect_params()
    w = {name[len(block.prefix):] if prefix is None else prefix + name[len(block.prefix):]:
         jnp.asarray(p.data().asnumpy()) for name, p in params.items()}

    def ref_loss(w_, x_):
        return jnp.sum(ref_fn(w_, x_) * probe_np)

    want = ref_fn(w, jnp.asarray(x_np))
    with jax.default_matmul_precision("highest"):
        gw, gx = jax.grad(ref_loss, argnums=(0, 1))(w, jnp.asarray(x_np))
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    assert np.max(np.abs(y.asnumpy() - np.asarray(want))) <= tol * scale
    assert np.max(np.abs(x.grad.asnumpy() - np.asarray(gx))) \
        <= tol * max(1.0, float(jnp.max(jnp.abs(gx))))
    for name, p in params.items():
        if p.grad_req == "null":
            continue
        key = (name[len(block.prefix):] if prefix is None
               else prefix + name[len(block.prefix):])
        g = np.asarray(gw[key])
        assert np.max(np.abs(p.grad().asnumpy() - g)) <= tol * max(1.0, np.max(np.abs(g))), name
    return y.asnumpy()


@pytest.mark.parametrize("s", [32, 40])
def test_kda_mixer_matches_the_reference(ref, s):
    lc = CFG["linear_attn_config"]
    block = nn.KDAMixer(CFG["hidden_size"], lc["num_heads"], lc["head_dim"],
                        lc["short_conv_kernel_size"], chunk_size=16, prefix="kda_")
    x = np.random.default_rng(0).normal(size=(2, s, CFG["hidden_size"])).astype(np.float32)
    _block_against(block, "kda_", lambda w, x_: ref._kda(CFG, exact, w, "kda_", x_), x)


def test_mla_mixer_matches_the_reference(ref):
    block = nn.MLAMixer(CFG["hidden_size"], CFG["num_attention_heads"],
                        CFG["kv_lora_rank"], CFG["qk_nope_head_dim"],
                        CFG["qk_rope_head_dim"], CFG["v_head_dim"], prefix="mla_")
    x = np.random.default_rng(1).normal(size=(2, 24, CFG["hidden_size"])).astype(np.float32)
    _block_against(block, "mla_", lambda w, x_: ref._mla(CFG, exact, w, "mla_", x_), x)


def _expert_layer(held, prefix="moe_"):
    return nn.HeldExperts(
        CFG["hidden_size"], CFG["moe_intermediate_size"], CFG["router_experts"],
        CFG["num_experts_per_token"], experts_held=held,
        routed_scaling_factor=CFG["routed_scaling_factor"], prefix=prefix)


def test_expert_layer_matches_the_reference(ref):
    cfg = {**CFG, "experts_held": [2, 6]}
    block = _expert_layer((2, 6), prefix="moe26_")
    x = np.random.default_rng(2).normal(size=(2, 40, CFG["hidden_size"])).astype(np.float32)
    _block_against(block, "moe_", lambda w, x_: ref._experts(cfg, exact, w, "moe_", x_), x)
    seen = mx.profiler.counters()
    assert seen["moe_dropped"] == 0.0
    assert sum(seen["moe_slots/" + block.name]) > 0


@pytest.mark.parametrize("crowded", [False, True], ids=["sorted_rows", "dense_branch"])
def test_experts_held_takes_either_branch(crowded):
    """The row buffer holds as many rows as tokens (+ a tile an expert). A
    routing that sends every token to two held experts asks for twice that
    and takes the dense branch; output and gradients equal a plain loop over
    the held experts on both branches, and nothing is left out."""
    t, d, f, k, lo, held, tile = 64, 16, 8, 2, 2, 4, 8
    r = np.random.default_rng(11)
    ids = (np.tile(np.array([[2, 3]]), (t, 1)) if crowded
           else np.stack([r.permutation(8)[:k] for _ in range(t)]))
    ids = jnp.asarray(ids, jnp.int32)
    tables = moe.dispatch_tables(ids, lo, held, t, tile)
    assert bool(tables["rows_needed"] > tables["row_slot"].shape[0]) == crowded
    x = jnp.asarray(r.normal(size=(t, d)), jnp.float32)
    w = jnp.asarray(r.uniform(size=(t, k)), jnp.float32)
    gate_up = jnp.asarray(r.normal(size=(held, 2 * f, d)) * 0.3, jnp.float32)
    down = jnp.asarray(r.normal(size=(held, d, f)) * 0.3, jnp.float32)

    def plain(x, w, gate_up, down):
        y = 0.0
        for e in range(held):
            h = jnp.matmul(x, gate_up[e].T, precision="highest")
            out = jnp.matmul(jax.nn.silu(h[:, :f]) * h[:, f:], down[e].T, precision="highest")
            y = y + out * jnp.sum(jnp.where(ids == lo + e, w, 0.0), axis=1)[:, None]
        return y

    def program(x, w, gate_up, down):
        y, counts, unplaced = moe.experts_held(x, ids, w, gate_up, down, lo, tile=tile)
        return y, (counts, unplaced)

    y, (counts, unplaced) = program(x, w, gate_up, down)
    want = plain(x, w, gate_up, down)
    assert np.max(np.abs(np.asarray(y - want))) <= 1e-4 * max(1.0, float(jnp.max(jnp.abs(want))))
    assert int(unplaced) == 0
    assert int(jnp.sum(counts)) == int(jnp.sum((ids >= lo) & (ids < lo + held)))
    got = jax.grad(lambda *a: jnp.sum(program(*a)[0] ** 2), argnums=(0, 1, 2, 3))(x, w, gate_up, down)
    ref_g = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2, 3))(x, w, gate_up, down)
    for a, b in zip(got, ref_g):
        assert np.max(np.abs(np.asarray(a - b))) <= 1e-4 * max(1.0, float(jnp.max(jnp.abs(b))))


def test_skewed_routing_drops_nothing(ref):
    """A bias that sends most slots to one held expert and none to another:
    the layer still equals the reference, and its tally says so."""
    cfg = {**CFG, "experts_held": [0, 4]}
    block = _expert_layer((0, 4), prefix="skewed_")
    block.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
    bias = np.zeros(8, np.float32)
    bias[1], bias[2] = 10.0, -10.0           # everyone picks 1, nobody picks 2
    block.router_running_bias.set_data(mx.nd.array(bias))
    x_np = np.random.default_rng(4).normal(size=(2, 64, CFG["hidden_size"])).astype(np.float32)
    before = mx.profiler.counters().get("moe_slots/" + block.name, [0.0] * 4)
    y = block(mx.nd.array(x_np)).asnumpy()
    w = {"moe_" + n[len(block.prefix):]: jnp.asarray(p.data().asnumpy())
         for n, p in block.collect_params().items()}
    want = np.asarray(ref._experts(cfg, exact, w, "moe_", jnp.asarray(x_np)))
    assert np.max(np.abs(y - want)) <= 1e-4 * max(1.0, np.max(np.abs(want)))
    seen = mx.profiler.counters()
    load = [a - b for a, b in zip(seen["moe_slots/" + block.name], before)]
    assert load[1] == 128 and load[2] == 0 and seen["moe_dropped"] == 0.0


def test_dispatch_tables_place_every_held_slot_once():
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 16, (50, 3)), jnp.int32)
    t = moe.dispatch_tables(ids, lo=4, n_held=4, capacity_rows=150, tile=8)
    held = np.flatnonzero((np.asarray(ids).reshape(-1) >= 4) & (np.asarray(ids).reshape(-1) < 8))
    placed = np.asarray(t["row_slot"])[np.asarray(t["row_valid"])]
    assert sorted(placed.tolist()) == held.tolist()
    experts = np.repeat(np.asarray(t["tile_expert"]), 8)[np.asarray(t["row_valid"])]
    assert (np.asarray(ids).reshape(-1)[placed] - 4 == experts).all()


def _equations(jaxpr):
    """Every equation of a jaxpr, through its sub-jaxprs (remat, custom
    derivatives, loops) but not into a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_kernel_path_keeps_the_chunk_terms_in_the_kernels():
    """On the chip's path everything inside a chunk is built in VMEM: outside
    the kernels the gradient's program holds no array larger than the keys
    (the pairwise-decay operand, four times their size, is gone), no loop
    over head groups, and under the layer's remat the chunk terms are built
    twice (forward, remat's forward) and rebuilt once inside their backward
    kernel, which reads the saved inverse: not three XLA builds and a
    differentiated fourth."""
    r = np.random.default_rng(5)
    shape = (1, 16, 256, 16)
    q, k, v, g = (jnp.asarray(r.normal(size=shape), jnp.float32) for _ in range(4))
    beta = jnp.asarray(r.uniform(size=shape[:3]), jnp.float32)

    def loss(*a):
        return jnp.sum(kda.kda_chunked(*a, chunk_size=64, use_kernel=True, interpret=True))

    for wrap, want in ((lambda f: f, {"mxtpu_kda_chunk_fwd": 1, "mxtpu_kda_chunk_bwd": 1,
                                      "mxtpu_kda_fwd": 1, "mxtpu_kda_bwd": 1}),
                       (jax.checkpoint, {"mxtpu_kda_chunk_fwd": 2, "mxtpu_kda_chunk_bwd": 1,
                                         "mxtpu_kda_fwd": 2, "mxtpu_kda_bwd": 1})):
        program = jax.make_jaxpr(jax.grad(wrap(loss), argnums=(0, 1, 2, 3, 4)))(
            q, k, v, -jnp.abs(g), beta)
        kernels, largest = {}, 0
        for eqn in _equations(program.jaxpr):
            name = eqn.primitive.name
            assert name not in ("while", "scan", "cond"), name
            if name == "pallas_call":
                kernels[eqn.params["name"]] = kernels.get(eqn.params["name"], 0) + 1
            elif name != "jit":     # a jitted kernel wrapper hands on what its kernel made
                largest = max([largest] + [int(np.prod(o.aval.shape)) for o in eqn.outvars])
        assert kernels == want
        assert largest <= int(np.prod(shape))


def test_the_name_scopes_reach_the_compiled_program():
    """``kda_roofline`` and ``moe_expert_roofline`` find their mechanism's device
    time by the name scope XLA keeps on each instruction: the twin's loops, every
    product, exponential and loop of the kernel path (forward and backward
    kernels, here as the interpreter unrolls them), and the expert layer's
    branch carry it."""
    r = np.random.default_rng(12)
    q, k, v, g = (jnp.asarray(r.normal(size=(1, 16, 64, 16)), jnp.float32) for _ in range(4))
    beta = jnp.asarray(r.uniform(size=(1, 16, 64)), jnp.float32)
    step = jax.jit(jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a, chunk_size=16)), (0, 1, 2)))
    loops = [l for l in step.lower(q, k, v, -jnp.abs(g), beta).compile().as_text().splitlines()
             if " while(" in l]
    assert len(loops) >= 2 and all("mxtpu_kda" in l for l in loops)
    step = jax.jit(jax.grad(lambda *a: jnp.sum(kda.kda_chunked(
        *a, chunk_size=16, use_kernel=True, interpret=True)), (0, 1, 2, 3, 4)))
    lines = step.lower(q, k, v, -jnp.abs(g), beta).compile().as_text().splitlines()
    for kind in (" while(", " dot(", " exponential("):
        found = [l for l in lines if kind in l]
        assert found and all("mxtpu_kda" in l for l in found), kind
    ids = jnp.asarray(r.integers(0, 8, (32, 2)), jnp.int32)
    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    x, w = ones((32, 16)), ones((32, 2))
    routed = jax.jit(lambda x: moe.experts_held(x, ids, w, ones((4, 16, 16)),
                                                ones((4, 16, 8)), 2, tile=8)[0])
    branch = [l for l in routed.lower(x).compile().as_text().splitlines() if " conditional(" in l]
    assert branch and all("mxtpu_moe" in l for l in branch)


# ---- (d) the shares test ------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(ref):
    """Each of four shares holds two of the eight experts and computes its
    partial sum; with the shared expert counted once, the partial results add
    up to the uncut layer's reference output."""
    whole = _expert_layer((0, 8))
    whole.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
    x_np = np.random.default_rng(6).normal(size=(2, 32, CFG["hidden_size"])).astype(np.float32)
    x = mx.nd.array(x_np)
    whole(x)
    wp = {n[len(whole.prefix):]: p.data().asnumpy() for n, p in whole.collect_params().items()}
    wp["router_running_bias"] = np.random.default_rng(7).normal(size=8).astype(np.float32) * 0.1
    shared = nn.GatedMLP(CFG["hidden_size"], CFG["moe_intermediate_size"], prefix="shared_")
    shared.initialize(ctx=mx.cpu())
    shared(x)
    shared.gate_up.weight.set_data(mx.nd.array(wp["shared_gate_up_weight"]))
    shared.down.weight.set_data(mx.nd.array(wp["shared_down_weight"]))
    shared_out = shared(x).asnumpy()
    total = np.zeros_like(shared_out)
    for lo in range(0, 8, 2):
        part = _expert_layer((lo, lo + 2))
        part.initialize(ctx=mx.cpu())
        part(x)
        for n, p in part.collect_params().items():
            key = n[len(part.prefix):]
            if key.startswith("experts_"):
                p.set_data(mx.nd.array(wp[key][lo:lo + 2]))
            elif key != "running_slots":
                p.set_data(mx.nd.array(wp[key]))
        total += part(x).asnumpy() - shared_out
    total += shared_out
    w = {"moe_" + k: jnp.asarray(v) for k, v in wp.items()}
    want = np.asarray(ref._experts({**CFG, "experts_held": [0, 8]}, exact, w, "moe_",
                                   jnp.asarray(x_np)))
    assert np.max(np.abs(total - want)) <= 1e-4 * max(1.0, np.max(np.abs(want)))


# ---- the flash op at unequal widths ---------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True], ids=["jnp_fallback", "pallas_interpret"])
def test_flash_attention_takes_a_wider_qk_than_v(monkeypatch, interpret):
    if interpret:
        monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    r = np.random.default_rng(8)
    q, k = (r.normal(size=(1, 2, 40, 192)).astype(np.float32) for _ in range(2))
    v = r.normal(size=(1, 2, 40, 128)).astype(np.float32)
    out = mx.nd.flash_attention(mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
                                causal=True).asnumpy()
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(192.0)
    s = np.where(np.tril(np.ones((40, 40), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
    assert out.shape == (1, 2, 40, 128)
    assert np.max(np.abs(out - want)) <= 2e-4


# ---- the decoder from its config, on the training path ---------------------------------

def test_kimi_linear_trains_through_record_backward_step():
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    cfg = {**CFG, "experts_held": [0, 2]}
    net = kimi_linear(cfg, prefix="kimi_")
    net.initialize(mx.init.Normal(0.02), ctx=mx.cpu())
    kinds = [type(layer.mixer).__name__ for layer in net.layers]
    assert kinds == ["KDAMixer", "KDAMixer", "KDAMixer", "MLAMixer", "KDAMixer"]
    assert type(net.layers[0].ffn).__name__ == "GatedMLP"
    assert all(type(l.ffn).__name__ == "HeldExperts" for l in net.layers[1:])
    net.remat_per_layer()
    net.hybridize()
    ids = mx.nd.array(np.random.default_rng(9).integers(0, 64, (2, 32)), dtype="int32")
    trainer = gluon.Trainer(net.collect_params(), "adamw", {"learning_rate": 3e-3})
    before = mx.profiler.counters()     # running sums of the process: compare increments
    builds = before["cachedop_builds"]
    losses = []
    for _ in range(4):
        with autograd.record():
            logits = net(ids)
            loss = mx.nd.softmax_cross_entropy(logits.reshape((-1, 64)), ids.reshape((-1,)))
        loss.backward()
        trainer.step(64)
        losses.append(float(loss.asnumpy().sum()) / 64)
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    seen = mx.profiler.counters()
    assert seen["cachedop_builds"] == builds + 1     # routing re-traces nothing
    assert seen["moe_dropped"] == before.get("moe_dropped", 0.0)
    assert seen["looped"] == before["looped"]


def test_remat_rows_changes_neither_the_gradients_nor_the_tally():
    """Each layer taking the batch one row at a time (remat_rows=1: a lax.map
    inside the checkpoint) gives the loss, gradients and expert tallies of the
    whole batch at once; the tally is kept as an increment so the rows add up."""
    from mxnet_tpu.gluon.model_zoo import kimi_linear

    cfg = {**CFG, "experts_held": [0, 4], "num_hidden_layers": 2,
           "linear_attn_config": {**CFG["linear_attn_config"], "kda_layers": [1, 2],
                                  "full_attn_layers": []}}
    ids = mx.nd.array(np.random.default_rng(11).integers(0, 64, (2, 32)), dtype="int32")
    seen = {}
    for rows in (None, 1):
        net = kimi_linear(cfg, prefix=f"rows{rows}_")
        net.initialize(mx.init.Normal(0.02), ctx=mx.cpu())
        net(ids)
        r = np.random.default_rng(12)
        for name, p in net.collect_params().items():
            if "running_slots" not in name:
                p.set_data(mx.nd.array(r.normal(size=p.shape).astype(np.float32) * 0.05))
        net.remat_per_layer(rows=rows)
        net.hybridize()
        before = net.layers[1].ffn.running_slots.data().asnumpy()
        with autograd.record():
            loss = net(ids, ids)
        loss.backward()
        grads = {n[len(net.prefix):]: p.grad().asnumpy()
                 for n, p in net.collect_params().items() if p.grad_req != "null"}
        tally = net.layers[1].ffn.running_slots.data().asnumpy() - before
        seen[rows] = (float(loss.asnumpy().sum()), grads, tally)
    (l0, g0, t0), (l1, g1, t1) = seen[None], seen[1]
    assert l1 == pytest.approx(l0, rel=1e-5)
    assert t0.sum() > 0 and (t0 == t1).all()
    for name, g in g0.items():
        assert np.max(np.abs(g1[name] - g)) <= 1e-4 * max(1.0, np.max(np.abs(g))), name


@pytest.mark.parametrize("use_kernel", [False, True], ids=["scan_twin", "pallas_interpret"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_kda_stays_exact_when_keys_repeat(chunk, use_kernel):
    """Keys that nearly repeat from token to token (what a few optimizer
    steps can do to a fresh model) make I + A far from the identity: the
    chunk's inverse by blocked forward substitution stays with the token
    recurrence, where doubling over the whole chunk was off by 1e19 and sent
    a chip run to NaN in its fourth step (PR 27). Both inverses are held to
    it, the twin's and the kernels' own (whole tiles of chunks at once), and
    so are the gradients: the kernels' backward reads the saved inverse."""
    r = np.random.default_rng(21)
    b, h, s, d = 1, 2, 128, 16
    base = r.normal(size=(1, 1, 1, d))
    q, k = (base + 0.05 * r.normal(size=(b, h, s, d)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    args = tuple(jnp.asarray(a, jnp.float32) for a in (
        q, k, r.normal(size=(b, h, s, d)), -0.01 * np.abs(r.normal(size=(b, h, s, d))),
        0.9 + 0.1 * r.uniform(size=(b, h, s))))
    probe = jnp.asarray(np.random.default_rng(3).normal(size=(b, h, s, d)), jnp.float32)

    def chunked(*a):
        return kda.kda_chunked(*a, chunk_size=chunk, use_kernel=use_kernel, interpret=True)

    want = kda.kda_recurrent(*args)
    out = chunked(*args)
    assert float(jnp.max(jnp.abs(out - want))) <= 2e-3 * float(jnp.max(jnp.abs(want)))
    got = jax.grad(lambda *a: jnp.sum(probe * chunked(*a)), argnums=(0, 1, 2, 3, 4))(*args)
    ref_g = jax.grad(lambda *a: jnp.sum(probe * kda.kda_recurrent(*a)),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v log_decay beta".split(), got, ref_g):
        assert float(jnp.max(jnp.abs(a - b))) <= 3e-3 * float(jnp.max(jnp.abs(b))), name
