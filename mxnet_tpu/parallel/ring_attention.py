"""Ring attention & all-to-all (Ulysses-style) sequence parallelism.

Long-context support the reference does not have in any form (SURVEY
§5.7: MXNet v1.x has no fused attention, no sequence/context
parallelism) — first-class here because the TPU mesh makes it natural:

- :func:`ring_attention` — the sequence axis is sharded over a mesh
  axis; K/V chunks rotate around the ring via ``lax.ppermute`` (ICI
  neighbor exchanges) while each device folds incoming chunks into an
  online-softmax accumulator. The fold is a plain jnp einsum +
  online-softmax update (NOT the Pallas flash kernel): it materializes
  one (B, H, C, C) score block per ring step, so peak memory per
  device is O(C^2) per (batch, head) — bounded by the chunk size, not
  the global sequence. ``remat=True`` (default) recomputes the score
  blocks in backward.
- :func:`ulysses_attention` — all-to-all over the mesh axis re-shards
  (B, H, S/P, D) → (B, H/P, S, D) so each device computes full-sequence
  attention for a head subset (single flash kernel call on TPU), then
  all-to-all back. Two collectives per call; cheaper than the ring when
  H ≥ P and the ICI all-to-all bandwidth is good.

Both are differentiable (ppermute/all_to_all have transposes; the ring
uses lax.scan) and are meant to be called INSIDE ``shard_map`` with the
sequence dimension sharded over ``axis_name``. The shard_map wrapper
:func:`make_ring_attention_fn` is the convenience entry the tests and
models use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention",
           "make_ring_attention_fn", "make_ulysses_attention_fn"]

_NEG_INF = -1e30


def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None,
                   axis_size=None, remat=True, use_flash=None):
    """Blockwise self-attention over a ring of sequence shards.

    Parameters
    ----------
    q, k, v : (B, H, C, D) local sequence chunks; the global sequence
        (S = C * P) is sharded over mesh axis ``axis_name`` in order.
    causal : global causal mask (chunk offsets are accounted for).
    remat : recompute score blocks in backward (flash-style memory).
    use_flash : fold chunks with the Pallas flash kernel + log-sum-exp
        combiner (O(C) per-step memory — the score block never leaves
        VMEM). ``None`` = auto: kernel when the data lives on TPU (or
        kernel-interpret mode is forced), else the pure-jnp
        online-softmax fold (which materializes one (B, H, C, C) score
        block per step and remains the CPU/debug fallback).
    """
    P_ = axis_size if axis_size is not None else lax.axis_size(axis_name)
    b, h, c, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % P_) for j in range(P_)]

    if use_flash is None:
        # inside shard_map q is a tracer, so this can only consult the
        # backend/interpret flags; make_ring_attention_fn resolves the
        # real mesh-device platform BEFORE wrapping and passes it in
        from ..ops.pallas import _util as _pu
        use_flash = _pu.pallas_ok_for(q)

    if use_flash:
        fold = functools.partial(_fold_flash, q, causal, scale, idx)
    else:
        fold = functools.partial(_fold_jnp, q.astype(jnp.float32), causal,
                                 scale, idx, c)

    def step(carry, t):
        # permute-then-compute: after t rotations this device holds
        # chunk (idx - t) mod P; exactly P-1 neighbor exchanges total
        kc, vc, m, l, acc = carry
        kc, vc = lax.ppermute((kc, vc), axis_name, perm)
        m, l, acc = fold((m, l, acc), kc, vc, (idx - t) % P_)
        return (kc, vc, m, l, acc), None

    if remat:
        fold = jax.checkpoint(fold)
        step = jax.checkpoint(step)

    m0 = jnp.full((b, h, c, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, c, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, c, d), jnp.float32)
    m, l, acc = fold((m0, l0, acc0), k, v, idx)  # own chunk, no comm
    if P_ > 1:
        (_, _, m, l, acc), _ = lax.scan(
            step, (k, v, m, l, acc), jnp.arange(1, P_))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.where(l == 0.0, 0.0, acc / l_safe)
    return out.astype(q.dtype)


def _fold_jnp(qf, causal, scale, idx, c, carry, kc, vc, src):
    """Online-softmax fold of chunk ``src`` (pure jnp: one (B,H,C,C)
    score block per step — the CPU/debug fallback)."""
    m, l, acc = carry
    row = idx * c + lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        col = src * c + lax.broadcasted_iota(jnp.int32, (c, c), 1)
        s = jnp.where(col <= row, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _fold_flash(q, causal, scale, idx, carry, kc, vc, src):
    """Fold chunk ``src`` via the Pallas flash kernel + LSE combiner.

    Per-chunk attention runs entirely in VMEM (O(C) memory); the
    (normalized out, lse) pair merges into the running accumulator with
    the log-sum-exp combiner. Gradients flow through BOTH kernel
    outputs (flash_attention_with_lse carries the dlse cotangent into
    its fused backward).

    Because the kernel's causal offset must be trace-time static but
    ``src`` rotates dynamically, the global causal structure is split
    into three static cases selected by lax.switch: src < idx (fully
    visible — non-causal kernel), src == idx (diagonal — causal
    kernel, offset 0), src > idx (fully masked — zero contribution).
    """
    from ..ops.pallas.flash_attention import flash_attention_with_lse

    m, l, acc = carry
    b, h, c, d = q.shape

    def full_chunk():
        return flash_attention_with_lse(q, kc, vc, scale, False, 0)

    def diag_chunk():
        return flash_attention_with_lse(q, kc, vc, scale, True, 0)

    def masked_chunk():
        return (jnp.zeros((b, h, c, d), q.dtype),
                jnp.full((b, h, c), _NEG_INF, jnp.float32))

    if causal:
        case = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
        o_c, lse_c = lax.switch(case, (full_chunk, diag_chunk, masked_chunk))
    else:
        o_c, lse_c = full_chunk()

    lse_c = lse_c[..., None]  # (b, h, c, 1)
    m_new = jnp.maximum(m, lse_c)
    # exp(sentinel - sentinel) = 1 would resurrect empty accumulators:
    # gate each term on its side having seen at least one real score
    alpha = jnp.where(m > _NEG_INF / 2, jnp.exp(m - m_new), 0.0)
    beta = jnp.where(lse_c > _NEG_INF / 2, jnp.exp(lse_c - m_new), 0.0)
    l_new = l * alpha + beta
    acc_new = acc * alpha + o_c.astype(jnp.float32) * beta
    return m_new, l_new, acc_new


def ulysses_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    q, k, v : (B, H, C, D) sequence chunks, H divisible by the axis
    size. Re-shards to (B, H/P, S, D), runs full-sequence attention
    locally (Pallas flash kernel on TPU via the op-layer impl), and
    re-shards back.
    """
    qg = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    from ..ndarray.op_impl_nn import flash_attention_op

    og = flash_attention_op(qg, kg, vg, causal=causal, sm_scale=sm_scale)
    return lax.all_to_all(og, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def _seq_sharded_wrapper(fn, mesh, axis_name, **kw):
    spec = P(None, None, axis_name, None)
    return jax.shard_map(
        functools.partial(fn, axis_name=axis_name, **kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)


def make_ring_attention_fn(mesh, axis_name="sp", causal=False,
                           sm_scale=None, remat=True, use_flash=None):
    """shard_map-wrapped ring attention over ``mesh[axis_name]``.

    Returns fn(q, k, v) on GLOBAL (B, H, S, D) arrays with S sharded
    over ``axis_name``; jit/grad-compatible.
    """
    if use_flash is None:
        # resolve on the mesh's REAL devices (inside shard_map only the
        # backend is visible): a CPU-device mesh in a TPU-backend
        # process must take the jnp fold, not crash in Mosaic
        from ..ops.pallas._util import interpret_mode, pallas_enabled
        use_flash = pallas_enabled() and (
            interpret_mode() or
            all(d.platform == "tpu" for d in mesh.devices.flat))
    return _seq_sharded_wrapper(
        ring_attention, mesh, axis_name, causal=causal, sm_scale=sm_scale,
        axis_size=int(mesh.shape[axis_name]), remat=remat,
        use_flash=use_flash)


def make_ulysses_attention_fn(mesh, axis_name="sp", causal=False,
                              sm_scale=None):
    """shard_map-wrapped Ulysses attention over ``mesh[axis_name]``."""
    return _seq_sharded_wrapper(
        ulysses_attention, mesh, axis_name, causal=causal, sm_scale=sm_scale)
