"""Jitted bucketed gradient allreduce — the CommDevice/NCCL analog.

Reference behavior being replaced (SURVEY §2.1/§3.2): KVStore 'device'
reduces per-GPU gradients with a P2P add tree (src/kvstore/comm.h
CommDevice) and 'nccl' with ncclAllReduce (kvstore_nccl.h), both fusing
many small tensors into buckets. TPU-first redesign: the per-context
gradient replicas of one logical parameter already live on distinct
chips, so we view them as ONE global array whose leading "replica" axis
is sharded over a 1-D device mesh, and compile `sum(axis=0)` with a
replicated output sharding. The XLA SPMD partitioner turns that into an
ICI/DCN AllReduce, and its all-reduce combiner pass fuses the reduces
of every parameter in the bucket — the NCCL-bucketing analog, but done
by the compiler.

One AOT-compiled executable is cached per (device tuple, shapes/dtypes)
structure — the whole parameter set is one bucket, so Trainer.step
dispatches ONE compiled computation per step regardless of param count.
Multi-process (DistKVStore) uses the same mechanism over the global
device list: every process contributes its local shards and executes
the same SPMD program, which is exactly jax multihost jit semantics.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["reduce_replica_lists", "can_fast_reduce", "last_hlo_text"]

# (devices, shapes/dtypes) -> (executable, stack_sharding, hlo text)
_CACHE: dict = {}
_LAST_HLO: list = [None]


def last_hlo_text():
    """HLO of the most recently used reduce executable (test hook: the
    multi-device tests assert an all-reduce is in the compiled text)."""
    return _LAST_HLO[0]


def can_fast_reduce(value_lists: Sequence[Sequence]) -> bool:
    """True when every key's per-context arrays sit on the same tuple of
    distinct devices (the Trainer layout) — the jitted stacked-psum path
    applies. Single-element lists are fine (pure multi-process reduce).
    """
    if not value_lists:
        return False
    dev0 = None
    for vlist in value_lists:
        devs = tuple(v.device for v in vlist)
        if len(set(devs)) != len(devs):
            return False
        if dev0 is None:
            dev0 = devs
        elif devs != dev0:
            return False
    return True


def _build(devices, shapes_dtypes):
    mesh = Mesh(np.asarray(devices), ("dp",))
    stack_sh = NamedSharding(mesh, P("dp"))
    repl_sh = NamedSharding(mesh, P())

    def reduce_all(stacked):
        return [x.sum(axis=0) for x in stacked]

    avals = [jax.ShapeDtypeStruct((len(devices),) + tuple(s), d,
                                  sharding=stack_sh)
             for s, d in shapes_dtypes]
    lowered = jax.jit(
        reduce_all, out_shardings=[repl_sh] * len(shapes_dtypes)).lower(avals)
    compiled = lowered.compile()
    return compiled, stack_sh, compiled.as_text()


def reduce_replica_lists(value_lists, devices=None):
    """Sum each key's per-device replica arrays in ONE compiled call.

    value_lists: list (over keys) of lists of same-shape jax.Arrays,
    each inner list holding one array per device of ``devices`` (order
    irrelevant — arrays are matched to mesh positions by .device).
    devices: the participating device tuple; defaults to the devices of
    the first list (single-process). For multi-process reduce pass the
    GLOBAL device list — local arrays are the addressable shards.

    Returns a list of globally-replicated jax.Arrays (one per key);
    read per-device copies off ``.addressable_shards``.
    """
    if devices is None:
        devices = tuple(a.device for a in value_lists[0])
    devices = tuple(devices)
    n = len(devices)
    shapes_dtypes = tuple(
        (tuple(v[0].shape), jnp.dtype(v[0].dtype)) for v in value_lists)
    key = (devices, shapes_dtypes)
    entry = _CACHE.get(key)
    if entry is None:
        entry = _build(devices, shapes_dtypes)
        _CACHE[key] = entry
    compiled, stack_sh, hlo = entry
    _LAST_HLO[0] = hlo

    stacked = []
    for vlist, (shape, dtype) in zip(value_lists, shapes_dtypes):
        # device_put commits an (possibly uncommitted) array to its own
        # device so the reshape below cannot migrate it to the default
        # device (no copy is made for an already-resident buffer).
        shards = [jax.device_put(v, v.device).reshape((1,) + shape)
                  for v in vlist]
        stacked.append(jax.make_array_from_single_device_arrays(
            (n,) + shape, stack_sh, shards))
    return compiled(stacked)


def reduce_compressed_replica_lists(value_lists, residual_lists,
                                    devices=None, ctype="2bit",
                                    threshold=0.5):
    """Gradient-compressed fused reduce with error feedback — the
    reference GradientCompression (src/kvstore/gradient_compression.cc)
    redesigned for compiled collectives: quantization, residual update
    and the all-reduce are ONE XLA computation; residuals stay sharded
    per device, the reduced value comes back replicated.

    ctype '2bit': each element of (grad + residual) maps to
    {+threshold, 0, -threshold}; residual accumulates the error
    (reference 2-bit stochastic quantization contract). ctype 'int8':
    symmetric per-tensor int8 with the scale computed in-graph.

    Returns (reduced_list, new_residual_lists)."""
    if devices is None:
        devices = tuple(a.device for a in value_lists[0])
    devices = tuple(devices)
    n = len(devices)
    shapes_dtypes = tuple(
        (tuple(v[0].shape), jnp.dtype(v[0].dtype)) for v in value_lists)
    key = ("compressed", devices, shapes_dtypes, ctype, float(threshold))
    entry = _CACHE.get(key)
    if entry is None:
        mesh = Mesh(np.asarray(devices), ("dp",))
        stack_sh = NamedSharding(mesh, P("dp"))
        repl_sh = NamedSharding(mesh, P())
        t = float(threshold)

        def reduce_all(stacked_g, stacked_r):
            outs, new_rs = [], []
            for g, r in zip(stacked_g, stacked_r):
                eff = g.astype(jnp.float32) + r
                if ctype == "2bit":
                    q = jnp.where(eff >= t, t,
                                  jnp.where(eff <= -t, -t, 0.0))
                else:  # int8: in-graph symmetric scale per shard
                    amax = jnp.maximum(jnp.max(jnp.abs(eff)), 1e-8)
                    s = amax / 127.0
                    q = jnp.round(eff / s).astype(jnp.int8).astype(jnp.float32) * s
                new_rs.append(eff - q)
                outs.append(q.sum(axis=0).astype(g.dtype))
            return outs, new_rs

        n_keys = len(shapes_dtypes)
        avals_g = [jax.ShapeDtypeStruct((n,) + tuple(s), d, sharding=stack_sh)
                   for s, d in shapes_dtypes]
        avals_r = [jax.ShapeDtypeStruct((n,) + tuple(s), jnp.float32,
                                        sharding=stack_sh)
                   for s, _ in shapes_dtypes]
        compiled = jax.jit(
            reduce_all,
            out_shardings=([repl_sh] * n_keys, [stack_sh] * n_keys),
            donate_argnums=(1,),
        ).lower(avals_g, avals_r).compile()
        entry = (compiled, stack_sh, compiled.as_text())
        _CACHE[key] = entry
    compiled, stack_sh, hlo = entry
    _LAST_HLO[0] = hlo

    def stack(vlists):
        out = []
        for vlist, (shape, _) in zip(vlists, shapes_dtypes):
            shards = [jax.device_put(v, v.device).reshape((1,) + shape)
                      for v in vlist]
            out.append(jax.make_array_from_single_device_arrays(
                (n,) + shape, stack_sh, shards))
        return out

    if residual_lists is None:
        # first call: zero error-feedback buffers, sharded like the grads
        residual_lists = [
            jax.make_array_from_callback(
                (n,) + tuple(shape), stack_sh,
                lambda idx, shape=shape: np.zeros(
                    (1,) + tuple(shape), np.float32))
            for shape, _ in shapes_dtypes]
    reduced, new_res = compiled(stack(value_lists), residual_lists)
    # new_res are stacked sharded arrays — hand them back in on the next
    # call (the per-device error-feedback state lives on its device)
    return reduced, new_res


def reduce_grad_ndarrays_inplace(grads):
    """Sum each key's per-context NDArray gradients and write the sum
    back into every replica — the kvstore-less multi-device reduce used
    by Trainer/Module when no store was configured (reference
    executor_group still sums; silently training on divergent replicas
    is never correct). One compiled all-reduce when the replicas sit on
    distinct devices, an eager add-tree otherwise (tests sharing one
    device)."""
    vlists = [[g._data for g in glist] for glist in grads]
    if (can_fast_reduce(vlists) and len(vlists[0]) > 1
            and len({a.device for a in vlists[0]}) == len(vlists[0])):
        reduced = reduce_replica_lists(vlists)
        for glist, garr in zip(grads, reduced):
            for g in glist:
                g._set_data(shard_for_device(garr, g._data.device))
        return
    for glist in grads:
        total = glist[0]
        for g in glist[1:]:
            total = total + g.as_in_context(total.ctx)
        for g in glist:
            g._set_data(total._data if g.ctx == total.ctx
                        else total.as_in_context(g.ctx)._data)


def shard_for_device(garr, device):
    """The addressable shard of a replicated global array on ``device``
    (zero-copy view — this is how reduced gradients get written back
    into each context's NDArray)."""
    for s in garr.addressable_shards:
        if s.data.device == device:
            return s.data
    raise ValueError(f"device {device} not addressable in {garr.sharding}")
