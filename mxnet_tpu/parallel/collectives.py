"""Named-axis collectives.

The XLA-collective replacements for the reference's communication
backends (SURVEY §5.8): ncclAllReduce → lax.psum over a mesh axis;
CommDevice ring/tree reduce → the partitioner's AllReduce; ps-lite
ZPush/ZPull → psum over the DCN-spanning axis; CUDA P2P CopyFromTo →
lax.ppermute. Use inside shard_map/jit; these are thin wrappers that
keep MXNet-ish naming.
"""
from __future__ import annotations

import jax
from jax import lax

__all__ = ["allreduce", "allgather", "reduce_scatter", "ppermute",
           "alltoall", "axis_index", "axis_size"]


def allreduce(x, axis_name: str, op: str = "sum"):
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown allreduce op {op}")


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute(x, axis_name: str, perm):
    return lax.ppermute(x, axis_name, perm)


def alltoall(x, axis_name: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.axis_size(axis_name)
