"""Sharded embedding tables with all-to-all exchange (expert/embedding
parallelism over ICI).

SURVEY §2.4 names "sharded embedding tables + all-to-all over ICI" as
the TPU-native equivalent of the reference's row_sparse embedding +
kvstore sparse pull/push (src/kvstore/kvstore_dist.h sparse path,
gluon/contrib SparseEmbedding): instead of every worker pulling rows
from a parameter server, the table lives row-sharded across the mesh
and lookups route to the owning shard with ``lax.all_to_all`` — the
DLRM-style exchange, bandwidth-optimal on the torus.

Protocol per device (inside shard_map, axis ``ep``, n devices):
1. bucket the local batch's ids by owner shard (sort + fixed capacity
   c = local batch size — worst case every id lives on one shard);
2. ``all_to_all`` the (n, c) id buckets → each shard receives the ids
   it owns;
3. local gather from the table shard → (n, c, E) rows;
4. ``all_to_all`` back → senders reassemble their batch's embeddings.

Everything is static-shape (pad slots route row 0 and are zeroed on
return), so the whole exchange jits into one XLA program; the backward
transposes the all_to_alls and scatter-adds into the owning shard —
the gradient never materializes the full table anywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["make_sharded_embedding_fn", "shard_embedding_table"]


def shard_embedding_table(table, mesh, axis_name="ep"):
    """Place a (V, E) table row-sharded over ``axis_name``. V must be
    divisible by the axis size."""
    n = mesh.shape[axis_name]
    if table.shape[0] % n:
        raise ValueError(
            f"table rows {table.shape[0]} not divisible by mesh axis "
            f"{axis_name}={n}")
    return jax.device_put(table, NamedSharding(mesh, P(axis_name, None)))


def _local_lookup(table_l, ids_l, axis_name):
    """Per-device body: bucketed all_to_all exchange (see module doc)."""
    n = lax.axis_size(axis_name)  # static python int inside shard_map
    rows = table_l.shape[0]
    b = ids_l.shape[0]
    c = b  # bucket capacity: worst case all local ids on one shard

    owner = (ids_l // rows).astype(jnp.int32)
    order = jnp.argsort(owner)  # stable: groups ids by destination
    sorted_ids = ids_l[order]
    cnt = jnp.sum(owner[None, :] == jnp.arange(n)[:, None], axis=1)  # (n,)
    start = jnp.cumsum(cnt) - cnt
    k_idx = start[:, None] + jnp.arange(c)[None, :]          # (n, c)
    valid = jnp.arange(c)[None, :] < cnt[:, None]            # (n, c)
    gather_idx = jnp.clip(k_idx, 0, b - 1)
    send_ids = jnp.where(valid, sorted_ids[gather_idx], 0)   # (n, c)

    # row i of send_ids goes to device i; receive one row from each
    recv_ids = lax.all_to_all(send_ids, axis_name, 0, 0)
    me = lax.axis_index(axis_name)
    local = jnp.clip(recv_ids - me * rows, 0, rows - 1)
    vals = table_l[local]                                    # (n, c, E)
    back = lax.all_to_all(vals, axis_name, 0, 0)             # (n, c, E)

    contrib = jnp.where(valid[..., None], back, 0.0)
    out = jnp.zeros((b, table_l.shape[1]), table_l.dtype)
    out = out.at[order[gather_idx].reshape(-1)].add(
        contrib.reshape(-1, table_l.shape[1]).astype(table_l.dtype))
    return out


def make_sharded_embedding_fn(mesh, axis_name="ep", batch_axis=None):
    """Build ``lookup(table, ids) -> (batch, E)`` where the table is
    row-sharded over ``axis_name`` and the batch is sharded over
    ``batch_axis`` (defaults to ``axis_name`` — the pure-EP layout).

    Passing a distinct ``batch_axis`` composes EP with data
    parallelism on one mesh: ids shard over (batch_axis, axis_name)
    jointly — every device owns a distinct slice of the batch — and
    the all_to_all exchange rides the table axis within each dp row
    (the DLRM dp x ep layout; splitting the dp-shard across tp peers
    also divides the exchange work instead of duplicating it).

    Differentiable: grad w.r.t. the table stays sharded (scatter-add on
    the owning shard via the transposed exchange). ids length must be
    divisible by the product of the named axis sizes.
    """
    id_spec = (P((batch_axis, axis_name)) if batch_axis
               and batch_axis != axis_name else P(axis_name))

    def lookup(table, ids):
        return jax.shard_map(
            lambda t, i: _local_lookup(t, i.reshape(-1), axis_name),
            mesh=mesh,
            in_specs=(P(axis_name, None), id_spec),
            out_specs=id_spec,
        )(table, ids)

    return lookup
