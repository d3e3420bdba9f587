"""Sharded embedding tables (expert/vocab partitioning).

≙ reference distributed lookup table (SURVEY.md §2.3: huge embeddings sharded
across pservers, trainer prefetches rows by id — prefetch_op.cc,
lookup_sparse_table_op.cc, distribute_transpiler.py:212). TPU-native design:
the table lives sharded over a mesh axis (rows split); lookups run under
shard_map — each device gathers the ids that fall in its row range and the
partial results are psum-combined (an all-to-all-free formulation that XLA
maps well to ICI; masked-gather cost is O(ids) per device).

The backward pass through jnp.take is a scatter-add onto the local shard,
which XLA keeps sharded — the gradient never materializes the full table
(the SelectedRows sparse-grad capability, reference selected_rows.h:32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import MODEL_AXIS, DeviceMesh


def sharded_embedding_lookup(mesh: DeviceMesh, table, ids,
                             axis_name: str = MODEL_AXIS):
    """table: [V, D] (will be row-sharded over `axis_name`); ids: int [...].
    Returns [..., D]."""
    n = mesh.axis_size(axis_name)
    v, d = table.shape
    assert v % n == 0, f"vocab {v} not divisible by shard count {n}"
    rows_per = v // n

    def body(tbl, ids):
        idx = jax.lax.axis_index(axis_name)
        lo = idx * rows_per
        local = ids - lo
        in_range = (local >= 0) & (local < rows_per)
        safe = jnp.clip(local, 0, rows_per - 1)
        vals = jnp.take(tbl, safe, axis=0)
        vals = jnp.where(in_range[..., None], vals, 0.0)
        return jax.lax.psum(vals, axis_name)

    f = jax.shard_map(body, mesh=mesh.jax_mesh,
                  in_specs=(P(axis_name, None), P()),
                  out_specs=P())
    return f(table, ids)


def embedding_table_sharding(mesh: DeviceMesh, axis_name: str = MODEL_AXIS):
    """NamedSharding to place/keep a [V, D] table row-sharded."""
    return mesh.sharding(axis_name, None)
