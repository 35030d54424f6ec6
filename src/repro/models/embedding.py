"""Embedding substrate: plain lookup, EmbeddingBag, and row-sharded
distributed lookup.

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse — per the task
spec this IS part of the system: bags are ``jnp.take`` + ``segment_sum``.

Distributed lookup: tables are row-sharded over 'model' (a 10^8-row DLRM
table never fits one chip). A ``shard_map`` pulls the classic pattern —
each shard masks the ids it owns, gathers locally, and a ``psum`` over the
table axis assembles the result — so the table is never all-gathered.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_rules


def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Single-id lookup (ids (...,) -> (..., D)), mesh-aware.

    With sharding rules installed, runs the mask+gather+psum shard_map over
    the 'table_rows' axis; otherwise a plain take (CPU tests).
    """
    rules = current_rules()
    axis = rules.table.get("table_rows") if rules else None
    if rules is None or rules.mesh is None or axis is None:
        return table[ids]

    batch_spec = rules.spec("batch")
    batch_axes = batch_spec[0] if len(batch_spec) else None
    # divisibility guard: a batch of 1 (retrieval encode) or any
    # non-dividing leading dim falls back to a replicated id batch.
    if batch_axes is not None:
        axs = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)
        sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))
        total = 1
        for a in axs:
            total *= sizes.get(a, 1)
        if ids.shape[0] % total != 0:
            batch_axes = None

    def local(table_local, ids_local):
        p = jax.lax.axis_index(axis)
        r_local = table_local.shape[0]
        local_ids = ids_local - p * r_local
        valid = (local_ids >= 0) & (local_ids < r_local)
        emb = table_local[jnp.clip(local_ids, 0, r_local - 1)]
        emb = jnp.where(valid[..., None], emb, 0)
        return jax.lax.psum(emb, axis)

    ids_spec = P(batch_axes, *([None] * (ids.ndim - 1)))
    out_spec = P(batch_axes, *([None] * ids.ndim))
    fn = jax.shard_map(
        local, mesh=rules.mesh,
        in_specs=(P(axis, None), ids_spec),
        out_specs=out_spec, check_vma=False)
    return fn(table, ids)


def embedding_bag(table: jax.Array, flat_ids: jax.Array,
                  segment_ids: jax.Array, n_segments: int,
                  mode: str = "sum",
                  weights: jax.Array | None = None) -> jax.Array:
    """EmbeddingBag: ragged multi-hot bags -> (n_segments, D) reduce.

    flat_ids (L,) int32, segment_ids (L,) int32 sorted, optional per-sample
    weights (L,).
    """
    emb = embedding_lookup(table, flat_ids)                    # (L, D)
    if weights is not None:
        emb = emb * weights[:, None]
    if mode == "sum":
        return jax.ops.segment_sum(emb, segment_ids, n_segments)
    if mode == "mean":
        s = jax.ops.segment_sum(emb, segment_ids, n_segments)
        cnt = jax.ops.segment_sum(jnp.ones_like(flat_ids, jnp.float32),
                                  segment_ids, n_segments)
        return s / jnp.maximum(cnt, 1.0)[:, None]
    if mode == "max":
        return jax.ops.segment_max(emb, segment_ids, n_segments)
    raise ValueError(f"unknown bag mode {mode!r}")


def embedding_init(key, n_rows: int, dim: int, scale: float = 0.01,
                   dtype=jnp.float32, pad_rows_to: int = 1) -> jax.Array:
    """``pad_rows_to``: round the row count up so a row-sharded table
    divides any mesh axis (ids never reference the padding rows)."""
    rows = -(-n_rows // pad_rows_to) * pad_rows_to
    return (jax.random.normal(key, (rows, dim), jnp.float32)
            * scale).astype(dtype)
