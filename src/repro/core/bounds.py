"""Cluster / segment rank-score bound estimation (paper §3.1–3.2).

Given a query Q and cluster index with segmented maximum term weights:

    B_{i,j}        = sum_{t in Q} w_q(t) * max_{d in S_{i,j}} w_{t,d}
    MaxSBound(C_i) = max_j B_{i,j}          (Formula 3)
    AvgSBound(C_i) = (1/n) sum_j B_{i,j}    (Formula 4)
    BoundSum(C_i)  = sum_{t in Q} max_{d in C_i} w_{t,d}   (Formula 2)

``BoundSum`` equals ``B`` computed on the segment-collapsed table — which
the index *stores* as the last row of the stacked bound table
(``seg_max_stacked``, shape ``(m, n_seg + 1, V)``, maintained at
build/compaction time and max-folded by online inserts), so no retrieve
call ever rebuilds ``seg_max.max(axis=1)`` *or* copies the table to stack
the collapsed row under it: the fused GEMM operand is a zero-copy
``reshape(m * (n_seg + 1), V)`` of the stored layout.

Two implementations of the same contraction:
  * ``segment_bounds_gather`` — gather ``q_pad`` columns from the table and
    dot with query weights. Work ~ m*n_seg*q_pad; best when q_pad << V.
    This is the pure-jnp oracle.
  * ``segment_bounds_gemm``   — scatter the query to a dense (V,) map and
    run ``(m*n_seg, V) @ (V, n_q)`` as one quantized GEMM; the Pallas kernel
    in ``kernels/segment_bound`` implements exactly this contraction on the
    MXU (int8 feed, fused dequant) and is the serving hot path for query
    batches. ``cluster_bounds`` stacks the collapsed BoundSum row under the
    segment table so segment bounds *and* BoundSum come out of one fused
    GEMM instead of two separate contractions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import ClusterIndex, QueryBatch


def _gather_bounds(table: jax.Array, queries: QueryBatch,
                   scale: jax.Array) -> jax.Array:
    """(n_q, m, n) bounds from a (m, n, V) uint8 max-weight table."""
    V = table.shape[-1]
    qt = jnp.where(queries.mask, queries.tids, V)                # (n_q, qp)
    qw = jnp.where(queries.mask, queries.tw, 0.0)
    # pad the vocab axis with a zero slot so PAD_TERM gathers are no-ops
    padded = jnp.pad(table, ((0, 0), (0, 0), (0, 1)))            # (m,n,V+1)
    cols = padded[:, :, qt]                                      # (m,n,nq,qp)
    b = jnp.einsum("mnqt,qt->qmn", cols.astype(jnp.float32), qw)
    return b * scale


def segment_bounds_gather(index: ClusterIndex,
                          queries: QueryBatch) -> jax.Array:
    """(n_q, m, n_seg) float32 segment bounds B[q, i, j]."""
    return _gather_bounds(index.seg_max, queries, index.scale)


def segment_bounds_gemm(index: ClusterIndex, queries: QueryBatch,
                        use_kernel: bool = False,
                        qmaps: jax.Array | None = None) -> jax.Array:
    """Same contraction as one dense GEMM over the vocab axis.

    ``qmaps`` optionally passes pre-materialized dense query maps
    (``queries.dense_map()`` output) so callers that already built them
    for scoring don't scatter the batch twice."""
    if qmaps is None:
        qmaps = queries.dense_map()
    qmap = qmaps[:, : index.vocab]                               # (n_q, V)
    m, n_seg, V = index.seg_max.shape
    table = index.seg_max.reshape(m * n_seg, V)
    b = _gemm_bounds(table, qmap, index.scale, use_kernel)
    return b.reshape(queries.n_queries, m, n_seg)


def _gemm_bounds(table: jax.Array, qmap: jax.Array, scale: jax.Array,
                 use_kernel: bool) -> jax.Array:
    if use_kernel:
        from repro.kernels.segment_bound import ops as sb_ops
        return sb_ops.segment_bound_gemm(table, qmap, scale)
    # a full f32 contraction: at TPU default precision this GEMM rounds
    # its operands to bf16 (measured ~1e-3 off on a v5e), and a bound
    # rounded low stops dominating the scores it must
    return jnp.einsum("sv,qv->qs", table.astype(jnp.float32), qmap,
                      precision="highest") * scale


def cluster_bounds(index: ClusterIndex, queries: QueryBatch,
                   impl: str = "gather",
                   use_kernel: bool = False,
                   qmaps: jax.Array | None = None) -> dict[str, jax.Array]:
    """All bound statistics needed by any method, each (n_q, m).

    BoundSum comes from the collapsed row of the *stored* stacked table:
    under ``impl="gemm"`` the whole ``(m, n_seg + 1, V)`` table is fed to
    one fused GEMM as a zero-copy reshape, so segment bounds and BoundSum
    for the entire batch come out of a single contraction with no per-call
    uint8 stacking copy (that copy existed before the stacked layout was
    stored on the index; at WordPiece-scale ``m * n_seg * V`` its traffic
    overtook the saved dispatch)."""
    m, n_seg, V = index.seg_max.shape
    if impl == "gather":
        b = segment_bounds_gather(index, queries)
        bound_sum = _gather_bounds(index.seg_max_collapsed[:, None, :],
                                   queries, index.scale)[..., 0]
    elif impl == "gemm":
        if qmaps is None:
            qmaps = queries.dense_map()
        qmap = qmaps[:, :V]
        fused_table = index.seg_max_stacked.reshape(m * (n_seg + 1), V)
        fused = _gemm_bounds(fused_table, qmap, index.scale, use_kernel)
        fused = fused.reshape(queries.n_queries, m, n_seg + 1)
        b = fused[..., :n_seg]                           # (n_q, m, n_seg)
        bound_sum = fused[..., n_seg]                    # (n_q, m)
    else:
        raise ValueError(f"unknown bounds impl {impl!r}")
    max_s = b.max(axis=-1)
    avg_s = b.mean(axis=-1)
    return {"segment": b, "max_s": max_s, "avg_s": avg_s,
            "bound_sum": bound_sum}


def superblock_bounds(index: ClusterIndex, qmaps: jax.Array,
                      use_kernel: bool = False) -> dict[str, jax.Array]:
    """Level-0 bound statistics from the coarse superblock table, each
    ``(n_q, S)`` (plus ``"segment"`` at ``(n_q, S, n_seg)``).

    Same fused contraction as :func:`cluster_bounds` ``impl="gemm"``,
    over ``super_max_stacked.reshape(S * (n_seg + 1), V)`` — an
    ``O(S * V)`` GEMM instead of ``O(m * V)``. Because the coarse table
    elementwise-dominates every member's fine table and query-map
    weights are non-negative, each statistic here dominates the same
    statistic of every member cluster: a superblock pruned by the
    (mu, eta) test at level 0 could not have had any member admitted by
    the identical test at level 1 (docs/perf.md §superblock)."""
    S, n_seg_p1, V = index.super_max_stacked.shape
    n_seg = n_seg_p1 - 1
    qmap = qmaps[:, :V]
    fused_table = index.super_max_stacked.reshape(S * n_seg_p1, V)
    fused = _gemm_bounds(fused_table, qmap, index.scale, use_kernel)
    fused = fused.reshape(qmap.shape[0], S, n_seg_p1)
    b = fused[..., :n_seg]
    return {"segment": b, "max_s": b.max(axis=-1), "avg_s": b.mean(axis=-1),
            "bound_sum": fused[..., n_seg]}
