"""Core data types for the ASC cluster-skipping index.

Everything is a registered-dataclass pytree of *padded dense arrays* so the
whole index is shardable with NamedSharding and usable inside jit. Static
geometry (pad sizes, vocab) lives in metadata fields so jit re-traces only
when the index geometry changes, never per query.

Layout choices (see DESIGN.md §2):
  * forward (doc-major) layout inside clusters: ``doc_tids``/``doc_tw`` give
    each document's own nonzero terms — scoring is a gather from a dense
    query map + dot, the TPU-idiomatic replacement for posting-list
    traversal;
  * a dense uint8 segment-maximum table ``seg_max`` of shape
    ``(m, n_seg, vocab)`` — bound estimation for a batch of queries becomes
    one int8 GEMM (kernels/segment_bound);
  * all weights quantized to uint8 with one global scale; segment maxima are
    computed *after* quantization so every rank-safety proposition holds
    exactly in quantized score space.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

# Sentinel term id used to pad ``doc_tids`` rows. Points at a dedicated
# zero-weight slot (index ``vocab``) in every dense query map.
PAD_TERM = -1


def _register(cls, data_fields, meta_fields):
    return jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=list(meta_fields)
    )


@partial(
    _register,
    data_fields=("tids", "tw", "mask"),
    meta_fields=("vocab",),
)
@dataclasses.dataclass(frozen=True)
class SparseDocs:
    """A batch of sparse documents in padded COO-per-row form.

    tids: (n_docs, t_pad) int32, PAD_TERM-padded term ids.
    tw:   (n_docs, t_pad) float32 term weights (0 at padding).
    mask: (n_docs, t_pad) bool validity of each slot.
    """

    tids: jax.Array
    tw: jax.Array
    mask: jax.Array
    vocab: int

    @property
    def n_docs(self) -> int:
        return self.tids.shape[0]

    @property
    def t_pad(self) -> int:
        return self.tids.shape[1]

    def densify(self) -> jax.Array:
        """(n_docs, vocab) dense matrix — test/oracle use only."""
        tids = jnp.where(self.mask, self.tids, self.vocab)
        dense = jnp.zeros((self.n_docs, self.vocab + 1), self.tw.dtype)
        dense = dense.at[jnp.arange(self.n_docs)[:, None], tids].max(
            jnp.where(self.mask, self.tw, 0.0)
        )
        return dense[:, : self.vocab]


@partial(
    _register,
    data_fields=("tids", "tw", "mask"),
    meta_fields=("vocab",),
)
@dataclasses.dataclass(frozen=True)
class QueryBatch:
    """A batch of sparse queries.

    tids: (n_q, q_pad) int32 term ids (PAD_TERM padded).
    tw:   (n_q, q_pad) float32 query term weights (0 at padding).
    mask: (n_q, q_pad) bool.
    """

    tids: jax.Array
    tw: jax.Array
    mask: jax.Array
    vocab: int

    @property
    def n_queries(self) -> int:
        return self.tids.shape[0]

    @property
    def q_pad(self) -> int:
        return self.tids.shape[1]

    def dense_map(self) -> jax.Array:
        """(n_q, vocab + 1) dense query maps; the trailing slot is the
        zero-weight landing pad for PAD_TERM gathers."""
        tids = jnp.where(self.mask, self.tids, self.vocab)
        out = jnp.zeros((self.n_queries, self.vocab + 1), jnp.float32)
        out = out.at[jnp.arange(self.n_queries)[:, None], tids].add(
            jnp.where(self.mask, self.tw, 0.0)
        )
        return out.at[:, self.vocab].set(0.0)


@partial(
    _register,
    data_fields=(
        "doc_tids",
        "doc_tw",
        "doc_mask",
        "doc_ids",
        "doc_seg",
        "doc_seg_mod",
        "seg_max_stacked",
        "seg_offsets",
        "sorted_upto",
        "scale",
        "cluster_ndocs",
        "super_of",
        "super_members",
        "super_max_stacked",
    ),
    meta_fields=("vocab", "n_seg"),
)
@dataclasses.dataclass(frozen=True)
class ClusterIndex:
    """Cluster-skipping forward index with segmented maximum term weights.

    m = number of clusters, d_pad = padded docs/cluster, t_pad = padded
    terms/doc, n_seg = segments per cluster, V = vocab.

    doc_tids: (m, d_pad, t_pad) uint16 (int32 if vocab >= 2^16)
              term ids (== vocab at padding).
    doc_tw:   (m, d_pad, t_pad) uint8   quantized term weights.
    doc_mask: (m, d_pad) bool           per-document validity.
    doc_ids:  (m, d_pad) int32          global document ids (-1 padding).
    doc_seg:  (m, d_pad) int32          segment id of each doc in [0, n_seg).
    doc_seg_mod: (m, d_pad) int32       the *hoisted modded segment map*:
              ``doc_seg % n_seg``, maintained at pack/insert/compaction
              time so per-wave planning (core/plan.py doc admission and
              doc-run compaction) indexes segment-admission tables
              directly instead of re-modding ``doc_seg`` every wave.
              Invariant: always in [0, n_seg); lifecycle write paths keep
              it consistent with ``doc_seg`` (tests/test_lifecycle.py).
    seg_max_stacked: (m, n_seg + 1, V) uint8 — the *stored stacked* bound
              table: rows [0, n_seg) are the segmented maximum term
              weights, row n_seg is their max over segments (the BoundSum
              row). Storing the stacked layout means the fused bounds GEMM
              reshapes it to (m * (n_seg + 1), V) for free instead of
              concatenating a per-call uint8 copy, and the whole table
              still shards on the leading cluster axis. Maintained at
              build/compaction time and max-folded by online inserts.
    seg_offsets: (m, n_seg + 1) int32 — per-cluster *segment prefix
              table* of the segment-major physical layout: pack_clusters
              lays each cluster's docs out segment-contiguously (doc_seg
              stays random — only the slot order sorts), so segment j of
              cluster c occupies slots [seg_offsets[c, j],
              seg_offsets[c, j + 1]) and seg_offsets[c, n_seg] is the
              packed live count. Planning turns an admitted segment into
              exactly one doc run by gathering this table (core/plan.py)
              instead of run-length-encoding a per-doc mask.
    sorted_upto: (m,) int32 — how many leading slots of each cluster
              still obey the segment-major layout. d_pad right after
              pack/compaction; online inserts append into the unsorted
              tail [sorted_upto, d_pad) (reusing a tombstoned slot
              inside the sorted prefix shrinks it — see
              lifecycle/mutable.py), and the planner falls back to
              mask-RLE for the tail only. Tombstones inside the sorted
              prefix do NOT shrink it: a run may cover dead slots, the
              executor's residual mask keeps per-doc output exact.
    scale:    () float32                w_fp = w_u8 * scale.
    cluster_ndocs: (m,) int32           live docs per cluster.
    super_of: (m,) int32 — superblock id of each cluster in [0, S). The
              level-0 grouping is computed once at pack time
              (core/index.py ``group_superblocks``: deterministic kmeans
              over the clusters' collapsed bound rows, S ~ sqrt(m)) and
              is *stable under churn*: inserts max-fold into the owning
              superblock's table, deletes touch nothing, compaction
              regroups from the re-packed bounds.
    super_members: (S, super_cap) int32 — member cluster ids per
              superblock, ascending, -1 padded. The inverse of
              ``super_of``; the two-level walk gathers a pruned-in
              superblock's member tiles from here.
    super_max_stacked: (S, n_seg + 1, V) uint8 — the *coarse* stacked
              bound table: elementwise max over the member clusters'
              ``seg_max_stacked`` rows. Invariant (the whole rank-safety
              argument of the two-level walk, docs/perf.md §superblock):
              ``super_max_stacked[super_of[c]] >= seg_max_stacked[c]``
              elementwise, at all times — pack computes it exactly,
              inserts max-fold both tables, deletes tombstone only
              (both stay valid upper bounds), compaction rebuilds both.

    ``seg_max`` / ``seg_max_collapsed`` remain available as zero-copy
    views into the stacked table.
    """

    doc_tids: jax.Array
    doc_tw: jax.Array
    doc_mask: jax.Array
    doc_ids: jax.Array
    doc_seg: jax.Array
    doc_seg_mod: jax.Array
    seg_max_stacked: jax.Array
    seg_offsets: jax.Array
    sorted_upto: jax.Array
    scale: jax.Array
    cluster_ndocs: jax.Array
    super_of: jax.Array
    super_members: jax.Array
    super_max_stacked: jax.Array
    vocab: int
    n_seg: int

    @property
    def seg_max(self) -> jax.Array:
        """(m, n_seg, V) segment rows of the stacked table."""
        return self.seg_max_stacked[:, : self.n_seg]

    @property
    def seg_max_collapsed(self) -> jax.Array:
        """(m, V) BoundSum row (max over segments) of the stacked table."""
        return self.seg_max_stacked[:, self.n_seg]

    @property
    def m(self) -> int:
        return self.doc_tids.shape[0]

    @property
    def d_pad(self) -> int:
        return self.doc_tids.shape[1]

    @property
    def t_pad(self) -> int:
        return self.doc_tids.shape[2]

    @property
    def n_super(self) -> int:
        """S — number of superblocks of the level-0 grouping."""
        return self.super_max_stacked.shape[0]

    @property
    def super_cap(self) -> int:
        """Padded member slots per superblock."""
        return self.super_members.shape[1]

    @property
    def n_docs(self) -> jax.Array:
        return self.cluster_ndocs.sum()

    @property
    def free_slots(self) -> jax.Array:
        """(m,) free slots per cluster — the write path's admission /
        headroom metadata. ``cluster_ndocs`` counts live docs and slots
        freed by tombstoning are reusable, so this is exact under churn."""
        return self.d_pad - self.cluster_ndocs

    def replace(self, **updates) -> "ClusterIndex":
        """Functional update of data fields and/or static metadata."""
        return dataclasses.replace(self, **updates)

    def nbytes(self) -> int:
        return sum(
            x.size * x.dtype.itemsize
            for x in (self.doc_tids, self.doc_tw, self.doc_mask,
                      self.doc_ids, self.doc_seg, self.doc_seg_mod,
                      self.seg_max_stacked, self.seg_offsets,
                      self.sorted_upto, self.super_of,
                      self.super_members, self.super_max_stacked)
        )


@partial(
    _register,
    data_fields=("doc_ids", "scores", "n_scored_docs", "n_scored_clusters",
                 "n_scored_segments", "n_scored_tiles", "n_walked_tiles",
                 "n_walked_docs", "n_waves", "n_bounded_clusters",
                 "n_walked_superblocks", "n_pruned_superblocks"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class TopK:
    """Top-k result plus work counters (the TPU analogue of latency).

    doc_ids: (n_q, k) int32, score-descending; -1 where fewer than k hits.
    scores:  (n_q, k) float32.
    n_scored_docs / n_scored_clusters / n_scored_segments: (n_q,) int32 —
    how much work the pruning actually admitted; the efficiency metric every
    benchmark reports alongside wall-clock.
    n_scored_tiles / n_walked_tiles: (n_q,) int32 — executor grid blocks
    actually scored vs what a score-everything walk would have executed.
    Semantics are engine-specific: the batched engine counts compacted
    (cluster tile, query block) pairs over the whole batch, replicated
    per query (it shards/psums like the other counters); the per-query
    reference engine counts that query's own admitted/visited cluster
    tiles. Their ratio is the frontier-compaction ratio *within* one
    engine — never compare the raw counts across engines.
    n_walked_docs: (n_q,) int32 — document slots the executor actually
    walks (per-query-block doc-run compaction, core/plan.py): for the
    batched engine the batch-level sum over live (admitted tile, query
    block) pairs of that pair's own ``n_dblock * block_d``, replicated
    per query; for the per-query reference engine (whole-tile
    execution) ``n_scored_tiles * d_pad`` exactly. Invariants (pinned by
    tests/test_rank_safety_property.py): ``n_walked_docs <=
    n_scored_tiles * d_pad`` with equality iff no doc run is skipped,
    and every admitted doc (``n_scored_docs``) lies inside a walked run.
    n_waves: (n_q,) int32 — waves (loop iterations of the walk) run:
    the batched engine's waves over the batch, replicated per query
    like the tile counters (``n_waves * G * n_qb == n_walked_tiles``);
    the two-level walk's level-0 waves (superblocks visited); the
    per-query engine's own loop iterations; the pipelined engine's
    dispatched waves; the distributed path's most over its cluster
    shards; 0 for the brute-force oracle.
    n_bounded_clusters / n_walked_superblocks / n_pruned_superblocks:
    (n_q,) int32 — the level-0 funnel of the two-level walk
    (``SearchConfig.superblocks``, docs/perf.md §superblock). For the
    two-level batched engine these are batch-level counts replicated per
    query (like the tile counters): superblocks any live query admitted
    at level 0 (walked), superblocks every query pruned — including the
    early-exited tail (pruned, walked + pruned == S), and the member
    clusters of walked superblocks that entered the fine bounds GEMM
    (bounded — the O(S + survivors) term; ``n_bounded_clusters <=
    members of walked superblocks <= m``). Single-level engines report
    the degenerate funnel: bounded == m (one dense GEMM prices every
    cluster), walked == S, pruned == 0.
    """

    doc_ids: jax.Array
    scores: jax.Array
    n_scored_docs: jax.Array
    n_scored_clusters: jax.Array
    n_scored_segments: jax.Array
    n_scored_tiles: jax.Array
    n_walked_tiles: jax.Array
    n_walked_docs: jax.Array
    n_waves: jax.Array
    n_bounded_clusters: jax.Array
    n_walked_superblocks: jax.Array
    n_pruned_superblocks: jax.Array


def tree_bytes(tree: Any) -> int:
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
        if hasattr(x, "dtype")
    )
