"""Frontier-compaction planner: admission -> dense per-wave work queues.

One *wave* is one group of ``G`` clusters of the shared batch visitation
order (core/search.py). The planner turns the per-(query, cluster)
admission decisions of a wave into the compact execution plan the
Pallas executor (kernels/score_cluster_batch) scalar-prefetches:

  * ``tile_cids`` — the wave's *admitted* cluster tiles (global cluster
    ids), compacted to the front; a tile no query admits never enters the
    executor grid at all, instead of being ``pl.when``-skipped after its
    DMA was already issued;
  * ``qblock`` — per admitted tile, the query *blocks* (``block_q``
    consecutive queries of the batch) containing at least one admitting
    query with a non-empty doc union, again compacted to the front. The
    executor's grid is blocked over queries, so only these blocks' dense
    query maps are gathered into VMEM — batch 256+ no longer pins the
    whole ``(n_q, V+1)`` map block resident;
  * *doc-run queues* — the second compaction level, keyed by
    **(tile, query block)**: each query block folds its *own* union of
    segment admissions (via the hoisted ``doc_seg_mod`` map) into a
    per-(tile, qblock) doc-admission mask, encoded into ``(start,
    length)`` doc runs and projected onto the executor's doc-axis
    blocking as a compacted *doc sub-tile queue* (``dblock`` /
    ``n_dblock``). Keying by query block instead of the whole batch is
    what keeps doc skipping alive at batch 256: the batch-wide union
    approaches "every segment admitted by someone" while a 16-query
    block's union stays sparse (``SearchConfig.doc_union`` selects the
    scope; ``"batch"`` reproduces the old batch-union behaviour for
    comparison);
  * under the **segment-major physical layout**
    (``ClusterIndex.seg_offsets`` / ``sorted_upto``, core/index.py) run
    encoding is a *prefix-table gather*: an admitted segment of the
    sorted prefix is exactly one run ``[seg_offsets[j],
    seg_offsets[j+1])`` clipped to ``sorted_upto``; only the unsorted
    insert tail ``[sorted_upto, d_pad)`` falls back to per-doc mask-RLE.
    Runs may cover tombstoned slots inside an admitted segment — they
    are a *superset* of the union admission mask, and the executor's
    residual in-kernel mask (``dmask_union``) keeps per-doc output
    exact;
  * queue tails are *clamped* (padded by repeating the last live entry),
    so skipped grid steps re-map to the block already resident in VMEM
    and trigger no new HBM traffic.

The (mu, eta)/segment admission tests and the budget rank-horizon live
here too: planning is pure bound arithmetic on ``O(n_q * G * n_seg)``
scalars, executing is the ``O(pairs * d_pad * t_pad)`` scoring — the
plan/execute split is exactly the paper's promise that pruning should
*skip* work, applied to the batch engine's compute, not just its HBM
traffic.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.types import _register
from repro.kernels.plan_wave.compact import compact_front as _compact_front


@partial(
    _register,
    data_fields=("cids", "live", "admit", "seg_admit", "tile_cids",
                 "tile_pos", "n_tiles", "qblock", "n_qblock",
                 "n_blocks", "drun_start", "drun_len", "n_drun",
                 "dblock", "n_dblock", "dmask_union"),
    meta_fields=("block_q", "block_d"),
)
@dataclasses.dataclass(frozen=True)
class WavePlan:
    """Compact execution plan for one visitation wave of ``G`` clusters.

    cids:      (G,) int32   global cluster ids of the wave, walk order.
    live:      (G,) bool    wave positions that are real clusters.
    admit:     (n_q, G) bool      per-(query, tile) admission.
    seg_admit: (n_q, G, n_seg) bool  per-segment document admission.
    tile_cids: (G,) int32   admitted tiles' global cluster ids, compacted
                            to the front, tail clamped to the last live
                            entry (never out of [0, m)).
    tile_pos:  (G,) int32   each compacted tile's position within the
                            wave (indexes admit/seg_admit/outputs).
    n_tiles:   () int32     number of admitted tiles (<= G).
    qblock:    (G, n_qb) int32  per compacted tile: indices of query
                            blocks with >= 1 admitting query and a
                            non-empty doc union, compacted, tail clamped.
    n_qblock:  (G,) int32   live query-block count per compacted tile.
    n_blocks:  () int32     total executor grid blocks with real work
                            (= sum of n_qblock over admitted tiles).
    drun_start:(G, n_qb, R) int32  per (compacted tile, compacted query-
                            block slot): start doc slot of each admitted
                            doc run of *that query block's* union,
                            compacted, tail clamped like the tile queue.
    drun_len:  (G, n_qb, R) int32  matching run lengths (0 past n_drun,
                            so a clamped tail entry never admits
                            anything).
    n_drun:    (G, n_qb) int32  live run count per (tile, qblock slot).
    dblock:    (G, n_qb, n_db) int32  per (tile, qblock slot): indices
                            of doc sub-tiles (``block_d`` consecutive
                            slots) intersecting that block's union,
                            compacted, clamped.
    n_dblock:  (G, n_qb) int32  live doc sub-tile count per (tile,
                            qblock slot) — the executor's per-(g, qb)
                            doc-axis clamp.
    dmask_union: (G, n_qb, d_pad) bool  per (tile, qblock slot): the
                            union doc-admission mask of that query block
                            (any of its queries admits the doc's segment
                            AND the doc is live) — the executor's
                            in-kernel residual mask for docs a visited
                            sub-tile carries outside the union.
    block_q:   static       queries per block (grid blocking factor).
    block_d:   static       doc slots per sub-tile (doc-axis blocking;
                            == d_pad disables intra-tile skipping).
    """

    cids: jax.Array
    live: jax.Array
    admit: jax.Array
    seg_admit: jax.Array
    tile_cids: jax.Array
    tile_pos: jax.Array
    n_tiles: jax.Array
    qblock: jax.Array
    n_qblock: jax.Array
    n_blocks: jax.Array
    drun_start: jax.Array
    drun_len: jax.Array
    n_drun: jax.Array
    dblock: jax.Array
    n_dblock: jax.Array
    dmask_union: jax.Array
    block_q: int
    block_d: int

    @property
    def n_qb(self) -> int:
        return self.qblock.shape[1]

    @property
    def n_db(self) -> int:
        return self.dblock.shape[-1]

    @property
    def d_pad(self) -> int:
        return self.dmask_union.shape[-1]

    def walked_docs(self) -> jax.Array:
        """() int32: doc slots the executor walks for this wave — each
        live (admitted tile, query block) pair scores its own
        ``n_dblock[g, qb] * block_d`` doc slots. Equals
        ``n_blocks * d_pad`` iff no sub-tile is skipped."""
        return (self.n_dblock.sum() * self.block_d).astype(jnp.int32)


def resolve_block_d(d_pad: int, block_d: int | None) -> int:
    """Executor doc-axis blocking factor: the smallest divisor of
    ``d_pad`` that is >= the requested ``block_d`` (None => d_pad, i.e.
    whole-tile execution). Rounding *up* to a divisor keeps sub-tiles
    from degenerating (a prime d_pad falls back to whole tiles rather
    than 1-doc blocks)."""
    if block_d is None or block_d >= d_pad:
        return d_pad
    if block_d < 1:
        raise ValueError(f"block_d must be >= 1, got {block_d}")
    for cand in range(block_d, d_pad + 1):
        if d_pad % cand == 0:
            return cand
    return d_pad


# Stable front-compaction (indices of True entries moved to the front,
# clamped tail, plus count) now lives in kernels/plan_wave/compact.py as
# a cumsum+scatter scan — the device-plan launch shape — with the old
# argsort formulation kept as kernels/plan_wave/ref.py and pinned
# bit-identical. plan_wave() takes it as the injectable ``_compact``
# seam so the equivalence tests can swap backends.


def segment_histogram(doc_seg_mod: jax.Array, doc_mask: jax.Array,
                      n_seg: int) -> jax.Array:
    """(..., n_seg) int32 live-doc count per segment for each tile.

    The per-tile fold the doc-run compaction rests on: a segment's
    admission decision covers exactly ``hist[..., j]`` docs, so the
    expected walked-doc fraction is ``sum_admitted hist / sum hist``
    (docs/perf.md has the arithmetic; tests pin hist against the union
    mask)."""
    oh = jax.nn.one_hot(doc_seg_mod, n_seg, dtype=jnp.int32)
    return (oh * doc_mask[..., None].astype(jnp.int32)).sum(axis=-2)


def seg_lookup(seg_admit: jax.Array, doc_seg_mod: jax.Array) -> jax.Array:
    """(..., G, d_pad) bool: ``seg_admit[..., g, doc_seg_mod[g, d]]``.

    seg_admit: (..., G, n_seg) bool segment admission (leading axes — a
    query or query-block axis — broadcast against the metadata);
    doc_seg_mod: (G, d_pad) int32 in [0, n_seg). n_seg == 1 is the
    collapsed (anytime) table: one bit covers every doc of the tile.

    Written as an OR of ``n_seg`` elementwise selects, not a gather: XLA
    lowers ``take_along_axis`` here to a scalar-index gather over every
    (…, G, d_pad) slot (on the TPU its (…, 1) index operand pads to 128
    lanes), while the selects fuse into the consumer's mask."""
    n_seg = seg_admit.shape[-1]
    if n_seg == 1:
        return jnp.broadcast_to(seg_admit,
                                seg_admit.shape[:-1] + doc_seg_mod.shape[-1:])
    acc = False
    for j in range(n_seg):
        acc = acc | ((doc_seg_mod == j) & seg_admit[..., j:j + 1])
    return acc


def _union_doc_admission(seg_admit_any: jax.Array, doc_seg_mod: jax.Array,
                         doc_mask: jax.Array) -> jax.Array:
    """(..., G, d_pad) bool: docs admitted by the given segment union.

    seg_admit_any: (..., G, n_seg_eff) union segment admission (leading
    axes — e.g. a query-block axis — broadcast against the (G, d_pad)
    metadata)."""
    return doc_mask & seg_lookup(seg_admit_any, doc_seg_mod)


def _doc_runs(admit_docs: jax.Array, n_runs: int,
              _compact=_compact_front
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run-length encode each row's admitted doc slots.

    admit_docs: (G, d_pad) bool. Returns (start (G, n_runs) int32,
    length (G, n_runs) int32, count (G,) int32); starts compacted to the
    front with a clamped tail, lengths 0 past the live count (so clamped
    tail entries admit nothing). ``n_runs`` must be >= d_pad // 2 + 1
    (the maximum possible run count)."""
    G, dp = admit_docs.shape
    prev = jnp.pad(admit_docs[:, :-1], ((0, 0), (1, 0)))
    nxt = jnp.pad(admit_docs[:, 1:], ((0, 0), (0, 1)))
    is_start = admit_docs & jnp.logical_not(prev)            # (G, dp)
    is_end = admit_docs & jnp.logical_not(nxt)               # (G, dp)
    starts_all, n_run = _compact(is_start)
    ends_all, _ = _compact(is_end)          # same count: runs pair up
    starts = starts_all[:, :n_runs]
    # run length = matching end - start + 1; a scatter-add over the run
    # ids would also work but XLA:CPU serializes 2-D scatters (see
    # kernels/plan_wave/compact.py) — the paired compact is pure gather
    slot = jnp.arange(n_runs, dtype=jnp.int32)
    lens = jnp.where(slot < n_run[:, None],
                     ends_all[:, :n_runs] - starts + 1, 0)
    return starts, lens, n_run


def runs_to_mask(starts: jax.Array, lens: jax.Array, n_drun: jax.Array,
                 d_pad: int) -> jax.Array:
    """Reconstruct the (..., d_pad) admission mask a run queue encodes —
    the executor-facing semantics (ref path + property tests). Works for
    any leading batch shape (per-tile or per-(tile, qblock) queues).
    Note the reconstruction is a *superset* of the union admission mask
    under the segment-major layout: prefix-table runs cover tombstoned
    slots inside admitted segments (the residual mask owns those)."""
    slot = jnp.arange(d_pad, dtype=jnp.int32)
    R = starts.shape[-1]
    live = jnp.arange(R, dtype=jnp.int32) < n_drun[..., None]  # (..., R)
    inside = ((slot >= starts[..., None])
              & (slot < (starts + lens)[..., None])
              & live[..., None])                             # (..., R, dp)
    return inside.any(axis=-2)


def plan_wave(cids: jax.Array, live: jax.Array, admit: jax.Array,
              seg_admit: jax.Array, block_q: int,
              doc_seg_mod: jax.Array, doc_mask: jax.Array,
              block_d: int | None = None,
              seg_offsets: jax.Array | None = None,
              sorted_upto: jax.Array | None = None,
              union_scope: str = "qblock",
              _compact=_compact_front) -> WavePlan:
    """Compact a wave's admission masks into dense work queues.

    cids (G,) int32; live (G,) bool; admit (n_q, G) bool;
    seg_admit (n_q, G, n_seg) bool; doc_seg_mod/doc_mask (G, d_pad) the
    wave's gathered *pre-modded* segment map (ClusterIndex.doc_seg_mod)
    and liveness; seg_offsets (G, n_seg + 1) / sorted_upto (G,) the
    wave's gathered segment-major layout metadata (None falls back to
    pure mask-RLE run encoding, treating every slot as unsorted tail).
    ``block_q`` must divide the padded batch the executor will run
    (callers pad; n_q here may be unpadded — the trailing partial block
    simply admits fewer queries). ``block_d`` is resolved via
    :func:`resolve_block_d` (None => whole-tile execution).
    ``union_scope`` keys the doc-run/sub-tile queues by query block
    (``"qblock"``, the default) or replicates the whole-batch union into
    every block (``"batch"``, the pre-per-qblock behaviour). ``_compact``
    injects the front-compaction backend (kernels/plan_wave) — the
    device-plan equivalence tests swap it; production callers leave the
    default."""
    if union_scope not in ("qblock", "batch"):
        raise ValueError(f"unknown union_scope {union_scope!r}")
    n_q, G = admit.shape
    dp = doc_mask.shape[-1]
    n_seg_eff = seg_admit.shape[-1]
    block_d = resolve_block_d(dp, block_d)
    n_qb = -(-n_q // block_q)
    pad = n_qb * block_q - n_q
    admit_p = jnp.pad(admit, ((0, pad), (0, 0))) if pad else admit
    seg_p = (jnp.pad(seg_admit, ((0, pad), (0, 0), (0, 0)))
             if pad else seg_admit)

    # per-query-block segment unions: the union over block_q consecutive
    # queries instead of the whole batch — at batch 256 a block's union
    # stays sparse where the batch union saturates
    seg_qb = seg_p.reshape(n_qb, block_q, G, n_seg_eff).any(axis=1)
    if union_scope == "batch":
        seg_qb = jnp.broadcast_to(seg_qb.any(axis=0, keepdims=True),
                                  seg_qb.shape)              # (n_qb, G, s)
    # per-qblock union doc admission (segment fold via the hoisted modded
    # map), wave-position space
    dmask_qb = _union_doc_admission(seg_qb, doc_seg_mod,
                                    doc_mask)                # (n_qb, G, dp)

    # a tile whose batch union is empty — every segment pruned for every
    # admitting query, or only tombstones/padding — is dropped from the
    # tile queue outright, it could only produce masked output
    docs_any = dmask_qb.any(axis=0)                          # (G, dp)
    tile_keep = admit.any(axis=0) & live & docs_any.any(axis=-1)   # (G,)
    tile_pos, n_tiles = _compact(tile_keep)
    tile_cids = cids[tile_pos]

    # per wave-position: query blocks with an admitting query AND a
    # non-empty doc union (a block whose queries admit the tile but
    # prune every segment would only produce masked output)
    blk_any = admit_p.reshape(n_qb, block_q, G).any(axis=1)  # (n_qb, G)
    blk_keep = (blk_any & dmask_qb.any(axis=-1))[:, tile_pos].T  # (G, n_qb)
    qblock, n_qblock = _compact(blk_keep)
    # tiles beyond n_tiles contribute no work regardless of their clamped
    # queue contents
    t = jnp.arange(G, dtype=jnp.int32)
    n_qblock = jnp.where(t < n_tiles, n_qblock, 0)

    # gather the union masks and segment unions into compacted
    # (tile slot, qblock slot) order — aligned with tile_cids and qblock
    dmask_c = jnp.take_along_axis(
        jnp.transpose(dmask_qb, (1, 0, 2))[tile_pos],
        qblock[:, :, None], axis=1)                          # (G, n_qb, dp)
    seg_qb_c = jnp.take_along_axis(
        jnp.transpose(seg_qb, (1, 0, 2))[tile_pos],
        qblock[:, :, None], axis=1)                          # (G, n_qb, s)

    # ---- doc-run queues, per (tile, qblock slot) -----------------------
    # Segment-major prefix gather: an admitted segment of the sorted
    # prefix is ONE run [off[j], off[j+1]) clipped to sorted_upto — no
    # per-doc scan. Only the unsorted insert tail [sorted_upto, dp) is
    # mask-RLE'd. Runs are a superset of the union mask (they may cover
    # tombstones inside admitted segments); dmask_c stays the executor's
    # exact residual mask.
    if seg_offsets is None or sorted_upto is None:
        off = jnp.zeros((G, n_seg_eff + 1), jnp.int32)
        su = jnp.zeros((G,), jnp.int32)
        off_total = off[:, -1:]
    else:
        off = seg_offsets[tile_pos].astype(jnp.int32)        # (G, n_seg+1)
        su = sorted_upto[tile_pos].astype(jnp.int32)         # (G,)
        off_total = off[:, -1:]
    if n_seg_eff == 1:
        # collapsed (anytime) table: the whole sorted prefix is one run
        seg_starts = jnp.zeros((G, 1), jnp.int32)
        seg_ends = jnp.minimum(off_total, su[:, None])
    else:
        seg_starts = jnp.minimum(off[:, :-1], su[:, None])
        seg_ends = jnp.minimum(off[:, 1:], su[:, None])
    seg_lens = jnp.maximum(seg_ends - seg_starts, 0)         # (G, s)
    cand_seg_start = jnp.broadcast_to(seg_starts[:, None],
                                      (G, n_qb, n_seg_eff))
    cand_seg_len = jnp.broadcast_to(seg_lens[:, None],
                                    (G, n_qb, n_seg_eff))
    keep_seg = seg_qb_c & (cand_seg_len > 0)

    slot = jnp.arange(dp, dtype=jnp.int32)
    tail_mask = dmask_c & (slot >= su[:, None, None])        # (G, n_qb, dp)
    rt = dp // 2 + 1
    ts, tl, tn = _doc_runs(tail_mask.reshape(G * n_qb, dp), rt,
                           _compact=_compact)
    ts = ts.reshape(G, n_qb, rt)
    tl = tl.reshape(G, n_qb, rt)
    tn = tn.reshape(G, n_qb)
    keep_tail = jnp.arange(rt, dtype=jnp.int32) < tn[..., None]

    cand_start = jnp.concatenate([cand_seg_start, ts], axis=-1)
    cand_len = jnp.concatenate([cand_seg_len, tl], axis=-1)
    cand_keep = jnp.concatenate([keep_seg, keep_tail], axis=-1)
    ridx, n_drun = _compact(cand_keep)
    drun_start = jnp.take_along_axis(cand_start, ridx, axis=-1)
    drun_len = jnp.take_along_axis(cand_len, ridx, axis=-1)
    rslot = jnp.arange(ridx.shape[-1], dtype=jnp.int32)
    drun_len = jnp.where(rslot < n_drun[..., None], drun_len, 0)

    # doc sub-tile queue per (tile, qblock slot): the executor's doc-axis
    # clamp — grid stays (G, n_qb, n_db), n_db clamps per (g, qb)
    n_db = dp // block_d
    sub_any = dmask_c.reshape(G, n_qb, n_db, block_d).any(axis=-1)
    dblock, n_dblock = _compact(sub_any)
    qb_live = jnp.arange(n_qb, dtype=jnp.int32)[None] < n_qblock[:, None]
    n_drun = jnp.where(qb_live, n_drun, 0)
    n_dblock = jnp.where(qb_live, n_dblock, 0)
    return WavePlan(
        cids=cids, live=live, admit=admit, seg_admit=seg_admit,
        tile_cids=tile_cids, tile_pos=tile_pos, n_tiles=n_tiles,
        qblock=qblock, n_qblock=n_qblock,
        n_blocks=n_qblock.sum().astype(jnp.int32),
        drun_start=drun_start, drun_len=drun_len, n_drun=n_drun,
        dblock=dblock, n_dblock=n_dblock, dmask_union=dmask_c,
        block_q=block_q, block_d=block_d)


def wave_summaries(plans: WavePlan, executed) -> list[dict]:
    """Host-side per-wave work summary from *stacked* recorded plans
    (the ``record_plans`` output of core/search.py: every WavePlan field
    carries a leading ``(n_groups,)`` axis, ``executed`` marks waves the
    early-exiting walk actually ran).

    One dict per executed wave, in walk order: admitted tile count,
    live executor grid blocks, admitted (query, tile) pairs, admitted
    segments, and the doc slots the executor walks for the wave
    (``n_dblock * block_d``, the per-wave term of
    ``TopK.n_walked_docs``). This is what the observability layer hangs
    per-wave trace-span args on (repro.obs / docs/observability.md) —
    wave *counts* are exact even though wave *durations* inside one
    fused device computation are not individually measurable."""
    import numpy as np

    ex = np.asarray(executed)
    n_tiles = np.asarray(plans.n_tiles)
    n_blocks = np.asarray(plans.n_blocks)
    admit = np.asarray(plans.admit)
    seg_admit = np.asarray(plans.seg_admit)
    n_dblock = np.asarray(plans.n_dblock)
    out = []
    for g in np.nonzero(ex)[0]:
        out.append({
            "wave": int(g),
            "tiles_admitted": int(n_tiles[g]),
            "grid_blocks": int(n_blocks[g]),
            "admitted_pairs": int(admit[g].sum()),
            "admitted_segments": int(seg_admit[g].sum()),
            "walked_doc_slots": int(n_dblock[g].sum()) * plans.block_d,
        })
    return out


def doc_admission(plan: WavePlan, doc_seg_mod: jax.Array,
                  doc_mask: jax.Array) -> jax.Array:
    """(n_q, G, d_pad) bool: which (query, doc) scores are admitted.

    doc_seg_mod/doc_mask are the wave's (G, d_pad) gathered metadata —
    the *pre-modded* segment map hoisted onto ClusterIndex (planning no
    longer pays ``doc_seg % n_seg`` per wave). This is the single source
    of truth for masking executor output to NEG — including blocks the
    compacted grid never visited (whose kernel output is unwritten
    garbage by design)."""
    return (seg_lookup(plan.seg_admit, doc_seg_mod)
            & plan.admit[:, :, None] & doc_mask[None])
