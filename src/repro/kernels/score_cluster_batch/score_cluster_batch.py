"""Pallas TPU kernel: work-queue executor for the plan/execute pipeline.

The planner (core/plan.py) compacts each visitation wave's admitted
(query, cluster) pairs into dense work queues; this kernel *is* the
executor. It scalar-prefetches the queues and uses them in its BlockSpec
index maps, so the grid walks only real work:

  * grid = (G, n_qb, n_db[, n_vb]): compacted tile slots x query blocks
    x doc sub-tiles (x vocab chunks for WordPiece-scale maps);
  * the cluster tile for slot ``i`` is DMA'd straight out of the *full*
    ``(m, d_pad, t_pad)`` index arrays at row ``tile_cids[i]`` — no XLA
    gather ever materializes the wave's tiles, and a tile admitted by no
    query is simply absent from the queue (it never enters the grid,
    rather than being ``pl.when``-skipped after its DMA was issued);
  * the query-map block for step ``(i, j)`` is rows
    ``[qblock[i, j] * BQ, (qblock[i, j] + 1) * BQ)`` — only blocks
    containing an admitting query are fetched, and the resident VMEM
    footprint is ``BQ * V_chunk`` floats instead of the whole
    ``(n_q, V + 1)`` map, which is what lets batch 256+ fit VMEM;
  * the tile's doc axis is blocked into ``block_d``-slot sub-tiles and
    step ``(i, j, d)`` loads sub-tile ``dblock[i, j, d]`` — the
    planner's doc-run queues, keyed by **(tile, query block)** and
    projected onto the blocking, so a sub-tile *this query block's*
    union admits nothing in never enters the grid: the paper's
    in-cluster document skipping, applied per query block to both the
    DMA and the multiply-adds (``n_db`` clamps per ``(g, qb)`` via the
    prefetched ``n_dblock[i, j]`` counts — batch 256 skips like batch
    8 because each block only walks its own union). Residual docs a
    visited sub-tile carries outside the block's union are masked to
    NEG *in-kernel* via the planner's per-qblock union admission mask,
    so written output is exact for unadmitted docs too;
  * steps past the end of a queue are re-mapped (in the index maps, via
    the prefetched counts) to the block of the *last real step*, so they
    issue no DMA, compute nothing (``pl.when``), and their write-back is
    an idempotent rewrite of data the last real step already produced.

Output blocks the queues never visit are uninitialized garbage *by
design*: the op wrapper (ops.py) masks everything non-admitted to NEG
with the planner's doc-admission mask, which is the single source of
truth downstream (top-k merge, work counters).

Optional vocab blocking (``block_v``): the dense-map gather cannot be
blocked by slicing (tids are arbitrary in [0, V]), so each vocab chunk
contributes ``where(v0 <= tid < v0 + BV, chunk[tid - v0], 0)`` and the
output block accumulates across the innermost grid dimension. Full-V
(one chunk) is the default and skips the masking entirely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import pallas_interpret_default

# python float (not a traced jnp scalar): pallas kernels cannot capture
# array constants
NEG = float(jnp.finfo(jnp.float32).min)


def _queue_step(i, j, d, n_tiles_ref, n_qblock_ref, n_dblock_ref):
    """Clamp a (tile, qblock, doc sub-tile) grid step onto the queues.

    Real steps map to themselves; steps past any queue's end map to the
    *last real step* of the innermost live queue (same blocks already
    resident in VMEM => no DMA, and the write-back rewrites what that
    step already wrote). Padded steps must pin the last real step's
    blocks outright — min() clamping per axis would restart inner queues
    at slot 0 and revisit out blocks non-consecutively, which compiled
    write-back turns into stale-VMEM clobbers of already-written scores
    (interpret mode re-reads out blocks per step and cannot see this).
    Also returns whether the step is real, so the vocab-chunk index can
    be clamped the same way.

    ``n_dblock_ref`` is (G, n_qb): the doc queue is keyed per
    (tile, query-block slot), so the doc-axis clamp — and therefore how
    many sub-tiles a step actually walks — is resolved per ``(ii, jj)``
    pair, not per tile."""
    tile_live = i < n_tiles_ref[0]
    ii = jnp.where(tile_live, i, jnp.maximum(n_tiles_ref[0] - 1, 0))
    lastq = jnp.maximum(n_qblock_ref[ii] - 1, 0)
    qb_live = tile_live & (j < n_qblock_ref[ii])
    jj = jnp.where(qb_live, j, lastq)
    lastd = jnp.maximum(n_dblock_ref[ii, jj] - 1, 0)
    real = qb_live & (d < n_dblock_ref[ii, jj])
    dd = jnp.where(real, d, lastd)
    return ii, jj, dd, real


def _kernel(tile_cids_ref, tile_pos_ref, n_tiles_ref, qblock_ref,
            n_qblock_ref, dblock_ref, n_dblock_ref, tids_ref, tw_ref,
            qmaps_ref, dmask_ref, out_ref, *, n_vb: int, block_v: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    d = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((i < n_tiles_ref[0]) & (j < n_qblock_ref[i])
             & (d < n_dblock_ref[i, j]))
    def _score():
        tids = tids_ref[...][0].astype(jnp.int32)        # (BD, tp)
        # Mosaic has no uint8 -> float32 cast: widen through int32
        tw = tw_ref[...][0].astype(jnp.int32).astype(jnp.float32)
        qmaps = qmaps_ref[...]                           # (BQ, BV)
        if n_vb == 1:
            qv = jnp.take(qmaps, tids.reshape(-1), axis=1,
                          indices_are_sorted=False, unique_indices=False)
            qv = qv.reshape((qmaps.shape[0],) + tids.shape)
        else:
            v0 = k * block_v
            local = jnp.clip(tids - v0, 0, block_v - 1)
            qv = jnp.take(qmaps, local.reshape(-1), axis=1,
                          indices_are_sorted=False, unique_indices=False)
            qv = qv.reshape((qmaps.shape[0],) + tids.shape)
            in_chunk = (tids >= v0) & (tids < v0 + block_v)
            qv = jnp.where(in_chunk[None], qv, 0.0)
        partial_scores = jnp.sum(qv * tw[None], axis=-1)  # (BQ, BD)
        # residual docs the sub-tile carries outside this query block's
        # union: exactly NEG in the written output (unvisited blocks
        # stay garbage; the op wrapper's doc-admission mask owns those)
        in_run = dmask_ref[...][0, 0, 0] != 0             # (1, BD)

        if n_vb == 1:
            out_ref[...] = jnp.where(in_run, partial_scores,
                                     NEG)[None, None, None]
        else:
            @pl.when(k == 0)
            def _init():
                out_ref[...] = jnp.where(in_run, partial_scores,
                                         NEG)[None, None, None]

            @pl.when(k > 0)
            def _accum():
                out_ref[...] += jnp.where(in_run, partial_scores,
                                          0.0)[None, None, None]


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_d", "block_v", "interpret"))
def score_queue_kernel(
    doc_tids: jax.Array,        # (m, dp, tp) integer in [0, V] (V = zero slot)
    doc_tw: jax.Array,          # (m, dp, tp) uint8
    qmaps: jax.Array,           # (n_q_pad, V + 1) float32, qmaps[:, V] == 0
    tile_cids: jax.Array,       # (G,) int32 compacted global cluster ids
    tile_pos: jax.Array,        # (G,) int32 wave position per compacted tile
    n_tiles: jax.Array,         # () int32
    qblock: jax.Array,          # (G, n_qb) int32 compacted query-block queue
    n_qblock: jax.Array,        # (G,) int32
    dblock: jax.Array,          # (G, n_qb, n_db) int32 per-(tile, qblock)
                                #   compacted doc sub-tile queue
    n_dblock: jax.Array,        # (G, n_qb) int32 per-(tile, qblock) clamp
    dmask_union: jax.Array,     # (G, n_qb, dp) uint8 per-qblock union doc
                                #   admission per slot
    *,
    block_q: int,
    block_d: int,
    block_v: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(n_q_pad, G, dp) raw scores laid out by *wave position* (the
    ``tile_pos`` entry of each queue slot), without scale or admission
    masking; wave positions / doc sub-tiles the queues never visit hold
    unwritten garbage — callers must mask with the planner's
    doc-admission (ops.score_admitted does). Docs a *visited* sub-tile
    carries outside its query block's union come out exactly NEG (the
    in-kernel residual mask)."""
    if interpret is None:       # backend auto-detect + env override
        interpret = pallas_interpret_default()
    m, dp, tp = doc_tids.shape
    n_q_pad, v_cols = qmaps.shape
    G, n_qb = qblock.shape
    n_db = dblock.shape[-1]
    if n_q_pad % block_q:
        raise ValueError(f"qmaps rows {n_q_pad} not a multiple of "
                         f"block_q {block_q}")
    if dp % block_d or n_db != dp // block_d:
        raise ValueError(f"doc queue width {n_db} does not block d_pad "
                         f"{dp} by block_d {block_d}")
    if block_v is None:
        block_v = v_cols
    v_pad = -v_cols % block_v
    if v_pad:
        qmaps = jnp.pad(qmaps, ((0, 0), (0, v_pad)))
    n_vb = qmaps.shape[1] // block_v

    def tile_idx(i, j, d, k, cids, pos, nt, qb, nqb, db, ndb):
        ii, jj, dd, _ = _queue_step(i, j, d, nt, nqb, ndb)
        return (cids[ii], db[ii, jj, dd], 0)

    def qmap_idx(i, j, d, k, cids, pos, nt, qb, nqb, db, ndb):
        ii, jj, _, real = _queue_step(i, j, d, nt, nqb, ndb)
        # padded steps pin the *last* chunk too — the one the previous
        # real step left resident — so they issue no qmap DMA either
        kk = jnp.where(real, k, n_vb - 1)
        return (qb[ii, jj], kk)

    def dmask_idx(i, j, d, k, cids, pos, nt, qb, nqb, db, ndb):
        ii, jj, dd, _ = _queue_step(i, j, d, nt, nqb, ndb)
        return (ii, jj, db[ii, jj, dd], 0, 0)

    def out_idx(i, j, d, k, cids, pos, nt, qb, nqb, db, ndb):
        ii, jj, dd, _ = _queue_step(i, j, d, nt, nqb, ndb)
        return (pos[ii], db[ii, jj, dd], qb[ii, jj], 0, 0)

    # the per-step mask and output blocks are whole trailing dims of
    # these layouts, which keeps them legal TPU blocks ((8, 128)-aligned
    # or full) for any block_q / block_d; the output is transposed back
    # to (n_q_pad, G, dp) below
    n_qb_out = n_q_pad // block_q
    dmask5 = dmask_union.astype(jnp.int32).reshape(G, n_qb, n_db, 1,
                                                   block_d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        # doc sub-tiles inside query blocks: the query-map block stays
        # resident across a tile's whole doc queue (it is the dominant
        # traffic at WordPiece scale); the tile's sub-blocks re-stream
        # per query block but shrink with every skipped run
        grid=(G, n_qb, n_db, n_vb),
        in_specs=[
            # one doc sub-tile straight out of the full index arrays
            pl.BlockSpec((1, block_d, tp), tile_idx),
            pl.BlockSpec((1, block_d, tp), tile_idx),
            # only query blocks with >= 1 admitting query are fetched
            pl.BlockSpec((block_q, block_v), qmap_idx),
            # per-qblock union doc-admission for the in-kernel residual
            # mask
            pl.BlockSpec((1, 1, 1, 1, block_d), dmask_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_q, block_d), out_idx),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n_vb=n_vb, block_v=block_v),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, n_db, n_qb_out, block_q, block_d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret,
    )(tile_cids.astype(jnp.int32), tile_pos.astype(jnp.int32),
      n_tiles.reshape(1).astype(jnp.int32), qblock.astype(jnp.int32),
      n_qblock.astype(jnp.int32), dblock.astype(jnp.int32),
      n_dblock.astype(jnp.int32), doc_tids, doc_tw, qmaps, dmask5)
    return out.transpose(2, 3, 0, 1, 4).reshape(n_q_pad, G, dp)
