"""Jit'd public wrapper for the work-queue executor kernel.

``score_admitted`` pads the query batch to the plan's block size, runs
the scalar-prefetch kernel over the compacted work queues (tile queue,
query-block queue, and the doc-run-derived doc sub-tile queue), then
applies scale and the planner's doc-admission mask so every non-admitted
(query, doc) pair — including grid blocks the compacted queues never
visited — comes out exactly ``NEG``.

Interpret mode is resolved per call (always compiled on a TPU,
interpreted elsewhere unless ``REPRO_PALLAS_INTERPRET=0``) — see
``repro.utils.pallas_interpret_default``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.plan import WavePlan, doc_admission
from repro.kernels.score_cluster_batch.ref import (NEG, score_admitted_ref,
                                                   score_runs_ref)
from repro.kernels.score_cluster_batch.score_cluster_batch import (
    score_queue_kernel)


def score_admitted(index_doc_tids: jax.Array, index_doc_tw: jax.Array,
                   doc_seg_mod: jax.Array, doc_mask: jax.Array,
                   qmaps: jax.Array, plan: WavePlan, scale: jax.Array,
                   *, block_v: int | None = None, **kw) -> jax.Array:
    """index_doc_tids/index_doc_tw: the FULL (m, dp, tp) index arrays —
    the kernel DMAs admitted doc sub-tiles straight out of them via the
    plan's queues; doc_seg_mod/doc_mask: (G, dp) wave metadata (the
    pre-modded segment map + liveness, hosts of the admission mask);
    qmaps: (n_q, V + 1). Returns (n_q, G, dp) scores with non-admitted
    pairs at NEG."""
    n_q = qmaps.shape[0]
    pad = -n_q % plan.block_q
    qmaps_p = jnp.pad(qmaps, ((0, pad), (0, 0))) if pad else qmaps
    raw = score_queue_kernel(
        index_doc_tids, index_doc_tw, qmaps_p, plan.tile_cids,
        plan.tile_pos, plan.n_tiles, plan.qblock, plan.n_qblock,
        plan.dblock, plan.n_dblock, plan.dmask_union,
        block_q=plan.block_q, block_d=plan.block_d, block_v=block_v, **kw)
    raw = raw[:n_q] * scale
    return jnp.where(doc_admission(plan, doc_seg_mod, doc_mask), raw,
                     jnp.float32(NEG))


__all__ = ["score_admitted", "score_admitted_ref", "score_runs_ref"]
