"""Top-k retrieval: ASC, Anytime Ranking, Anytime*, and the rank-safe oracle.

Two engines express every method (DESIGN.md §2):

``engine="batched"`` (default, the serving hot path) — a plan/execute
batch-frontier loop for the whole query batch:

  1. bounds for all clusters are computed up front — segment bounds *and*
     the collapsed BoundSum row come out of one fused GEMM / gather over
     the *stored stacked* bound table (``seg_max_stacked``; core/bounds.py
     reshapes it for free instead of stacking a per-call copy);
  2. clusters are walked in a *shared* visitation order (fair interleave:
     a cluster's priority is the best rank any query in the batch assigns
     it), so each cluster's (d_pad, t_pad) forward tile crosses the HBM
     boundary **once per batch** instead of once per query;
  3. per wave of ``group_size`` clusters, the *planner* (core/plan.py)
     applies every query's own (mu, eta) admission test, segment-level
     pruning and the budget rank-horizon, then compacts the surviving
     (query, cluster) pairs into dense work queues;
  4. the *executor* (kernels/score_cluster_batch) scalar-prefetches the
     queues: admitted tiles are DMA'd straight out of the full index
     arrays, only query blocks with an admitting query are gathered, and
     a tile no query admits never enters the grid — pruning skips
     compute, not just HBM traffic;
  5. each query's top-k/theta is updated by an incremental
     threshold-filtered merge (group candidates above theta -> top-k of the
     group -> 2k-merge with the running heap), not a concatenate + top_k
     over k + G*d_pad candidates;
  6. a query leaves the frontier when the suffix-maximum of its ordering
     key over the remaining visitation positions can no longer beat
     ``theta / exit_div``; the loop exits when every query is done.

``engine="per_query"`` — the original ``vmap`` of a per-query grouped
``lax.while_loop`` over that query's own bound-sorted order. Kept as the
reference oracle: benchmarks/serve_throughput.py measures the batched
engine against it, and tests/test_rank_safety.py asserts result-set
equivalence at mu = eta = 1.

``engine="pipelined"`` — the batched walk restructured as a host-driven
dispatch loop over *device* launches (``retrieve_pipelined``): each
wave's plan is one ``kernels/plan_wave`` launch (admission + queue
compaction fully on device, only the clamped queue lengths return to
host), plans run ahead of execution against a theta snapshot that may
*lag* the exact frontier state (superset admission — see
docs/perf.md §device-planning for the rank-safety argument), and
consecutive low-admission waves are fused into one executor launch that
re-derives the *exact* per-wave admission from the live carry before
masking/merging — so ids, scores and all admission counters are
bit-identical to ``engine="batched"`` while the host stops serializing
plan -> execute every wave.

Pruning rules (theta = current top-k threshold):
  ASC       : cluster pruned iff MaxS <= theta/mu  AND  AvgS <= theta/eta;
              segment (i,j) pruned iff B_ij <= theta/eta.
  Anytime*  : cluster pruned iff BoundSum <= theta/mu (doc level ditto,
              expressed here as the n_seg=1 segment rule).
  Anytime   : Anytime* with mu = 1 (rank-safe), optional cluster budget —
              the TPU analogue of the paper's time budget is a bound on the
              number of clusters visited. Under the batched engine a
              budgeted query additionally only admits clusters inside its
              *own* top-``budget`` bound ranks, so the budget is spent on
              that query's best clusters even though the walk order is
              shared (docs/perf.md §rank-safety).

theta only ever grows (only true scores enter the heap), so the paper's
Propositions 1-4 apply unchanged under *any* visitation order; the shared
batch order updates each query's theta no more often than the sequential
algorithm, i.e. prunes *no more* — approximation guarantees are preserved
(tests/test_rank_safety.py checks them, including batched-vs-per-query
equivalence).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bounds import (_gemm_bounds, cluster_bounds,
                               superblock_bounds)
from repro.core.plan import (WavePlan, _union_doc_admission, doc_admission,
                             plan_wave, resolve_block_d, seg_lookup)
from repro.core.types import ClusterIndex, QueryBatch, TopK
from repro.kernels.score_cluster_batch.ref import (SCORE_CHUNK,
                                                   score_admitted_ref)

NEG = jnp.float32(jnp.finfo(jnp.float32).min)

#: named scopes of the served step's phases. Every leaf op of a compiled
#: ``retrieve`` carries one of them in its ``op_name`` metadata, so a
#: device trace splits the step's time by phase (docs/observability.md
#: §traces): the bounds pass, the planner (visitation order and each
#: wave's admission and queues), the executor, and the top-k merge with
#: its counters and early-exit test.
PHASE_SCOPES = ("asc.bounds", "asc.plan", "asc.execute", "asc.merge")


# `engine="auto"` routes tiny batches to the per-query reference engine:
# below this batch size the batched planner's per-wave queue compaction
# costs more than the tile reuse saves (BENCH_retrieval.json measured
# paired_speedup < 1 at batch 1; pinned by tests/test_batched_engine.py)
AUTO_ENGINE_MIN_BATCH = 4


def resolved_engine(cfg: "SearchConfig", n_q: int,
                    record_plans: bool = False) -> str:
    """The engine a retrieve with this (cfg, batch size) actually runs:
    resolves the ``"auto"`` route (batch size is a trace-time shape).
    The observability layer keys its counter semantics off this — the
    batched engine's tile/doc-walk counters are batch-level, the
    per-query engine's are per query (TopK docstring)."""
    if cfg.engine != "auto":
        return cfg.engine
    # plan recording only exists on the batched engine, so it wins the
    # route regardless of batch size; "pipelined" never wins the auto
    # route — it is host-driven and must be requested explicitly
    return ("per_query" if (n_q < AUTO_ENGINE_MIN_BATCH
                            and not record_plans) else "batched")


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    k: int = 10
    mu: float = 1.0
    eta: float = 1.0
    method: str = "asc"              # asc | anytime | anytime_star
    group_size: int = 8
    cluster_budget: int | None = None  # visit at most this many clusters
    bounds_impl: str = "gather"        # gather | gemm
    use_kernel: bool = False           # pallas kernels where available
    doc_prune: bool = True             # segment-level document pruning
    engine: str = "auto"               # auto | batched | per_query |
                                       # pipelined; auto routes batches
                                       # below AUTO_ENGINE_MIN_BATCH to
                                       # the per_query path; "pipelined"
                                       # is the host-driven device-plan
                                       # dispatch loop
                                       # (retrieve_pipelined)
    block_q: int | str = "auto"        # executor grid blocking over queries
                                       # ("auto": derived from batch size +
                                       # VMEM budget, see autotune_blocks)
    block_v: int | str | None = "auto"  # executor vocab chunking (None:
                                       # full-V; "auto": chunk only when
                                       # the map block would blow VMEM)
    block_d: int | str | None = "auto"  # executor doc sub-tile size;
                                       # rounded up to a divisor of d_pad
                                       # (None: whole-tile, no doc-run
                                       # skipping; "auto": from geometry +
                                       # the VMEM budget remainder)
    doc_union: str = "qblock"          # doc-run queue scope: per query
                                       # block (keeps doc skipping alive
                                       # at batch 256) | "batch" (legacy
                                       # batch-wide union, for comparison)
    score_impl: str = "auto"           # dense scoring formulation for the
                                       # jnp executor: "gather" (monolithic
                                       # transposed-map gather) | "chunked"
                                       # (same math in <= SCORE_CHUNK-query
                                       # chunks, bit-identical, cache-sized)
                                       # | "auto" (chunked above SCORE_CHUNK)
    fuse_waves: int | str = "auto"     # pipelined engine: max waves fused
                                       # into one executor launch (1 | 2 |
                                       # 4; "auto" = 4). 1 still pipelines
                                       # (plans run one launch ahead).
    superblocks: bool = False          # two-level walk on the batched
                                       # engine: a level-0 (mu, eta)
                                       # admission pass over the coarse
                                       # superblock bound table emits only
                                       # surviving superblocks' member
                                       # clusters into the fine bounds
                                       # GEMM — O(S + survivors) bound
                                       # cost instead of O(m)
                                       # (docs/perf.md §superblock).
                                       # engine="auto" batches below
                                       # AUTO_ENGINE_MIN_BATCH still route
                                       # to the (single-level, rank-safe)
                                       # per_query oracle.

    def __post_init__(self):
        if not (0.0 < self.mu <= self.eta <= 1.0):
            raise ValueError(
                f"need 0 < mu <= eta <= 1, got mu={self.mu} eta={self.eta}")
        if self.method not in ("asc", "anytime", "anytime_star"):
            raise ValueError(f"unknown method {self.method!r}")
        if self.engine not in ("auto", "batched", "per_query", "pipelined"):
            raise ValueError(f"unknown engine {self.engine!r}")
        if self.score_impl not in ("auto", "gather", "chunked"):
            raise ValueError(f"unknown score_impl {self.score_impl!r}")
        if self.fuse_waves != "auto" and self.fuse_waves not in (1, 2, 4):
            raise ValueError(f"fuse_waves must be 1, 2, 4 or 'auto', "
                             f"got {self.fuse_waves!r}")
        if self.block_q != "auto" and (not isinstance(self.block_q, int)
                                       or self.block_q < 1):
            raise ValueError(f"block_q must be >= 1 or 'auto', "
                             f"got {self.block_q!r}")
        for name in ("block_d", "block_v"):
            v = getattr(self, name)
            if v is not None and v != "auto" and (not isinstance(v, int)
                                                  or v < 1):
                raise ValueError(f"{name} must be >= 1, None or 'auto', "
                                 f"got {v!r}")
        if self.doc_union not in ("qblock", "batch"):
            raise ValueError(f"unknown doc_union {self.doc_union!r}")
        if self.superblocks and self.engine == "pipelined":
            raise ValueError("superblocks=True requires the batched "
                             "engine — the pipelined dispatch loop plans "
                             "against the full cluster order")


# executor resident-set target for block autotuning: roughly a quarter
# of a v5e core's 16 MiB VMEM, leaving room for double buffering and the
# scalar-prefetch queues (docs/perf.md §VMEM blocking math)
VMEM_BLOCK_BUDGET = 4 * 2**20


def plan_buffer_bytes(d_pad: int, n_seg: int, n_qb: int,
                      group_size: int) -> int:
    """Device-resident plan-buffer footprint for one wave's work queues
    (the arrays the executor scalar-prefetches while its tiles are in
    flight): per (tile, query block) the union mask ``dmask_union``
    (d_pad bool), the doc-run queue (start + length int32 over the
    ``d_pad // 2 + 1`` mask-RLE slots plus ``n_seg`` prefix-gather
    candidates), the run/sub-tile counts, and the sub-tile queue at its
    worst-case (block_d = 8) length. Since the planner moved on device
    (kernels/plan_wave) these buffers live alongside the executor's
    resident set, so the VMEM autotuner must charge them against the
    same budget (docs/perf.md §device-planning)."""
    runs = d_pad // 2 + 1 + n_seg
    per_pair = d_pad + 8 * runs + 8 + 4 * (d_pad // 8)
    return group_size * n_qb * per_pair


def autotune_blocks(d_pad: int, t_pad: int, n_seg: int, vocab: int,
                    n_q: int, group_size: int = 8
                    ) -> tuple[int, int, int | None]:
    """Derive (block_q, block_d, block_v) from index geometry + batch
    size under the VMEM budget. The resident set of one executor step is

        4 * BQ * BV          query-map block
      + 3 * BD * t_pad       doc sub-tile ids (2B) + weights (1B)
      + 4 * BQ * BD          output block
      + plan_buffer_bytes    device-resident wave-plan queues + masks

    (docs/perf.md). block_q is the power of two covering the batch,
    capped at 64; block_v chunks the map only when the full-V block
    would exceed half the budget; block_d spends the remainder but never
    exceeds ~one sub-tile per two segments (coarser blocks can't skip
    what segment admission prunes). The plan buffers are charged before
    the doc-axis remainder is spent — the old arithmetic over-committed
    VMEM once planning moved on device. Explicit SearchConfig values
    override each knob independently (resolve_blocks)."""
    bq = 1
    while bq < min(64, max(n_q, 1)):
        bq *= 2
    v_cols = vocab + 1
    if 4 * bq * v_cols <= VMEM_BLOCK_BUDGET // 2:
        bv = None                       # full-V gather, no chunk masking
        map_bytes = 4 * bq * v_cols
    else:
        bv = 512
        while 4 * bq * bv * 2 <= VMEM_BLOCK_BUDGET // 2:
            bv *= 2
        map_bytes = 4 * bq * bv
    n_qb = -(-max(n_q, 1) // bq)
    rem = max(VMEM_BLOCK_BUDGET - map_bytes
              - plan_buffer_bytes(d_pad, n_seg, n_qb, group_size), 0)
    bd_cap = max(8, rem // (3 * t_pad + 4 * bq))
    bd_req = max(8, min(int(bd_cap),
                        max(1, d_pad // max(2 * n_seg, 4))))
    return bq, resolve_block_d(d_pad, bd_req), bv


def resolve_blocks(index: ClusterIndex, n_q: int,
                   cfg: SearchConfig) -> tuple[int, int, int | None]:
    """Resolve the executor blocking factors for this (index, batch):
    ``"auto"`` entries come from :func:`autotune_blocks`, explicit
    SearchConfig values pass through untouched (block_d still rounds up
    to a divisor of d_pad)."""
    bq, bd, bv = cfg.block_q, cfg.block_d, cfg.block_v
    if "auto" in (bq, bd, bv):
        a_bq, a_bd, a_bv = autotune_blocks(index.d_pad, index.t_pad,
                                           index.n_seg, index.vocab, n_q,
                                           cfg.group_size)
        bq = a_bq if bq == "auto" else bq
        bd = a_bd if bd == "auto" else bd
        bv = a_bv if bv == "auto" else bv
    return bq, resolve_block_d(index.d_pad, bd), bv


def score_docs_ref(doc_tids: jax.Array, doc_tw: jax.Array, qmap: jax.Array,
                   scale: jax.Array) -> jax.Array:
    """RankScore for padded forward-layout docs.

    doc_tids: (..., t_pad) int32 in [0, V]; V is the zero landing slot.
    doc_tw:   (..., t_pad) uint8 quantized weights.
    qmap:     (V + 1,) float32 dense query map (qmap[V] == 0).
    """
    gathered = qmap[doc_tids]                               # (..., t_pad)
    return jnp.einsum("...t,...t->...", gathered,
                      doc_tw.astype(jnp.float32)) * scale


def _score_docs(index: ClusterIndex, cluster_ids: jax.Array,
                qmap: jax.Array, cfg: SearchConfig) -> jax.Array:
    """(G, d_pad) scores for the given clusters (one query)."""
    tids = index.doc_tids[cluster_ids]                      # (G, dp, tp)
    tw = index.doc_tw[cluster_ids]
    if cfg.use_kernel:
        from repro.kernels.score_docs import ops as sd_ops
        return sd_ops.score_docs(tids, tw, qmap, index.scale)
    return score_docs_ref(tids, tw, qmap, index.scale)


def brute_force_topk(index: ClusterIndex, queries: QueryBatch,
                     k: int) -> TopK:
    """Rank-safe oracle: score every live document (the MaxScore stand-in —
    identical result set, exhaustive execution)."""
    qmaps = queries.dense_map()                              # (n_q, V+1)

    def one(qmap):
        scores = score_docs_ref(index.doc_tids, index.doc_tw, qmap,
                                index.scale)                 # (m, d_pad)
        scores = jnp.where(index.doc_mask, scores, NEG)
        flat = scores.reshape(-1)
        top, pos = jax.lax.top_k(flat, k)
        ids = index.doc_ids.reshape(-1)[pos]
        return top, jnp.where(top > NEG, ids, -1)

    scores, ids = jax.vmap(one)(qmaps)
    n_docs = index.doc_mask.sum().astype(jnp.int32)
    nq = queries.n_queries
    m_full = jnp.full((nq,), index.m, jnp.int32)
    return TopK(
        doc_ids=ids, scores=scores,
        n_scored_docs=jnp.full((nq,), n_docs),
        n_scored_clusters=m_full,
        n_scored_segments=jnp.full((nq,), index.m * index.n_seg, jnp.int32),
        n_scored_tiles=m_full, n_walked_tiles=m_full,
        n_walked_docs=jnp.full((nq,), index.m * index.d_pad, jnp.int32),
        n_waves=jnp.zeros((nq,), jnp.int32),
        n_bounded_clusters=m_full,
        n_walked_superblocks=jnp.full((nq,), index.n_super, jnp.int32),
        n_pruned_superblocks=jnp.zeros((nq,), jnp.int32),
    )


def _resolve_budget(cfg: SearchConfig, m: int,
                    budget: jax.Array | None) -> jax.Array:
    if budget is None:
        return (jnp.int32(cfg.cluster_budget)
                if cfg.cluster_budget is not None else jnp.int32(m + 1))
    return jnp.asarray(budget, jnp.int32)


def _search_one_query(index: ClusterIndex, qmap: jax.Array,
                      seg_b: jax.Array, max_s: jax.Array, avg_s: jax.Array,
                      order_key: jax.Array, cfg: SearchConfig,
                      budget: jax.Array | None = None,
                      mu_eta: jax.Array | None = None) -> tuple:
    """The grouped-visitation loop for a single query (reference engine).

    seg_b (m, n_seg), max_s/avg_s/order_key (m,). Returns (ids, scores,
    counters). For anytime methods callers pass the collapsed bounds
    (seg_b == bound_sum[:, None] with n_seg picked up from the array).
    ``budget`` is an optional *traced* cluster-budget override so the
    serving feedback loop can retarget latency without recompiling
    (cfg.cluster_budget is static and would re-trace on every change).
    ``mu_eta`` (optional traced (2,) float32) overrides (cfg.mu, cfg.eta)
    the same way — the streaming front-end's per-request fidelity knob.
    """
    m = index.m
    G = cfg.group_size
    n_groups = -(-m // G)
    m_padded = n_groups * G
    k = cfg.k

    with jax.named_scope("asc.plan"):
        order = jnp.argsort(-order_key)                      # (m,)
        order = jnp.pad(order, (0, m_padded - m))
        sorted_key = jnp.pad(jnp.sort(-order_key) * -1.0,
                             (0, m_padded - m), constant_values=NEG)
    # work-based budget (the paper's time-budget semantics): only clusters
    # actually *scored* consume budget — clusters skipped by the (mu, eta)
    # test are free, so tighter pruning stretches the same budget deeper
    # into the visitation order (Table 7's ASC+budget > Anytime+budget).
    budget = _resolve_budget(cfg, m, budget)

    if mu_eta is None:
        mu = jnp.float32(cfg.mu)
        eta = jnp.float32(cfg.eta)
    else:
        mu, eta = mu_eta[0], mu_eta[1]
    # exit divisor: remaining clusters are all pruned once the sorted key
    # drops to theta/exit_div (see module docstring / Prop 2 analysis).
    exit_div = eta if cfg.method == "asc" else mu

    def cond(state):
        g, done, *_ = state
        with jax.named_scope("asc.merge"):
            return jnp.logical_and(g < n_groups, jnp.logical_not(done))

    def body(state):
        g, done, top_scores, top_ids, n_docs, n_clusters, n_segments = state
        with jax.named_scope("asc.plan"):
            theta = top_scores[k - 1]
            pos = g * G
            cids = jax.lax.dynamic_slice(order, (pos,), (G,))  # (G,)
            gkey = jax.lax.dynamic_slice(sorted_key, (pos,), (G,))
            live = (jnp.arange(G) + pos < m) & (gkey > NEG)

            b = seg_b[cids]                                    # (G, n_seg)
            if cfg.method == "asc":
                pruned = ((max_s[cids] <= theta / mu)
                          & (avg_s[cids] <= theta / eta))
            else:
                pruned = gkey <= theta / mu
            admit = live & jnp.logical_not(pruned)             # (G,)
            # spend budget only on admitted clusters, in visitation order
            admit = admit & (n_clusters
                             + jnp.cumsum(admit.astype(jnp.int32))
                             <= budget)

            # segment-level document pruning: B_ij is a valid upper bound
            # for every doc in segment j (Prop 1 proof), over-estimated by
            # eta (ASC) / mu (Anytime*).
            if cfg.doc_prune:
                seg_admit = b > theta / (eta if cfg.method == "asc" else mu)
            else:
                seg_admit = jnp.ones_like(b, dtype=bool)
            seg_admit = seg_admit & admit[:, None]             # (G, n_seg)

        with jax.named_scope("asc.execute"):
            scores = _score_docs(index, cids, qmap, cfg)       # (G, d_pad)
            doc_admit = index.doc_mask[cids] & seg_lookup(
                seg_admit, index.doc_seg_mod[cids])            # (G, d_pad)
            scores = jnp.where(doc_admit, scores, NEG)

        with jax.named_scope("asc.merge"):
            cand_scores = jnp.concatenate([top_scores, scores.reshape(-1)])
            cand_ids = jnp.concatenate([top_ids,
                                        index.doc_ids[cids].reshape(-1)])
            top_scores, pos_k = jax.lax.top_k(cand_scores, k)
            top_ids = cand_ids[pos_k]

            n_docs += doc_admit.sum().astype(jnp.int32)
            n_clusters += admit.sum().astype(jnp.int32)
            n_segments += seg_admit.sum().astype(jnp.int32)

            theta_new = top_scores[k - 1]
            nxt = jnp.minimum((g + 1) * G, m_padded - 1)
            done = sorted_key[nxt] <= theta_new / exit_div
            # budget exhaustion also terminates
            done = jnp.logical_or(done, n_clusters >= budget)
        return (g + 1, done, top_scores, top_ids,
                n_docs, n_clusters, n_segments)

    init = (jnp.int32(0), jnp.array(False),
            jnp.full((k,), NEG), jnp.full((k,), -1, jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0))
    (g_end, _, top_scores, top_ids, n_docs, n_clusters, n_segments) = (
        jax.lax.while_loop(cond, body, init))
    with jax.named_scope("asc.merge"):
        top_ids = jnp.where(top_scores > NEG, top_ids, -1)
        # tile counters in per-query terms (see TopK docstring): every
        # admitted cluster is a scored tile, every visited cluster
        # position a walked one (clamped: the last group's padding is
        # not a cluster); whole-tile execution walks exactly d_pad doc
        # slots per scored tile
        return (top_ids, top_scores, n_docs, n_clusters, n_segments,
                n_clusters, jnp.minimum(g_end * G, jnp.int32(m)),
                n_clusters * jnp.int32(index.d_pad), g_end)


def _admission(cfg: SearchConfig, *, glive, done, theta, max_s_w, avg_s_w,
               key_w, seg_b_w, rank_w, n_clusters, n_pruned, budget,
               gate_slack=None, clamp_slack=None, mu_eta=None) -> tuple:
    """One wave's (mu, eta)/segment admission + budget rank-horizon —
    the bound arithmetic shared by the serial planner, the device plan
    launch, and the fused executor's exact refinement. Returns
    (admit (n_q, G), seg_admit (n_q, G, n_seg), newly_pruned (n_q,)).

    ``gate_slack``/``clamp_slack`` (traced int32, default None = exact)
    relax the budget rank-horizon and the within-wave cumsum clamp for
    theta-lag planning: a plan built from a frontier snapshot that lags
    the executor by L clusters must admit a *superset* of the exact
    wave, which holds once the horizon is widened by L (n_pruned grows
    by at most L across the lag) and the clamp by one wave of G
    clusters (docs/perf.md §device-planning has the proof).

    ``mu_eta`` (optional traced (n_q, 2) float32) overrides the static
    (cfg.mu, cfg.eta) *per query*: every divisor below is already
    applied against the per-query theta, so a batch can mix degraded
    and full-fidelity requests and each query's Prop 1-3 guarantees
    hold at its own (mu, eta). With ``mu_eta=None`` the arithmetic is
    byte-identical to the scalar path (the bit-equality tests pin it)."""
    if mu_eta is None:
        mu = jnp.float32(cfg.mu)                     # scalar
        eta = jnp.float32(cfg.eta)
        mu_s, eta_s = mu, eta                        # vs (n_q, G, n_seg)
    else:
        mu = mu_eta[:, 0:1]                          # (n_q, 1)
        eta = mu_eta[:, 1:2]
        mu_s, eta_s = mu[..., None], eta[..., None]  # (n_q, 1, 1)

    if cfg.method == "asc":
        pruned = ((max_s_w <= theta[:, None] / mu)
                  & (avg_s_w <= theta[:, None] / eta))
    else:
        pruned = key_w <= theta[:, None] / mu
    live_q = glive[None, :] & ~done[:, None]              # (n_q, G)
    horizon = budget + n_pruned
    if gate_slack is not None:
        horizon = horizon + gate_slack
    gate = rank_w < horizon[:, None]
    admit = live_q & ~pruned & gate
    cap = budget if clamp_slack is None else budget + clamp_slack
    admit &= (n_clusters[:, None]
              + jnp.cumsum(admit.astype(jnp.int32), axis=1)) <= cap
    # pruned clusters inside the horizon are budget-free: widen it
    newly_pruned = (live_q & pruned & gate).sum(axis=1).astype(jnp.int32)

    if cfg.doc_prune:
        div = eta_s if cfg.method == "asc" else mu_s
        seg_admit = seg_b_w > theta[:, None, None] / div
    else:
        seg_admit = jnp.ones_like(seg_b_w, dtype=bool)
    seg_admit = seg_admit & admit[:, :, None]
    return admit, seg_admit, newly_pruned


def _plan_admission(cfg: SearchConfig, *, cids, glive, done, theta,
                    max_s_w, avg_s_w, key_w, seg_b_w, rank_w,
                    n_clusters, n_pruned, budget, dseg_mod_w, dmask_w,
                    block_q, block_d, soff_w=None, su_w=None,
                    gate_slack=None, clamp_slack=None,
                    mu_eta=None) -> tuple[WavePlan, jax.Array]:
    """Planner half of one wave: (mu, eta)/segment admission + budget
    rank-horizon (:func:`_admission`), compacted into the wave's work
    queues (tile, query-block, and per-qblock doc-run/sub-tile levels).

    The ``_w`` arrays are already sliced to the wave: max_s_w/avg_s_w/
    key_w/rank_w (n_q, G), seg_b_w (n_q, G, n_seg), dseg_mod_w/dmask_w
    (G, d_pad), soff_w (G, n_seg + 1)/su_w (G,) the segment-major layout
    metadata. Returns (plan, n_newly_pruned)."""
    admit, seg_admit, newly_pruned = _admission(
        cfg, glive=glive, done=done, theta=theta, max_s_w=max_s_w,
        avg_s_w=avg_s_w, key_w=key_w, seg_b_w=seg_b_w, rank_w=rank_w,
        n_clusters=n_clusters, n_pruned=n_pruned, budget=budget,
        gate_slack=gate_slack, clamp_slack=clamp_slack, mu_eta=mu_eta)
    plan = plan_wave(cids, glive, admit, seg_admit, block_q,
                     dseg_mod_w, dmask_w, block_d=block_d,
                     seg_offsets=soff_w, sorted_upto=su_w,
                     union_scope=cfg.doc_union)
    return plan, newly_pruned


def resolve_score_impl(cfg: SearchConfig, n_q: int) -> str:
    """Dense scoring formulation for this (cfg, batch size): ``"auto"``
    chunks the gather+einsum above SCORE_CHUNK queries (bit-identical
    values, cache-sized intermediates — the monolithic gather goes
    memory-bound at batch 256). Trace-time (n_q is a shape), so every
    engine at the same batch size resolves identically — the
    pipelined-vs-batched bit-equality tests depend on that."""
    if cfg.score_impl != "auto":
        return cfg.score_impl
    return "chunked" if n_q > SCORE_CHUNK else "gather"


def _execute_wave(index: ClusterIndex, plan: WavePlan, qmaps: jax.Array,
                  cfg: SearchConfig, dseg_mod: jax.Array | None = None,
                  dmask: jax.Array | None = None) -> jax.Array:
    """Executor half of one wave: (n_q, G, d_pad) admission-masked scores.

    Kernel path: the Pallas executor scalar-prefetches the plan's queues
    (tile, query-block, doc sub-tile) and DMAs admitted doc sub-tiles
    straight out of the full index arrays — no XLA gather, no fetch for
    tiles/query-blocks/sub-tiles outside the queues.
    jnp path: the dense oracle, wrapped in a cond so a wave with an empty
    queue skips its gather + einsum entirely. ``dseg_mod``/``dmask``
    default to gathering from ``plan.cids`` — inside the search loop the
    identical gathers already exist in the planner's trace and XLA CSE
    dedupes them; replay callers (execute_plans) rely on the defaults."""
    with jax.named_scope("asc.execute"):
        if dseg_mod is None:
            dseg_mod = index.doc_seg_mod[plan.cids]         # (G, dp)
        if dmask is None:
            dmask = index.doc_mask[plan.cids]
        if cfg.use_kernel:
            from repro.kernels.score_cluster_batch import ops as scb_ops
            block_v = resolve_blocks(index, qmaps.shape[0], cfg)[2]
            return scb_ops.score_admitted(
                index.doc_tids, index.doc_tw, dseg_mod, dmask, qmaps, plan,
                index.scale, block_v=block_v)

        def dense(_):
            tids = index.doc_tids[plan.cids]                # (G, dp, tp)
            tw = index.doc_tw[plan.cids]
            return score_admitted_ref(tids, tw, dseg_mod, dmask, qmaps,
                                      plan, index.scale,
                                      impl=resolve_score_impl(
                                          cfg, qmaps.shape[0]))

        def empty(_):
            shape = (qmaps.shape[0], plan.cids.shape[0], index.d_pad)
            return jnp.full(shape, NEG)

        return jax.lax.cond(plan.n_blocks > 0, dense, empty, operand=None)


def _merge_wave(index: ClusterIndex, cids: jax.Array, scores: jax.Array,
                theta: jax.Array, top_scores: jax.Array, top_ids: jax.Array,
                k: int) -> tuple[jax.Array, jax.Array]:
    """Incremental threshold-filtered merge of one wave's (n_q, G, d_pad)
    scores into each query's running top-k: group candidates must beat
    the query's theta; top-k of the group, then a 2k merge — never a
    top_k over k + G*d_pad. Masked docs are NEG and theta >= NEG, so
    the theta filter subsumes the admission mask. Returns the new
    (top_scores, top_ids)."""
    n_q, G, dp = scores.shape
    kc = min(k, G * dp)
    cand = jnp.where(scores > theta[:, None, None],
                     scores, NEG).reshape(n_q, G * dp)
    g_top, g_pos = jax.lax.top_k(cand, kc)
    ids_flat = index.doc_ids[cids].reshape(-1)                # (G*dp,)
    g_ids = jnp.where(g_top > NEG, ids_flat[g_pos], -1)
    if kc < k:
        g_top = jnp.pad(g_top, ((0, 0), (0, k - kc)), constant_values=NEG)
        g_ids = jnp.pad(g_ids, ((0, 0), (0, k - kc)), constant_values=-1)
    merged_s = jnp.concatenate([top_scores, g_top], axis=1)
    merged_i = jnp.concatenate([top_ids, g_ids], axis=1)
    top_scores, sel = jax.lax.top_k(merged_s, k)              # 2k -> k
    return top_scores, jnp.take_along_axis(merged_i, sel, axis=1)


def _visit_order(order_key: jax.Array, m_padded: int) -> tuple:
    """(rank, shared_p, suffix) of the batch's shared walk.

    rank[q, c]: position of cluster c in query q's own bound order.
    Budgeted queries admit only clusters inside their own rank horizon
    ``budget + n_pruned_q``, so the shared walk spends each query's
    budget on *that query's* best clusters, and clusters the (mu, eta)
    test prunes inside the horizon extend it — the sequential semantics
    where skipped clusters are free (exact for G=1 in own order;
    docs/perf.md).

    shared_p: the shared visitation order (padded to whole waves) — fair
    interleave: a cluster's priority is the best rank any query gives
    it, so everyone's top picks land in the first groups and thetas
    rise fast for the whole batch. Ties broken by the batch-max key
    (normalized below 1 so it never reorders across priorities).

    suffix: each query's ordering key along the shared walk, suffix-
    maximized: once suffix[q, pos] <= theta_q / exit_div, *every*
    cluster query q has not yet visited is provably pruned — the
    per-query analogue of the sorted-order early exit."""
    m = order_key.shape[1]
    with jax.named_scope("asc.plan"):
        rank = jnp.argsort(jnp.argsort(-order_key, axis=1), axis=1)
        prio = rank.min(axis=0).astype(jnp.float32)              # (m,)
        tie = order_key.max(axis=0)
        tie = tie / (jnp.abs(tie).max() + 1.0)
        shared = jnp.argsort(prio - tie)                         # (m,)
        shared_p = jnp.pad(shared, (0, m_padded - m))
        key_shared = jnp.pad(order_key[:, shared],
                             ((0, 0), (0, m_padded - m)),
                             constant_values=NEG)                # (n_q, mp)
        suffix = jnp.flip(
            jax.lax.cummax(jnp.flip(key_shared, axis=1), axis=1), axis=1)
    return rank, shared_p, suffix


def _search_batch(index: ClusterIndex, qmaps: jax.Array, seg_b: jax.Array,
                  max_s: jax.Array, avg_s: jax.Array, order_key: jax.Array,
                  cfg: SearchConfig,
                  budget: jax.Array | None = None,
                  record_plans: bool = False,
                  mu_eta: jax.Array | None = None) -> tuple:
    """Batch-frontier visitation: every query walks the same cluster order,
    each wave planned (admission -> compact work queues) then executed.

    qmaps (n_q, V+1); seg_b (n_q, m, n_seg); max_s/avg_s/order_key
    (n_q, m). Returns per-query (ids, scores, counters) like the vmapped
    reference engine — each cluster tile is fetched once per *batch*,
    and only for waves/queries that admit it. With ``record_plans`` the
    per-wave :class:`WavePlan` pytrees (stacked over waves, plus an
    ``executed`` mask) ride along in the result — the benchmark's
    executor-replay hook.
    """
    m, G, k = index.m, cfg.group_size, cfg.k
    n_q = order_key.shape[0]
    n_groups = -(-m // G)
    m_padded = n_groups * G
    block_q, block_d, _ = resolve_blocks(index, n_q, cfg)
    n_qb = -(-n_q // block_q)

    budget = _resolve_budget(cfg, m, budget)
    with jax.named_scope("asc.plan"):
        if mu_eta is None:
            mu = jnp.float32(cfg.mu)
            eta = jnp.float32(cfg.eta)
        else:                            # per-request fidelity: (n_q,)
            mu, eta = mu_eta[:, 0], mu_eta[:, 1]
        exit_div = eta if cfg.method == "asc" else mu
    rank, shared_p, suffix = _visit_order(order_key, m_padded)

    def _wave_plan(state_slices) -> tuple[WavePlan, jax.Array]:
        """One wave's planning from the generic per-wave slices."""
        (cids, glive, done, theta, n_clusters, n_pruned) = state_slices
        return _plan_admission(
            cfg, cids=cids, glive=glive, done=done, theta=theta,
            max_s_w=max_s[:, cids], avg_s_w=avg_s[:, cids],
            key_w=order_key[:, cids], seg_b_w=seg_b[:, cids, :],
            rank_w=rank[:, cids], n_clusters=n_clusters,
            n_pruned=n_pruned, budget=budget,
            dseg_mod_w=index.doc_seg_mod[cids],
            dmask_w=index.doc_mask[cids], block_q=block_q,
            block_d=block_d, soff_w=index.seg_offsets[cids],
            su_w=index.sorted_upto[cids], mu_eta=mu_eta)

    first_wave = (shared_p[:G], jnp.zeros((G,), bool),
                  jnp.zeros((n_q,), bool), jnp.full((n_q,), NEG),
                  jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,),
                                                          jnp.int32))
    if record_plans:
        # stacked per-wave WavePlan buffers (bench executor-replay hook),
        # shaped from the planner's abstract signature — no dummy compute
        plan_shapes = jax.eval_shape(_wave_plan, first_wave)[0]
        zero_plan = jax.tree_util.tree_map(
            lambda s: jnp.zeros((n_groups,) + s.shape, s.dtype),
            plan_shapes)
        rec_init = (zero_plan, jnp.zeros((n_groups,), bool))
    else:
        rec_init = None

    def cond(state):
        g, done = state[0], state[1]
        with jax.named_scope("asc.merge"):
            return jnp.logical_and(g < n_groups,
                                   jnp.logical_not(jnp.all(done)))

    def body(state):
        (g, done, top_scores, top_ids,
         n_docs, n_clusters, n_segments, n_pruned,
         n_tiles_exec, n_tiles_walk, n_docs_walk, rec) = state
        # ---- plan: admission + budget horizon -> compact work queues ----
        with jax.named_scope("asc.plan"):
            theta = top_scores[:, k - 1]                      # (n_q,)
            pos = g * G
            cids = jax.lax.dynamic_slice(shared_p, (pos,), (G,))  # (G,)
            glive = (jnp.arange(G) + pos) < m                 # (G,)
            plan, newly_pruned = _wave_plan(
                (cids, glive, done, theta, n_clusters, n_pruned))
            n_pruned += newly_pruned
            admit, seg_admit = plan.admit, plan.seg_admit

        # ---- execute: score the compacted queues ----
        # Non-admitted and tombstoned docs come out exactly NEG, which is
        # the single source of truth for the work counter and the
        # candidate filter.
        scores = _execute_wave(index, plan, qmaps, cfg)

        with jax.named_scope("asc.merge"):
            doc_admit = scores > NEG                          # (n_q,G,dp)
            top_scores, top_ids = _merge_wave(
                index, plan.cids, scores, theta, top_scores, top_ids, k)

            n_docs += doc_admit.sum(axis=(1, 2)).astype(jnp.int32)
            n_clusters += admit.sum(axis=1).astype(jnp.int32)
            n_segments += seg_admit.sum(axis=(1, 2)).astype(jnp.int32)
            n_tiles_exec += plan.n_blocks
            n_tiles_walk += jnp.int32(G * n_qb)
            n_docs_walk += plan.walked_docs()

            if record_plans:
                rec = (jax.tree_util.tree_map(
                           lambda buf, x: buf.at[g].set(x), rec[0], plan),
                       rec[1].at[g].set(True))

            theta_new = top_scores[:, k - 1]
            nxt = jnp.minimum((g + 1) * G, m_padded - 1)
            remaining = jax.lax.dynamic_slice_in_dim(
                suffix, nxt, 1, axis=1)[:, 0]                 # (n_q,)
            done = (done
                    | (remaining <= theta_new / exit_div)
                    | (n_clusters >= budget))
        return (g + 1, done, top_scores, top_ids,
                n_docs, n_clusters, n_segments, n_pruned,
                n_tiles_exec, n_tiles_walk, n_docs_walk, rec)

    init = (jnp.int32(0), jnp.zeros((n_q,), bool),
            jnp.full((n_q, k), NEG), jnp.full((n_q, k), -1, jnp.int32),
            jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), jnp.int32),
            jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), rec_init)
    (g_end, _, top_scores, top_ids, n_docs, n_clusters, n_segments, _,
     n_tiles_exec, n_tiles_walk, n_docs_walk, rec) = (
        jax.lax.while_loop(cond, body, init))
    with jax.named_scope("asc.merge"):
        top_ids = jnp.where(top_scores > NEG, top_ids, -1)
        # batch-level tile/doc/wave counters, replicated per query (TopK
        # docstring)
        full = lambda v: jnp.full((n_q,), v, jnp.int32)
        out = (top_ids, top_scores, n_docs, n_clusters, n_segments,
               full(n_tiles_exec), full(n_tiles_walk), full(n_docs_walk),
               full(g_end))
    return out + (rec,) if record_plans else out


def _search_batch_super(index: ClusterIndex, qmaps: jax.Array,
                        cfg: SearchConfig,
                        budget: jax.Array | None = None,
                        mu_eta: jax.Array | None = None) -> tuple:
    """Two-level batch-frontier visitation (docs/perf.md §superblock).

    Level 0 prices the whole batch against the S coarse superblock bound
    rows up front — an O(S * V) GEMM instead of the O(m * V) fine bound
    pass — and the walk proceeds one *superblock* per wave in a shared
    fair-interleave order over superblocks. Per wave, the (mu, eta) test
    on the coarse bounds decides per query whether the superblock
    survives; only when *some* query admits it are the member clusters'
    fine bound rows gathered and priced (``lax.cond`` — a pruned
    superblock's members never touch the fine GEMM), after which the
    wave runs the exact single-level planner/executor over the members.
    Because the coarse table elementwise-dominates every member's rows
    and query maps are non-negative, a level-0 prune implies every
    member would fail the identical level-1 test — the survivor set is a
    superset of the single-level admission set, so Propositions 1-4
    apply unchanged (exact result sets at mu = eta = 1, Prop-3
    mu-approximation otherwise; pinned by
    tests/test_rank_safety_property.py::TestSuperblock*).

    Two documented semantic differences from ``_search_batch``:

      * the budget rank-horizon is positional in the *shared* walk order
        over live member slots (``live_rank``) rather than each query's
        own fine-bound rank — the per-query rank over all m clusters is
        exactly the array this engine avoids computing;
      * ``n_walked_tiles`` counts member tiles of *walked* superblocks
        only (level-0-pruned waves never walk), and the level-0 funnel
        counters are batch-level scalars replicated per query, like the
        tile counters (TopK docstring).
    """
    m, k = index.m, cfg.k
    S, cap = index.n_super, index.super_cap
    n_seg = index.n_seg
    V = index.vocab
    n_q = qmaps.shape[0]
    block_q, block_d, _ = resolve_blocks(index, n_q, cfg)
    n_qb = -(-n_q // block_q)

    budget = _resolve_budget(cfg, m, budget)
    with jax.named_scope("asc.plan"):
        if mu_eta is None:
            mu = jnp.float32(cfg.mu)
            eta = jnp.float32(cfg.eta)
        else:                            # per-request fidelity: (n_q,)
            mu, eta = mu_eta[:, 0], mu_eta[:, 1]
        exit_div = eta if cfg.method == "asc" else mu

    # ---- level 0: coarse bounds + shared superblock order ----
    with jax.named_scope("asc.bounds"):
        sup = superblock_bounds(index, qmaps, use_kernel=cfg.use_kernel)
        _, sup_max, sup_avg, sup_key = _method_stats(sup, cfg)  # (n_q, S)
    with jax.named_scope("asc.plan"):
        sup_rank = jnp.argsort(jnp.argsort(-sup_key, axis=1), axis=1)
        prio = sup_rank.min(axis=0).astype(jnp.float32)      # (S,)
        tie = sup_key.max(axis=0)
        tie = tie / (jnp.abs(tie).max() + 1.0)
        shared_s = jnp.argsort(prio - tie)                   # (S,)

        # per-query suffix max of the coarse key along the shared walk:
        # the coarse key dominates every member's key, so once the
        # suffix drops to theta/exit_div every unvisited *cluster* is
        # provably pruned — the early exit is as safe as the
        # single-level one.
        key_shared = sup_key[:, shared_s]                    # (n_q, S)
        suffix = jnp.flip(
            jax.lax.cummax(jnp.flip(key_shared, axis=1), axis=1), axis=1)

        members_ord = index.super_members[shared_s]          # (S, cap)
        mem_live = members_ord >= 0
        # budget rank-horizon for the two-level walk: global position of
        # each live member slot along the shared superblock walk (see
        # docstring)
        live_rank = (jnp.cumsum(mem_live.reshape(-1).astype(jnp.int32))
                     - 1).reshape(S, cap)
        sup_max_o = sup_max[:, shared_s]                     # (n_q, S)
        sup_avg_o = sup_avg[:, shared_s]
        sup_key_o = sup_key[:, shared_s]

    with jax.named_scope("asc.bounds"):
        qmap_v = qmaps[:, :V]

    def cond(state):
        w, done = state[0], state[1]
        with jax.named_scope("asc.merge"):
            return jnp.logical_and(w < S, jnp.logical_not(jnp.all(done)))

    def body(state):
        (w, done, top_scores, top_ids, n_docs, n_clusters, n_segments,
         n_pruned, n_tiles_exec, n_tiles_walk, n_docs_walk,
         n_bounded, n_sup_walked) = state
        with jax.named_scope("asc.plan"):
            theta = top_scores[:, k - 1]                     # (n_q,)
            members = members_ord[w]                         # (cap,)
            glive = members >= 0
            cids = jnp.where(glive, members, 0)
            rank_w = jnp.broadcast_to(live_rank[w][None], (n_q, cap))

            # level-0 admission: the identical (mu, eta) test on the
            # coarse bounds (no budget at level 0 — the horizon gates
            # members)
            if cfg.method == "asc":
                sup_pruned = ((sup_max_o[:, w] <= theta / mu)
                              & (sup_avg_o[:, w] <= theta / eta))
            else:
                sup_pruned = sup_key_o[:, w] <= theta / mu
            s_admit = ~done & ~sup_pruned                    # (n_q,)
            walked = jnp.any(s_admit)

        def heavy(args):
            (done, top_scores, top_ids, n_docs, n_clusters, n_segments,
             n_pruned, n_tiles_exec, n_docs_walk) = args
            with jax.named_scope("asc.bounds"):
                # the survivors' share of the fine bound pass: one fused
                # GEMM over this superblock's member rows only
                sub = index.seg_max_stacked[cids]    # (cap, n_seg+1, V)
                fused = _gemm_bounds(sub.reshape(cap * (n_seg + 1), V),
                                     qmap_v, index.scale, cfg.use_kernel)
                fused = fused.reshape(n_q, cap, n_seg + 1)
                if cfg.method == "asc":
                    seg_b_w = fused[..., :n_seg]
                    max_s_w = seg_b_w.max(axis=-1)
                    avg_s_w = seg_b_w.mean(axis=-1)
                    key_w = max_s_w
                else:
                    bs = fused[..., n_seg]
                    seg_b_w, max_s_w, avg_s_w, key_w = (bs[..., None], bs,
                                                        bs, bs)
                # level-0-pruned queries: force their member bounds to
                # NEG so the shared _admission registers every member as
                # pruned (valid — theta cleared the dominating coarse
                # bound, which is >= 0 >= NEG — and the budget horizon
                # bookkeeping stays identical to a wave that priced the
                # members)
                mq = s_admit[:, None]
                max_s_w = jnp.where(mq, max_s_w, NEG)
                avg_s_w = jnp.where(mq, avg_s_w, NEG)
                key_w = jnp.where(mq, key_w, NEG)
                seg_b_w = jnp.where(mq[:, :, None], seg_b_w, NEG)

            with jax.named_scope("asc.plan"):
                plan, newly_pruned = _plan_admission(
                    cfg, cids=cids, glive=glive, done=done, theta=theta,
                    max_s_w=max_s_w, avg_s_w=avg_s_w, key_w=key_w,
                    seg_b_w=seg_b_w, rank_w=rank_w, n_clusters=n_clusters,
                    n_pruned=n_pruned, budget=budget,
                    dseg_mod_w=index.doc_seg_mod[cids],
                    dmask_w=index.doc_mask[cids], block_q=block_q,
                    block_d=block_d, soff_w=index.seg_offsets[cids],
                    su_w=index.sorted_upto[cids], mu_eta=mu_eta)
                n_pruned += newly_pruned
            scores = _execute_wave(index, plan, qmaps, cfg)

            with jax.named_scope("asc.merge"):
                doc_admit = scores > NEG              # (n_q, cap, dp)
                top_scores, top_ids = _merge_wave(
                    index, plan.cids, scores, theta, top_scores, top_ids,
                    k)
                n_docs += doc_admit.sum(axis=(1, 2)).astype(jnp.int32)
                n_clusters += plan.admit.sum(axis=1).astype(jnp.int32)
                n_segments += plan.seg_admit.sum(axis=(1, 2)).astype(
                    jnp.int32)
                n_tiles_exec += plan.n_blocks
                n_docs_walk += plan.walked_docs()
                return (done, top_scores, top_ids, n_docs, n_clusters,
                        n_segments, n_pruned, n_tiles_exec, n_docs_walk,
                        glive.sum().astype(jnp.int32),
                        jnp.int32(cap * n_qb))

        def skip(args):
            (done, top_scores, top_ids, n_docs, n_clusters, n_segments,
             n_pruned, n_tiles_exec, n_docs_walk) = args
            # every live member is pruned for every not-done query
            # (dominance) — pruned clusters inside the budget horizon
            # stay budget-free, exactly as _admission would count them
            with jax.named_scope("asc.plan"):
                live_q = glive[None, :] & ~done[:, None]
                gate = rank_w < (budget + n_pruned)[:, None]
                n_pruned += (live_q & gate).sum(axis=1).astype(jnp.int32)
            return (done, top_scores, top_ids, n_docs, n_clusters,
                    n_segments, n_pruned, n_tiles_exec, n_docs_walk,
                    jnp.int32(0), jnp.int32(0))

        args = (done, top_scores, top_ids, n_docs, n_clusters,
                n_segments, n_pruned, n_tiles_exec, n_docs_walk)
        (done, top_scores, top_ids, n_docs, n_clusters, n_segments,
         n_pruned, n_tiles_exec, n_docs_walk, bounded_w, walk_w) = (
            jax.lax.cond(walked, heavy, skip, args))
        with jax.named_scope("asc.merge"):
            n_bounded += bounded_w
            n_tiles_walk += walk_w
            n_sup_walked += walked.astype(jnp.int32)

            theta_new = top_scores[:, k - 1]
            nxt = jnp.minimum(w + 1, S - 1)
            remaining = jax.lax.dynamic_slice_in_dim(
                suffix, nxt, 1, axis=1)[:, 0]                # (n_q,)
            done = (done
                    | (remaining <= theta_new / exit_div)
                    | (n_clusters >= budget))
        return (w + 1, done, top_scores, top_ids, n_docs, n_clusters,
                n_segments, n_pruned, n_tiles_exec, n_tiles_walk,
                n_docs_walk, n_bounded, n_sup_walked)

    init = (jnp.int32(0), jnp.zeros((n_q,), bool),
            jnp.full((n_q, k), NEG), jnp.full((n_q, k), -1, jnp.int32),
            jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), jnp.int32),
            jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.int32(0))
    (w_end, _, top_scores, top_ids, n_docs, n_clusters, n_segments, _,
     n_tiles_exec, n_tiles_walk, n_docs_walk, n_bounded,
     n_sup_walked) = jax.lax.while_loop(cond, body, init)
    with jax.named_scope("asc.merge"):
        top_ids = jnp.where(top_scores > NEG, top_ids, -1)
        full = lambda v: jnp.full((n_q,), v, jnp.int32)
        # early-exited tail superblocks were never walked: count as pruned
        return (top_ids, top_scores, n_docs, n_clusters, n_segments,
                full(n_tiles_exec), full(n_tiles_walk), full(n_docs_walk),
                full(w_end), full(n_bounded), full(n_sup_walked),
                full(jnp.int32(S) - n_sup_walked))


def _method_stats(stats: dict, cfg: SearchConfig) -> tuple:
    """(seg_b, max_s, avg_s, order_key) for the configured method."""
    if cfg.method == "asc":
        return (stats["segment"], stats["max_s"], stats["avg_s"],
                stats["max_s"])
    bs = stats["bound_sum"]
    return bs[..., None], bs, bs, bs


def _cluster_stats(index: ClusterIndex, queries: QueryBatch,
                   qmaps: jax.Array, cfg: SearchConfig) -> tuple:
    """The single-level bounds pass: (seg_b, max_s, avg_s, order_key)
    of every cluster for every query."""
    with jax.named_scope("asc.bounds"):
        stats = cluster_bounds(index, queries, impl=cfg.bounds_impl,
                               use_kernel=cfg.use_kernel, qmaps=qmaps)
        return _method_stats(stats, cfg)


def _retrieve_arrays(index: ClusterIndex, queries: QueryBatch,
                     cfg: SearchConfig,
                     budget: jax.Array | None = None,
                     record_plans: bool = False,
                     mu_eta: jax.Array | None = None) -> tuple:
    """Every TopK field in TopK's order, each leading n_q — plus the
    recorded wave plans as a trailing element when ``record_plans``
    (batched engine only).

    Shared by :func:`retrieve`, :func:`retrieve_with_plans` and the
    distributed shard-local search. The dense query maps are
    materialized exactly once and threaded through bound estimation
    *and* scoring."""
    with jax.named_scope("asc.bounds"):
        qmaps = queries.dense_map()                           # (n_q, V+1)
    # tiny batches can't amortize the batched planner (measured
    # regression at batch 1 — see AUTO_ENGINE_MIN_BATCH); batch size
    # is a trace-time shape, so the routing costs nothing at runtime
    engine = resolved_engine(cfg, queries.n_queries, record_plans)
    if engine == "pipelined":
        raise ValueError("engine='pipelined' is host-driven — call "
                         "retrieve_pipelined(), not retrieve()")
    if cfg.superblocks and engine == "batched":
        if record_plans:
            raise ValueError("plan recording is not supported with "
                             "superblocks=True — the two-level walk "
                             "prices members inside a lax.cond")
        # the two-level engine never runs the full O(m) bound pass:
        # it prices superblocks up front and members on admission
        return _search_batch_super(index, qmaps, cfg, budget=budget,
                                   mu_eta=mu_eta)
    seg_b, max_s, avg_s, order_key = _cluster_stats(index, queries, qmaps,
                                                    cfg)
    # single-level engines report the degenerate level-0 funnel: every
    # cluster bounded, every superblock walked, none pruned
    nq = queries.n_queries
    degenerate = (jnp.full((nq,), index.m, jnp.int32),
                  jnp.full((nq,), index.n_super, jnp.int32),
                  jnp.zeros((nq,), jnp.int32))
    if engine == "per_query":
        if record_plans:
            raise ValueError("plan recording requires engine='batched'")
        if mu_eta is None:
            fn = jax.vmap(
                lambda qmap, b, mx, av, key: _search_one_query(
                    index, qmap, b, mx, av, key, cfg, budget=budget))
            return fn(qmaps, seg_b, max_s, avg_s, order_key) + degenerate
        fn = jax.vmap(
            lambda qmap, b, mx, av, key, me: _search_one_query(
                index, qmap, b, mx, av, key, cfg, budget=budget,
                mu_eta=me))
        return (fn(qmaps, seg_b, max_s, avg_s, order_key, mu_eta)
                + degenerate)
    out = _search_batch(index, qmaps, seg_b, max_s, avg_s, order_key,
                        cfg, budget=budget, record_plans=record_plans,
                        mu_eta=mu_eta)
    if record_plans:
        return tuple(out[:-1]) + degenerate + (out[-1],)
    return out + degenerate


def _topk_of(arrays: tuple) -> TopK:
    """The TopK of a ``_retrieve_arrays`` tuple (TopK's field order)."""
    return TopK(*arrays)


@partial(jax.jit, static_argnames=("cfg",))
def retrieve(index: ClusterIndex, queries: QueryBatch,
             cfg: SearchConfig, budget: jax.Array | None = None,
             mu_eta: jax.Array | None = None) -> TopK:
    """Batched cluster-based retrieval with the configured method.

    ``budget`` (optional, traced) overrides ``cfg.cluster_budget`` without
    retracing — the serving engine's adaptive-latency knob. ``mu_eta``
    (optional, traced (n_q, 2) float32) overrides (cfg.mu, cfg.eta)
    per query, so one batch can mix full-fidelity and degraded requests
    (the streaming front-end's closed-loop ladder, docs/serving.md);
    rows must satisfy the SearchConfig invariant 0 < mu <= eta <= 1 —
    traced values cannot be validated here, callers own it."""
    return _topk_of(_retrieve_arrays(index, queries, cfg, budget=budget,
                                     mu_eta=mu_eta))


@partial(jax.jit, static_argnames=("cfg",))
def retrieve_with_plans(index: ClusterIndex, queries: QueryBatch,
                        cfg: SearchConfig,
                        budget: jax.Array | None = None
                        ) -> tuple[TopK, tuple]:
    """Batched retrieval that also returns the per-wave work queues:
    (TopK, (stacked WavePlan, executed (n_groups,) bool)). Benchmark
    instrumentation — the stacked plans replay through
    :func:`execute_plans` to time the executor in isolation."""
    *arrays, rec = _retrieve_arrays(index, queries, cfg, budget=budget,
                                    record_plans=True)
    return _topk_of(tuple(arrays)), rec


@partial(jax.jit, static_argnames=("cfg",))
def execute_plans(index: ClusterIndex, qmaps: jax.Array, plans,
                  executed: jax.Array, cfg: SearchConfig) -> jax.Array:
    """Replay the executor over recorded wave plans (no planning, no
    merge): returns the (n_q,) sum of admitted scores — a data dependency
    that forces all the scoring work. ``qmaps`` is the *precomputed*
    dense query-map block (``queries.dense_map()``): materializing it is
    planner-side work and must stay out of the replay the benchmark
    times against the full retrieve to split planner vs executor cost."""

    def step(acc, wave):
        plan, ran = wave
        scores = _execute_wave(index, plan, qmaps, cfg)
        contrib = jnp.where(scores > NEG, scores, 0.0).sum(axis=(1, 2))
        return acc + jnp.where(ran, contrib, 0.0), None

    acc, _ = jax.lax.scan(step, jnp.zeros((qmaps.shape[0],)),
                          (plans, executed))
    return acc


# ---------------------------------------------------------------------------
# Pipelined engine: device plan launches running ahead of fused executor
# launches (ISSUE 8 / docs/perf.md §device-planning).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",))
def _pipeline_prologue(index: ClusterIndex, queries: QueryBatch,
                       cfg: SearchConfig,
                       budget: jax.Array | None = None) -> tuple:
    """One launch of everything wave-independent: dense query maps, the
    stacked bounds GEMM, per-query ranks, the shared visitation order and
    its per-query suffix maxima — byte-for-byte the same arithmetic as
    the head of :func:`_search_batch` (the bit-equality tests compare the
    two engines end to end)."""
    m, G = index.m, cfg.group_size
    m_padded = -(-m // G) * G
    with jax.named_scope("asc.bounds"):
        qmaps = queries.dense_map()                           # (n_q, V+1)
    seg_b, max_s, avg_s, order_key = _cluster_stats(index, queries, qmaps,
                                                    cfg)
    rank, shared_p, suffix = _visit_order(order_key, m_padded)
    bud = _resolve_budget(cfg, m, budget)
    return qmaps, seg_b, max_s, avg_s, order_key, rank, shared_p, suffix, bud


@partial(jax.jit,
         static_argnames=("cfg", "block_q", "block_d", "n_waves"))
def _plan_launch(index: ClusterIndex, pos, shared_p, done, top_scores,
                 n_clusters, n_pruned, max_s, avg_s, order_key, seg_b,
                 rank, budget, lag_waves, cfg: SearchConfig,
                 block_q: int, block_d: int, n_waves: int = 1) -> tuple:
    """ONE device launch planning ``n_waves`` consecutive waves against
    the same (possibly lagged) carry snapshot: slice each wave from the
    shared order, run admission, and compact the full queue set
    (kernels/plan_wave). Returns ``(plans, n_blocks)`` — a tuple of
    WavePlans and their stacked block counts, the only field the host
    reads back (the wave-fusion signal and the dispatch-boundary stall
    ``planner_share`` measures). Batching waves into one launch
    amortizes the per-launch dispatch + small-op overhead that would
    otherwise dominate the plan side.

    ``lag_waves`` (traced int32) counts the waves planned-but-not-yet-
    retired when this launch is dispatched; the i-th wave of the batch
    lags by ``lag_waves + i``. Lag 0 means the carry is exact and the
    plan equals the serial planner's bit-for-bit. Lagged plans admit a
    *superset* of the exact wave (theta only lags upward,
    done/n_clusters/n_pruned only grow — the relaxed gates in
    :func:`_admission` absorb the counter drift, with slack
    ``lag * G``), and the fused executor re-derives the exact admission
    before any score escapes, so lag never changes results."""
    m, G = index.m, cfg.group_size
    plans = []
    with jax.named_scope("asc.plan"):
        for i in range(n_waves):
            pos_i = pos + jnp.int32(i * G)
            cids = jax.lax.dynamic_slice(shared_p, (pos_i,), (G,))
            glive = (jnp.arange(G) + pos_i) < m
            lag_clusters = (lag_waves + jnp.int32(i)) * jnp.int32(G)
            plan, _ = _plan_admission(
                cfg, cids=cids, glive=glive, done=done,
                theta=top_scores[:, cfg.k - 1],
                max_s_w=max_s[:, cids], avg_s_w=avg_s[:, cids],
                key_w=order_key[:, cids], seg_b_w=seg_b[:, cids, :],
                rank_w=rank[:, cids], n_clusters=n_clusters,
                n_pruned=n_pruned, budget=budget,
                dseg_mod_w=index.doc_seg_mod[cids],
                dmask_w=index.doc_mask[cids], block_q=block_q,
                block_d=block_d, soff_w=index.seg_offsets[cids],
                su_w=index.sorted_upto[cids],
                gate_slack=lag_clusters,
                clamp_slack=jnp.minimum(lag_clusters, jnp.int32(G)))
            plans.append(plan)
        n_blocks = jnp.stack([p.n_blocks for p in plans])
    return tuple(plans), n_blocks


def _exact_wave_stats(cfg: SearchConfig, admit_ex, seg_ex, glive,
                      dseg_mod, dmask, block_q: int,
                      block_d: int) -> tuple:
    """Exact per-wave work accounting (tiles, grid blocks, walked doc
    slots) recomputed from the exact admission — the same folds
    plan_wave performs, minus the queue compaction. Keeps the pipelined
    engine's counters and wave summaries bit-identical to the serial
    engine's even though the *dispatched* queues may be lagged
    supersets."""
    n_q, G = admit_ex.shape
    dp = dmask.shape[-1]
    n_seg_eff = seg_ex.shape[-1]
    n_qb = -(-n_q // block_q)
    pad = n_qb * block_q - n_q
    admit_p = jnp.pad(admit_ex, ((0, pad), (0, 0))) if pad else admit_ex
    seg_p = jnp.pad(seg_ex, ((0, pad), (0, 0), (0, 0))) if pad else seg_ex
    seg_qb = seg_p.reshape(n_qb, block_q, G, n_seg_eff).any(axis=1)
    if cfg.doc_union == "batch":
        seg_qb = jnp.broadcast_to(seg_qb.any(axis=0, keepdims=True),
                                  seg_qb.shape)
    dmask_qb = _union_doc_admission(seg_qb, dseg_mod, dmask)  # (n_qb,G,dp)
    blk_any = admit_p.reshape(n_qb, block_q, G).any(axis=1)   # (n_qb, G)
    tile_keep = (admit_ex.any(axis=0) & glive
                 & dmask_qb.any(axis=0).any(axis=-1))         # (G,)
    blk_live = blk_any & dmask_qb.any(axis=-1) & tile_keep[None, :]
    n_db = dp // block_d
    sub_any = dmask_qb.reshape(n_qb, G, n_db, block_d).any(axis=-1)
    walked = ((sub_any & blk_live[..., None]).sum() * block_d)
    return (tile_keep.sum().astype(jnp.int32),
            blk_live.sum().astype(jnp.int32), walked.astype(jnp.int32))


@partial(jax.jit, static_argnames=("cfg",))
def _exec_fused(index: ClusterIndex, qmaps: jax.Array, plans: tuple,
                real: jax.Array, nxt: jax.Array, carry: tuple,
                max_s, avg_s, order_key, seg_b, rank, suffix, budget,
                cfg: SearchConfig) -> tuple:
    """ONE executor launch retiring F (= ``len(plans)``, static via the
    plan-tuple pytree structure — one compiled variant per fused width)
    consecutive waves against their dispatched (possibly theta-lagged)
    queues. ``plans`` is a tuple of F WavePlans: keeping the tuple
    un-stacked pushes the per-field batching out of the host's eager
    dispatch path (stacking 20+ queue fields per launch op-by-op cost
    more host time than the launch itself).

    Per wave, in order: re-derive the *exact* admission from the live
    carry (:func:`_admission`, slack-free — cheap elementwise bound
    math, no compaction), score via the dispatched queues, mask with the
    exact admission (a subset of what the lagged queues visit, so every
    admitted score was computed), then the identical threshold-filtered
    merge / counter / early-exit updates as :func:`_search_batch` — all
    gated on ``wave_on`` (a real wave, not yet all-done) so padding
    waves and post-exit dispatches are no-ops. Results and every counter
    are bit-identical to the serial engine; the only superset is the
    *work actually performed* on the lagged queues, which produces only
    masked output.

    Returns (carry', all_done, per-wave exact stats arrays)."""
    G, k = cfg.group_size, cfg.k
    n_q = qmaps.shape[0]
    F = len(plans)
    block_q, block_d = plans[0].block_q, plans[0].block_d
    n_qb = -(-n_q // block_q)
    exit_div = jnp.float32(cfg.eta if cfg.method == "asc" else cfg.mu)

    (done, top_scores, top_ids, n_docs, n_clusters, n_segments, n_pruned,
     n_tiles_exec, n_tiles_walk, n_docs_walk) = carry
    w_tiles, w_blocks, w_pairs, w_segs, w_slots, w_on = [], [], [], [], [], []

    for f in range(F):
        plan = plans[f]
        with jax.named_scope("asc.plan"):
            wave_on = real[f] & ~jnp.all(done)
            theta = top_scores[:, k - 1]
            cids = plan.cids
            dseg_mod = index.doc_seg_mod[cids]               # (G, dp)
            dmask = index.doc_mask[cids]
            admit_ex, seg_ex, newly_pruned = _admission(
                cfg, glive=plan.live, done=done, theta=theta,
                max_s_w=max_s[:, cids], avg_s_w=avg_s[:, cids],
                key_w=order_key[:, cids], seg_b_w=seg_b[:, cids, :],
                rank_w=rank[:, cids], n_clusters=n_clusters,
                n_pruned=n_pruned, budget=budget)

        raw = _execute_wave(index, plan, qmaps, cfg, dseg_mod, dmask)
        with jax.named_scope("asc.execute"):
            exact_plan = dataclasses.replace(plan, admit=admit_ex,
                                             seg_admit=seg_ex)
            mask_ex = doc_admission(exact_plan, dseg_mod, dmask)
            scores = jnp.where(mask_ex, raw, NEG)            # (n_q,G,dp)

        with jax.named_scope("asc.merge"):
            new_ts, new_ti = _merge_wave(index, cids, scores, theta,
                                         top_scores, top_ids, k)
            top_scores = jnp.where(wave_on, new_ts, top_scores)
            top_ids = jnp.where(wave_on, new_ti, top_ids)

            upd = lambda old, inc: old + jnp.where(wave_on, inc, 0)
            n_docs = upd(n_docs, (scores > NEG).sum(axis=(1, 2))
                         .astype(jnp.int32))
            n_clusters = upd(n_clusters,
                             admit_ex.sum(axis=1).astype(jnp.int32))
            n_segments = upd(n_segments,
                             seg_ex.sum(axis=(1, 2)).astype(jnp.int32))
            n_pruned = upd(n_pruned, newly_pruned)
            tiles_ex, blocks_ex, slots_ex = _exact_wave_stats(
                cfg, admit_ex, seg_ex, plan.live, dseg_mod, dmask,
                block_q, block_d)
            n_tiles_exec = upd(n_tiles_exec, blocks_ex)
            n_tiles_walk = upd(n_tiles_walk, jnp.int32(G * n_qb))
            n_docs_walk = upd(n_docs_walk, slots_ex)

            theta_new = top_scores[:, k - 1]
            remaining = jax.lax.dynamic_slice_in_dim(
                suffix, nxt[f], 1, axis=1)[:, 0]
            done_new = (done
                        | (remaining <= theta_new / exit_div)
                        | (n_clusters >= budget))
            done = jnp.where(wave_on, done_new, done)

            z = jnp.int32(0)
            w_tiles.append(jnp.where(wave_on, tiles_ex, z))
            w_blocks.append(jnp.where(wave_on, blocks_ex, z))
            w_pairs.append(jnp.where(wave_on,
                                     admit_ex.sum().astype(jnp.int32), z))
            w_segs.append(jnp.where(wave_on,
                                    seg_ex.sum().astype(jnp.int32), z))
            w_slots.append(jnp.where(wave_on, slots_ex, z))
            w_on.append(wave_on)

    carry = (done, top_scores, top_ids, n_docs, n_clusters, n_segments,
             n_pruned, n_tiles_exec, n_tiles_walk, n_docs_walk)
    stats = {"tiles": jnp.stack(w_tiles), "blocks": jnp.stack(w_blocks),
             "pairs": jnp.stack(w_pairs), "segments": jnp.stack(w_segs),
             "slots": jnp.stack(w_slots), "on": jnp.stack(w_on)}
    return carry, jnp.all(done), stats


def _pipeline_init_carry(n_q: int, k: int) -> tuple:
    return (jnp.zeros((n_q,), bool),
            jnp.full((n_q, k), NEG), jnp.full((n_q, k), -1, jnp.int32),
            jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), jnp.int32),
            jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0))


def _fuse_size(n: int) -> int:
    """Static fused-launch width covering n pending waves (1, 2 or 4 —
    one compiled _exec_fused variant per width)."""
    return 1 if n <= 1 else (2 if n == 2 else 4)


def retrieve_pipelined(index: ClusterIndex, queries: QueryBatch,
                       cfg: SearchConfig,
                       budget: jax.Array | None = None,
                       with_info: bool = False):
    """Host-driven plan/execute pipeline: the batched walk with device
    wave planning, theta-lag plan-ahead, and fused executor launches.

    The dispatch loop keeps three frontiers:

      * ``stale`` — the carry of the last *retired* executor launch; all
        plan launches read it (never the in-flight launch's output, so a
        plan dispatch has no data dependency on the running executor —
        on an async backend the two genuinely overlap);
      * ``inflight`` — the dispatched-but-unretired executor launch; its
        carry feeds the *next* executor launch directly (the exact state
        chain never leaves the device);
      * ``pending`` — waves planned against ``stale`` (lag = inflight
        waves + pending waves, passed to the plan launch as
        ``lag_clusters``), fused into the next executor launch once they
        accumulate ~half a wave's worth of grid blocks or ``fuse_waves``
        of them pile up.

    Results, counters and per-wave summaries are bit-identical to
    ``engine="batched"`` (pinned by tests/test_rank_safety_property.py).
    With ``with_info`` returns ``(TopK, info)`` where info carries the
    dispatch-boundary timings (``plan_ms`` = stalls fetching plan queue
    lengths, ``exec_ms`` = stalls retiring executor launches), launch
    counts (``plan_launches``/``exec_launches``/``fused_waves``) and the
    exact per-wave ``summaries`` (same schema as
    :func:`repro.core.plan.wave_summaries`)."""
    import time as _time

    if cfg.superblocks:
        raise ValueError("superblocks=True requires the batched "
                         "engine — the pipelined dispatch loop plans "
                         "against the full cluster order")
    n_q = queries.n_queries
    m, G, k = index.m, cfg.group_size, cfg.k
    n_groups = -(-m // G)
    block_q, block_d, _ = resolve_blocks(index, n_q, cfg)
    n_qb = -(-n_q // block_q)
    f_max = 4 if cfg.fuse_waves == "auto" else cfg.fuse_waves
    f_max = max(1, min(f_max, n_groups))
    # fuse while the pending waves stay under ~half a full wave's grid
    # blocks: low-admission waves pack together, a busy wave ships alone
    flush_blocks = max(G * n_qb // 2, 1)

    t0 = _time.perf_counter()
    pro = _pipeline_prologue(index, queries, cfg, budget=budget)
    (qmaps, seg_b, max_s, avg_s, order_key, rank, shared_p, suffix,
     bud) = pro
    jax.block_until_ready(shared_p)
    plan_ms = (_time.perf_counter() - t0) * 1e3
    exec_ms = 0.0
    plan_launches = exec_launches = fused_waves = n_waves = 0

    stale = _pipeline_init_carry(n_q, k)
    inflight = None          # (carry, all_done, stats, wave_ids)
    pending: list[tuple[WavePlan, int]] = []
    pending_blocks = 0
    summaries: list[dict] = []
    empty_plan = None
    stop = False

    def retire():
        """Block on the in-flight executor launch; fold its per-wave
        exact stats into the summaries."""
        nonlocal inflight, stale, exec_ms, stop
        if inflight is None:
            return
        carry, all_done, stats, wave_ids = inflight
        t0 = _time.perf_counter()
        stop = bool(all_done)
        stats = {key: np.asarray(v) for key, v in stats.items()}
        exec_ms += (_time.perf_counter() - t0) * 1e3
        for f, g in enumerate(wave_ids):
            if stats["on"][f]:
                summaries.append({
                    "wave": int(g),
                    "tiles_admitted": int(stats["tiles"][f]),
                    "grid_blocks": int(stats["blocks"][f]),
                    "admitted_pairs": int(stats["pairs"][f]),
                    "admitted_segments": int(stats["segments"][f]),
                    "walked_doc_slots": int(stats["slots"][f]),
                })
        stale = carry
        inflight = None

    def dispatch():
        """Fuse the pending plans into one executor launch."""
        nonlocal inflight, pending, pending_blocks
        nonlocal exec_launches, fused_waves, n_waves, empty_plan
        if not pending:
            return
        n_real = len(pending)
        F = _fuse_size(n_real)
        if empty_plan is None:
            empty_plan = jax.tree_util.tree_map(jnp.zeros_like,
                                                pending[0][0])
        wave_ids = [g for _, g in pending]
        plans = tuple(p for p, _ in pending) \
            + (empty_plan,) * (F - n_real)
        real = np.array([True] * n_real + [False] * (F - n_real))
        m_padded = n_groups * G
        nxt = np.array([min((g + 1) * G, m_padded - 1)
                        for g in wave_ids]
                       + [0] * (F - n_real), np.int32)
        carry_in = inflight[0] if inflight is not None else stale
        # retire the previous launch *after* reading its carry handle —
        # the exec chain stays on device, the host only syncs lengths
        retire()
        out = _exec_fused(index, qmaps, plans, real, nxt, carry_in,
                          max_s, avg_s, order_key, seg_b, rank, suffix,
                          bud, cfg)
        inflight = (out[0], out[1], out[2], wave_ids)
        exec_launches += 1
        n_waves += n_real
        if n_real > 1:
            fused_waves += n_real
        pending = []
        pending_blocks = 0

    g = 0
    while g < n_groups and not stop:
        P = min(f_max, n_groups - g)
        lag_waves = ((len(inflight[3]) if inflight is not None else 0)
                     + len(pending))
        t0 = _time.perf_counter()
        plans, nb_dev = _plan_launch(
            index, np.int32(g * G), shared_p, stale[0], stale[1],
            stale[4], stale[6], max_s, avg_s, order_key, seg_b, rank,
            bud, np.int32(lag_waves), cfg, block_q, block_d, n_waves=P)
        plan_ms += (_time.perf_counter() - t0) * 1e3
        plan_launches += 1
        # retire the in-flight executor *before* stalling on the plan's
        # queue lengths: device streams are ordered, so the stall below
        # would otherwise absorb all previously-queued executor work and
        # misattribute it to the planner (the plan launch is already
        # dispatched above — on an async backend it overlaps the
        # executor either way, this only reorders the host's waits)
        retire()
        if stop:
            break
        t0 = _time.perf_counter()
        nbs = np.asarray(nb_dev)      # the dispatch-boundary stall
        plan_ms += (_time.perf_counter() - t0) * 1e3
        for i in range(P):
            pending.append((plans[i], g + i))
            pending_blocks += int(nbs[i])
            if (len(pending) >= f_max
                    or pending_blocks >= flush_blocks
                    or g + i + 1 >= n_groups):
                dispatch()
        g += P
    if not stop:
        dispatch()   # waves planned after the last flush (early exit
                     # leaves pending plans undispatched — they would
                     # only execute as gated no-ops)
    retire()

    (done, top_scores, top_ids, n_docs, n_clusters, n_segments, _,
     n_tiles_exec, n_tiles_walk, n_docs_walk) = stale
    top_ids = jnp.where(top_scores > NEG, top_ids, -1)
    full = lambda v: jnp.full((n_q,), v, jnp.int32)
    topk = TopK(doc_ids=top_ids, scores=top_scores, n_scored_docs=n_docs,
                n_scored_clusters=n_clusters, n_scored_segments=n_segments,
                n_scored_tiles=full(n_tiles_exec),
                n_walked_tiles=full(n_tiles_walk),
                n_walked_docs=full(n_docs_walk),
                n_waves=full(n_waves),
                n_bounded_clusters=full(m),
                n_walked_superblocks=full(index.n_super),
                n_pruned_superblocks=full(0))
    if not with_info:
        return topk
    info = {
        "plan_ms": plan_ms, "exec_ms": exec_ms,
        "plan_launches": plan_launches, "exec_launches": exec_launches,
        "fused_waves": fused_waves, "summaries": summaries,
    }
    return topk, info


# jitted once at module level: re-jitting a fresh lambda per call would
# re-trace the dense-map build every time the split seam is used
_dense_map_jit = jax.jit(lambda q: q.dense_map())


def planner_executor_split(index: ClusterIndex, queries: QueryBatch,
                           cfg: SearchConfig,
                           budget: jax.Array | None = None,
                           reps: int = 1,
                           total_ms: float | None = None) -> tuple:
    """The planner-vs-executor **timing seam** (host-side, blocking).
    Used by the serving engine's sampled split requests (repro.obs) and
    by benchmarks/serve_throughput.py — one seam, one definition of
    "planner share" per engine, and one return shape:
    ``(topk, waves, split)`` where ``waves`` is the per-wave exact
    admission summary list (:func:`repro.core.plan.wave_summaries`
    schema) and ``split`` carries ``total_ms`` / ``executor_ms`` /
    ``planner_ms`` / ``planner_share``.

    * batched/per-query engines: one plan-recording retrieval
      (:func:`retrieve_with_plans`) plus a timed executor-only replay
      (:func:`execute_plans`) of the recorded work queues; planner time
      is the non-replayable remainder of ``total_ms``.
    * pipelined engine: the split is measured **at the dispatch
      boundary** — ``planner_ms`` is the sum of host stalls fetching
      each device plan launch's queue lengths (plus the prologue
      bounds-GEMM launch), ``executor_ms`` the stalls retiring executor
      launches. Host queue materialization no longer exists, so nothing
      host-side is misattributed to the planner; the split additionally
      reports ``plan_launches`` / ``exec_launches`` / ``fused_waves``.

    ``total_ms`` — caller-measured end-to-end p50 for the same
    (index, queries, cfg); when None the walk itself is timed over
    ``reps``. Both halves are compiled (warmed) before any timing."""
    import time as _time

    import numpy as _np

    from repro.core.plan import wave_summaries

    if resolved_engine(cfg, queries.n_queries) == "pipelined":
        jax.block_until_ready(
            retrieve_pipelined(index, queries, cfg, budget=budget))  # warm
        plan_l, exec_l, tot_l = [], [], []
        topk = info = None
        for _ in range(max(reps, 1)):
            t0 = _time.perf_counter()
            topk, info = retrieve_pipelined(index, queries, cfg,
                                            budget=budget, with_info=True)
            jax.block_until_ready(topk)
            tot_l.append((_time.perf_counter() - t0) * 1e3)
            plan_l.append(info["plan_ms"])
            exec_l.append(info["exec_ms"])
        if total_ms is None:
            total_ms = float(_np.median(tot_l))
        planner_ms = float(_np.median(plan_l))
        executor_ms = float(_np.median(exec_l))
        split = {
            "total_ms": total_ms,
            "executor_ms": executor_ms,
            "planner_ms": planner_ms,
            "planner_share": planner_ms / max(total_ms, 1e-9),
            "plan_launches": info["plan_launches"],
            "exec_launches": info["exec_launches"],
            "fused_waves": info["fused_waves"],
        }
        return topk, info["summaries"], split

    # warm / compile both halves and materialize the recorded plans
    topk, (plans, executed) = jax.block_until_ready(
        retrieve_with_plans(index, queries, cfg, budget=budget))
    qmaps = jax.block_until_ready(_dense_map_jit(queries))
    jax.block_until_ready(
        execute_plans(index, qmaps, plans, executed, cfg))
    if total_ms is None:
        lat = []
        for _ in range(max(reps, 1)):
            t0 = _time.perf_counter()
            jax.block_until_ready(
                retrieve_with_plans(index, queries, cfg, budget=budget))
            lat.append(_time.perf_counter() - t0)
        total_ms = float(_np.median(lat)) * 1e3
    lat = []
    for _ in range(max(reps, 1)):
        t0 = _time.perf_counter()
        jax.block_until_ready(
            execute_plans(index, qmaps, plans, executed, cfg))
        lat.append(_time.perf_counter() - t0)
    executor_ms = float(_np.median(lat)) * 1e3
    planner_ms = max(total_ms - executor_ms, 0.0)
    split = {
        "total_ms": total_ms,
        "executor_ms": executor_ms,
        "planner_ms": planner_ms,
        "planner_share": planner_ms / max(total_ms, 1e-9),
    }
    return topk, wave_summaries(plans, executed), split


def asc_retrieve(index: ClusterIndex, queries: QueryBatch, k: int,
                 mu: float = 1.0, eta: float = 1.0, **kw) -> TopK:
    return retrieve(index, queries,
                    SearchConfig(k=k, mu=mu, eta=eta, method="asc", **kw))


def anytime_retrieve(index: ClusterIndex, queries: QueryBatch, k: int,
                     mu: float = 1.0, cluster_budget: int | None = None,
                     **kw) -> TopK:
    method = "anytime" if mu == 1.0 else "anytime_star"
    return retrieve(index, queries,
                    SearchConfig(k=k, mu=mu, eta=mu, method=method,
                                 cluster_budget=cluster_budget, **kw))
