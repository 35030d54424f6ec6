"""Plan/execute batched engine: executor equivalence + rank safety.

Two layers of guarantees:

  * the work-queue executor (kernels/score_cluster_batch, Pallas + jnp
    ref) must reproduce ``score_docs_ref`` exactly for every admitted
    (query, doc) pair, and emit NEG for tombstoned docs, docs in
    non-admitted segments, (query, cluster) pairs the planner rejected,
    and tiles absent from the compacted queue (which never enter the
    kernel grid at all);
  * batched retrieval must return the same top-k result sets as the
    per-query reference engine at mu = eta = 1, and keep the paper's
    mu-approximation invariant (Prop 3) for mu < eta < 1 — the shared
    visitation order updates each query's theta no more often than the
    sequential walk, so pruning is never more aggressive.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.core.index import build_index
from repro.core.plan import plan_wave
from repro.core.search import (SearchConfig, brute_force_topk, retrieve,
                               score_docs_ref)
from repro.data.synthetic import CorpusSpec, make_corpus, make_queries
from repro.kernels.score_cluster_batch import ops as scb_ops

NEG_F = float(jnp.finfo(jnp.float32).min)


def _mk_plan(index, cids, seg_admit, block_q, block_d=None, live=None):
    """Wave plan from a raw (n_q, G, n_seg) segment-admission mask (a
    (query, tile) pair is admitted iff any of its segments is)."""
    cids = jnp.asarray(cids, jnp.int32)
    admit = jnp.asarray(seg_admit).any(axis=-1)
    if live is None:
        live = jnp.ones((cids.shape[0],), bool)
    return plan_wave(cids, live, admit, jnp.asarray(seg_admit), block_q,
                     index.doc_seg_mod[cids], index.doc_mask[cids],
                     block_d=block_d, seg_offsets=index.seg_offsets[cids],
                     sorted_upto=index.sorted_upto[cids])


def _scorer_expected(index, cids, qmaps, seg_admit):
    """Oracle: per-(query, doc) score_docs_ref + admission masking."""
    tids, tw = index.doc_tids[cids], index.doc_tw[cids]
    dseg, dmask = index.doc_seg[cids], index.doc_mask[cids]
    per_doc = jax.vmap(
        lambda qm: score_docs_ref(tids, tw, qm, index.scale))(qmaps)
    n_seg = seg_admit.shape[-1]
    admitted = (dmask[None]
                & jnp.asarray(seg_admit).any(-1)[:, :, None]
                & jnp.take_along_axis(
                    jnp.asarray(seg_admit), (dseg % n_seg)[None], axis=2))
    return np.asarray(admitted), np.asarray(per_doc)


def _check_scorer(index, cids, qmaps, seg_admit, block_q=8, block_v=None,
                  block_d=None):
    cids = jnp.asarray(cids, jnp.int32)
    dseg, dmask = index.doc_seg_mod[cids], index.doc_mask[cids]
    tids, tw = index.doc_tids[cids], index.doc_tw[cids]
    plan = _mk_plan(index, cids, seg_admit, block_q, block_d=block_d)
    admitted, expect = _scorer_expected(index, cids, qmaps, seg_admit)
    for impl, out in [
        ("ref", scb_ops.score_admitted_ref(
            tids, tw, dseg, dmask, qmaps, plan, index.scale)),
        ("runs_ref", scb_ops.score_runs_ref(
            tids, tw, dseg, dmask, qmaps, plan, index.scale)),
        ("kernel", scb_ops.score_admitted(
            index.doc_tids, index.doc_tw, dseg, dmask, qmaps, plan,
            index.scale, block_v=block_v)),
    ]:
        out = np.asarray(out)
        np.testing.assert_allclose(
            out[admitted], expect[admitted], rtol=1e-5, atol=1e-5,
            err_msg=f"{impl}: admitted scores diverge from score_docs_ref")
        assert (out[~admitted] == NEG_F).all(), \
            f"{impl}: masked docs must come out exactly NEG"


def test_batch_scorer_matches_score_docs_ref(index, queries):
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(6)
    rng = np.random.default_rng(0)
    seg_admit = jnp.asarray(
        rng.random((q.n_queries, 6, index.n_seg)) < 0.6)
    _check_scorer(index, cids, qmaps, seg_admit)


def test_batch_scorer_fully_pruned_tiles(index, queries):
    """A tile no query admits never enters the compacted queue: all its
    outputs are NEG and the plan's queue is shorter than the wave."""
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(4)
    seg_admit = np.ones((q.n_queries, 4, index.n_seg), bool)
    seg_admit[:, 1] = False          # nobody admits cluster 1
    seg_admit[:, 3] = False
    seg_admit = jnp.asarray(seg_admit)
    _check_scorer(index, cids, qmaps, seg_admit)
    plan = _mk_plan(index, cids, seg_admit, block_q=8)
    assert int(plan.n_tiles) == 2
    np.testing.assert_array_equal(np.asarray(plan.tile_cids)[:2], [0, 2])
    out = np.asarray(scb_ops.score_admitted(
        index.doc_tids, index.doc_tw, index.doc_seg_mod[cids],
        index.doc_mask[cids], qmaps, plan, index.scale))
    assert (out[:, 1] == NEG_F).all() and (out[:, 3] == NEG_F).all()


def test_batch_scorer_tombstoned_docs(index, queries):
    """Tombstones (doc_mask False) are masked even in admitted segments."""
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(4)
    rng = np.random.default_rng(1)
    dead = rng.random(np.asarray(index.doc_mask).shape) < 0.3
    tomb = index.replace(
        doc_mask=jnp.asarray(np.asarray(index.doc_mask) & ~dead))
    seg_admit = jnp.ones((q.n_queries, 4, index.n_seg), bool)
    _check_scorer(tomb, cids, qmaps, seg_admit)


def test_all_segments_admitted_equals_plain_scoring(index, queries):
    """With everything admitted the scorer is exactly score_docs_ref +
    liveness masking (no hidden scaling/masking surprises)."""
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(index.m)
    seg_admit = jnp.ones((q.n_queries, index.m, index.n_seg), bool)
    _check_scorer(index, cids, qmaps, seg_admit)


def test_executor_query_blocking_invariant(index, queries):
    """The executor result is invariant to the query-block size (blocks
    with no admitting query are skipped, not dropped)."""
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(6)
    rng = np.random.default_rng(7)
    # sparse admission so several query blocks are empty per tile
    seg_admit = jnp.asarray(
        rng.random((q.n_queries, 6, index.n_seg)) < 0.15)
    outs = {}
    for bq in (1, 4, q.n_queries, 2 * q.n_queries):
        plan = _mk_plan(index, cids, seg_admit, block_q=bq)
        outs[bq] = np.asarray(scb_ops.score_admitted(
            index.doc_tids, index.doc_tw, index.doc_seg_mod[cids],
            index.doc_mask[cids], qmaps, plan, index.scale))
    base = outs.popitem()[1]
    for bq, out in outs.items():
        np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6,
                                   err_msg=f"block_q={bq} diverges")


def test_executor_doc_blocking_invariant(index, queries):
    """The executor result is invariant to the doc sub-tile size (sub-
    tiles no admitted run intersects are skipped, not dropped)."""
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(6)
    rng = np.random.default_rng(13)
    # sparse admission so many doc sub-tiles are empty per tile
    seg_admit = jnp.asarray(
        rng.random((q.n_queries, 6, index.n_seg)) < 0.25)
    dp = index.d_pad
    outs = {}
    for bd in (1, 4, 16, dp, None):
        plan = _mk_plan(index, cids, seg_admit, block_q=8, block_d=bd)
        outs[bd] = np.asarray(scb_ops.score_admitted(
            index.doc_tids, index.doc_tw, index.doc_seg_mod[cids],
            index.doc_mask[cids], qmaps, plan, index.scale))
    base = outs.popitem()[1]
    for bd, out in outs.items():
        np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6,
                                   err_msg=f"block_d={bd} diverges")


def test_doc_runs_encode_union_admission(index, queries):
    """The plan's per-(tile, qblock) run queues cover exactly that query
    block's union doc-admission mask (a superset is allowed only on
    tombstoned slots inside admitted segments — the segment-major runs
    span whole segments), and the sub-tile queue covers the union."""
    from repro.core.plan import runs_to_mask
    from repro.kernels.score_cluster_batch.ref import walked_doc_slots
    q, _ = queries
    block_q = 4
    n_qb = -(-q.n_queries // block_q)
    cids = jnp.arange(8)
    rng = np.random.default_rng(5)
    seg_admit = jnp.asarray(
        rng.random((q.n_queries, 8, index.n_seg)) < 0.2)
    plan = _mk_plan(index, cids, seg_admit, block_q=block_q, block_d=8)
    n_tiles = int(plan.n_tiles)
    tile_pos = np.asarray(plan.tile_pos)
    dseg = np.asarray(index.doc_seg_mod[cids])
    dmask = np.asarray(index.doc_mask[cids])
    seg_qb = np.asarray(seg_admit).reshape(
        n_qb, block_q, 8, index.n_seg).any(axis=1)        # (n_qb, G, s)
    from_runs = np.asarray(runs_to_mask(
        plan.drun_start, plan.drun_len, plan.n_drun,
        index.d_pad))                                     # (G, n_qb, dp)
    walked = np.asarray(walked_doc_slots(plan))           # raw-qb space
    qblock = np.asarray(plan.qblock)
    n_qblock = np.asarray(plan.n_qblock)
    for g in range(n_tiles):
        wp = tile_pos[g]
        for s in range(n_qblock[g]):
            b = qblock[g, s]
            union = dmask[wp] & seg_qb[b, wp][dseg[wp]]
            runs = from_runs[g, s]
            # runs cover the union; anything extra is a dead slot in an
            # admitted segment (never a live doc outside the union)
            assert (union <= runs).all(), (g, s)
            extra = runs & ~union
            assert not (extra & dmask[wp]).any(), (g, s)
            # the committed residual mask is the exact union
            np.testing.assert_array_equal(
                np.asarray(plan.dmask_union)[g, s], union)
            # every admitted doc lies in a walked sub-tile of its own
            # query block (rank safety of per-qblock doc compaction)
            assert (union <= walked[g, b]).all(), (g, s)
    assert (np.asarray(plan.n_dblock) <= plan.n_db).all()


def test_per_qblock_queues_skip_more_than_batch_union(index, queries):
    """A block whose queries admit few segments walks fewer doc slots
    under per-qblock unions than under the replicated batch union."""
    q, _ = queries
    cids = jnp.arange(8)
    rng = np.random.default_rng(17)
    seg_admit = jnp.asarray(
        rng.random((q.n_queries, 8, index.n_seg)) < 0.2)
    admit = seg_admit.any(-1)
    live = jnp.ones((8,), bool)
    from repro.core.plan import plan_wave
    walked = {}
    for scope in ("qblock", "batch"):
        plan = plan_wave(cids, live, admit, seg_admit, 4,
                         index.doc_seg_mod[cids], index.doc_mask[cids],
                         block_d=8, seg_offsets=index.seg_offsets[cids],
                         sorted_upto=index.sorted_upto[cids],
                         union_scope=scope)
        walked[scope] = int(plan.walked_docs())
    assert walked["qblock"] <= walked["batch"]
    assert walked["qblock"] < walked["batch"], (
        "per-qblock unions should skip sub-tiles the batch union keeps")


def test_doc_subtile_skipping_dead_tail(index, queries):
    """A tile whose trailing slots are all tombstoned drops its trailing
    doc sub-tiles from every query block's queue, and scores stay
    exact."""
    from repro.core.plan import resolve_block_d
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(4)
    dp = index.d_pad
    bd = resolve_block_d(dp, 8)              # the size the plan will use
    keep = dp // 2 - (dp // 2) % bd          # kill an aligned tail
    mask = np.asarray(index.doc_mask).copy()
    mask[np.asarray(cids), keep:] = False
    tomb = index.replace(doc_mask=jnp.asarray(mask))
    seg_admit = jnp.ones((q.n_queries, 4, index.n_seg), bool)
    _check_scorer(tomb, cids, qmaps, seg_admit, block_d=bd)
    plan = _mk_plan(tomb, cids, seg_admit, block_q=8, block_d=bd)
    n_tiles = int(plan.n_tiles)
    assert n_tiles == 4
    nqb = np.asarray(plan.n_qblock)
    ndb = np.asarray(plan.n_dblock)
    for g in range(n_tiles):
        assert (ndb[g, :nqb[g]] <= keep // bd).all()
    assert int(plan.walked_docs()) < int(plan.n_blocks) * dp


def test_executor_vocab_blocking_invariant(index, queries):
    """Chunking the dense-map gather over the vocab axis accumulates to
    the same scores as the single full-V gather."""
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(5)
    rng = np.random.default_rng(11)
    seg_admit = jnp.asarray(
        rng.random((q.n_queries, 5, index.n_seg)) < 0.5)
    _check_scorer(index, cids, qmaps, seg_admit, block_v=128)
    _check_scorer(index, cids, qmaps, seg_admit, block_v=193)


def test_empty_wave_is_all_neg(index, queries):
    """A wave with no admitted pair at all stays exactly NEG everywhere
    (the executor grid does no real work; masking covers the garbage)."""
    q, _ = queries
    qmaps = q.dense_map()
    cids = jnp.arange(4)
    seg_admit = jnp.zeros((q.n_queries, 4, index.n_seg), bool)
    plan = _mk_plan(index, cids, seg_admit, block_q=8)
    assert int(plan.n_tiles) == 0 and int(plan.n_blocks) == 0
    assert int(plan.walked_docs()) == 0
    out = np.asarray(scb_ops.score_admitted(
        index.doc_tids, index.doc_tw, index.doc_seg_mod[cids],
        index.doc_mask[cids], qmaps, plan, index.scale))
    assert (out == NEG_F).all()


# ---------------------------------------------------------------------------
# batched engine vs per-query reference
# ---------------------------------------------------------------------------

_GRID_CACHE: dict = {}


def _grid_fixture():
    if not _GRID_CACHE:
        spec = CorpusSpec(n_docs=1200, vocab=384, n_topics=12, seed=42)
        docs, doc_topic = make_corpus(spec)
        q, _ = make_queries(spec, 8, doc_topic, seed=43)
        idx = build_index(docs, doc_topic % 16, m=16, n_seg=4, seed=44)
        _GRID_CACHE["v"] = (idx, q)
    return _GRID_CACHE["v"]


@settings(max_examples=16, deadline=None)
@given(
    mu=st.sampled_from([0.3, 0.6, 0.9, 1.0]),
    eta=st.sampled_from([0.7, 0.9, 1.0]),
    k=st.sampled_from([5, 10]),
    method=st.sampled_from(["asc", "anytime_star"]),
)
def test_batched_vs_reference_random_mu_eta(mu, eta, k, method):
    """Random (mu, eta) grid: identical result sets at mu = eta = 1; the
    Prop-3 mu-approximation bound for both engines otherwise."""
    if mu > eta:
        mu = eta
    if method == "anytime_star":
        eta = mu                      # anytime* collapses the two knobs
    idx, q = _grid_fixture()
    outs = {}
    for engine in ("batched", "per_query"):
        cfg = SearchConfig(k=k, mu=mu, eta=eta, method=method,
                           engine=engine)
        outs[engine] = retrieve(idx, q, cfg)
    b = np.sort(np.asarray(outs["batched"].scores), 1)[:, ::-1]
    p = np.sort(np.asarray(outs["per_query"].scores), 1)[:, ::-1]
    if mu == 1.0 and eta == 1.0:
        # rank-safe: both engines return the exact top-k score multiset
        np.testing.assert_allclose(b, p, rtol=1e-5, atol=1e-5)
    else:
        oracle = brute_force_topk(idx, q, k)
        o = np.sort(np.asarray(oracle.scores), 1)[:, ::-1]
        for name, a in (("batched", b), ("per_query", p)):
            a = np.where(a > NEG_F / 2, a, 0.0)   # unfilled slots -> 0
            assert np.all(a.mean(1) >= mu * o.mean(1) - 1e-4), (
                f"{name}: Prop-3 mu-approximation violated at "
                f"mu={mu} eta={eta} k={k} method={method}")


@pytest.mark.parametrize("method", ["asc", "anytime"])
def test_batched_identical_sets_safe_mode(index, queries, method):
    """mu = eta = 1: the batched engine's result *sets* match the
    per-query reference (ids compared score-aware to tolerate ties)."""
    q, _ = queries
    k = 10
    cfg = dict(k=k, mu=1.0, eta=1.0, method=method)
    b = retrieve(index, q, SearchConfig(**cfg))
    p = retrieve(index, q, SearchConfig(**cfg, engine="per_query"))
    bs = np.sort(np.asarray(b.scores), 1)
    ps = np.sort(np.asarray(p.scores), 1)
    np.testing.assert_allclose(bs, ps, rtol=1e-5, atol=1e-5)
    # ids: identical except where scores tie at the boundary
    for i in range(q.n_queries):
        bset = set(np.asarray(b.doc_ids)[i]) - {-1}
        pset = set(np.asarray(p.doc_ids)[i]) - {-1}
        if bset != pset:
            # every disagreement must be a score tie
            diff = bset ^ pset
            kth = bs[i, 0]            # lowest of the top-k
            full = brute_force_topk(index, q, max(k * 2, 20))
            scores_of = {int(d): float(s) for d, s in
                         zip(np.asarray(full.doc_ids)[i],
                             np.asarray(full.scores)[i])}
            for d in diff:
                assert abs(scores_of.get(int(d), kth) - kth) < 1e-4


def test_auto_engine_routes_small_batches_to_per_query(index, queries):
    """engine="auto" (the default) routes batches below
    AUTO_ENGINE_MIN_BATCH to the per-query path — the measured batch-1
    regression in BENCH_retrieval.json — and everything else to the
    batched planner. Pinned bit-exactly on every TopK field (the work
    counters differ between engines, so equality identifies the route)."""
    from repro.core.search import AUTO_ENGINE_MIN_BATCH
    q, _ = queries
    fields = ("doc_ids", "scores", "n_scored_docs", "n_scored_clusters",
              "n_scored_segments", "n_scored_tiles", "n_walked_tiles",
              "n_walked_docs")

    def take(n):
        import dataclasses as dc
        return dc.replace(q, tids=q.tids[:n], tw=q.tw[:n],
                          mask=q.mask[:n])

    for n, want in ((1, "per_query"), (AUTO_ENGINE_MIN_BATCH - 1,
                                       "per_query"),
                    (AUTO_ENGINE_MIN_BATCH, "batched"),
                    (q.n_queries, "batched")):
        qq = take(n)
        auto = retrieve(index, qq, SearchConfig(k=10, engine="auto"))
        expl = retrieve(index, qq, SearchConfig(k=10, engine=want))
        for f in fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(auto, f)),
                np.asarray(getattr(expl, f)),
                err_msg=f"auto at batch {n} did not route to {want} ({f})")


def test_batched_budget_cap_and_traced_budget(index, queries):
    """The traced budget knob caps scored clusters under the batched
    engine exactly as it did per-query."""
    q, _ = queries
    cfg = SearchConfig(k=10, method="anytime")
    capped = retrieve(index, q, cfg, budget=jnp.int32(5))
    assert float(capped.n_scored_clusters.max()) <= 5
    free = retrieve(index, q, cfg)
    assert float(free.n_scored_clusters.mean()) >= \
        float(capped.n_scored_clusters.mean()) - 1e-6


def test_batched_counters_not_more_work_than_reference(index, queries):
    """Shared visitation never admits more clusters than the per-query
    walk on average at safe settings (theta grows at least as fast for
    the batch's shared prefix)."""
    q, _ = queries
    cfg = dict(k=10, mu=0.9, eta=1.0)
    b = retrieve(index, q, SearchConfig(**cfg))
    p = retrieve(index, q, SearchConfig(**cfg, engine="per_query"))
    # not a theorem per-query, but a strong batch-level sanity check:
    # within 20% of the reference's admitted work
    assert float(b.n_scored_clusters.mean()) <= \
        1.2 * float(p.n_scored_clusters.mean()) + 1.0


def test_autotuned_blocks_fit_vmem_budget_and_overrides_win(index):
    """Auto blocking (SearchConfig defaults) keeps the executor resident
    set — query-map block + doc sub-tile + output block — under the VMEM
    budget at every batch size, chunks the vocab only at map scales that
    need it, and explicit SearchConfig values override each knob."""
    from repro.core.plan import resolve_block_d
    from repro.core.search import (VMEM_BLOCK_BUDGET, autotune_blocks,
                                   resolve_blocks)
    tp = index.t_pad
    for n_q in (1, 8, 64, 256, 1024):
        bq, bd, bv = autotune_blocks(index.d_pad, tp, index.n_seg,
                                     index.vocab, n_q)
        v_eff = bv if bv is not None else index.vocab + 1
        resident = 4 * bq * v_eff + 3 * bd * tp + 4 * bq * bd
        assert resident <= VMEM_BLOCK_BUDGET, (n_q, resident)
        assert bq >= 1 and index.d_pad % bd == 0
    # small vocab: full-V gather, no chunk masking
    assert autotune_blocks(index.d_pad, tp, index.n_seg, index.vocab,
                           64)[2] is None
    # WordPiece scale at batch 256 forces vocab chunking under budget
    bq, bd, bv = autotune_blocks(256, 64, 8, 30522, 256)
    assert bv is not None
    assert 4 * bq * bv <= VMEM_BLOCK_BUDGET // 2
    # explicit values pass through untouched (block_d still rounds up)
    cfg = SearchConfig(block_q=4, block_d=9, block_v=128)
    assert resolve_blocks(index, 64, cfg) == (
        4, resolve_block_d(index.d_pad, 9), 128)
    # mixed: only the "auto" knobs are derived
    cfg = SearchConfig(block_q="auto", block_d=8, block_v=None)
    bq2, bd2, bv2 = resolve_blocks(index, 64, cfg)
    assert bq2 == 64 and bd2 == resolve_block_d(index.d_pad, 8)
    assert bv2 is None


def test_queue_step_padding_maps_to_last_real_step():
    """Every padded grid step must re-map to exactly the LAST real step
    of the queue (not an earlier one): compiled Pallas writes the out
    VMEM buffer back whenever a block window closes, so a padded step
    that re-opened an *earlier* out block would clobber its correct
    scores with stale buffer contents. Interpret mode cannot see this
    (it re-reads out blocks per step), so the invariant is pinned here
    at the index-map level — now across all three queue levels (tile,
    query block, doc sub-tile)."""
    from repro.kernels.score_cluster_batch.score_cluster_batch import (
        _queue_step)
    n_tiles = jnp.asarray([2], jnp.int32)
    n_qblock = jnp.asarray([3, 1, 0, 0], jnp.int32)   # G=4, 2 live tiles
    # per-(tile, qblock) doc queues: each live (tile, qblock) pair has
    # its OWN sub-tile count now
    n_dblock = jnp.asarray([[2, 4, 1, 0],
                            [3, 0, 0, 0],
                            [0, 0, 0, 0],
                            [0, 0, 0, 0]], jnp.int32)
    G, n_qb, n_db = 4, 4, 4
    # overall last real step: tile slot 1, its last qblock, that PAIR's
    # last sub-tile
    last_real = (1, 0, 2)
    for i in range(G):
        for j in range(n_qb):
            for d in range(n_db):
                ii, jj, dd, real = _queue_step(
                    jnp.int32(i), jnp.int32(j), jnp.int32(d),
                    n_tiles, n_qblock, n_dblock)
                ii, jj, dd, real = int(ii), int(jj), int(dd), bool(real)
                nq_i = int(n_qblock[i]) if i < 2 else 0
                nd_ij = int(n_dblock[i, j]) if (i < 2 and j < nq_i) else 0
                if i < 2 and j < nq_i and d < nd_ij:
                    assert (ii, jj, dd) == (i, j, d) and real
                elif i < 2 and j < nq_i:
                    # doc tail of a live (tile, qblock): pin that pair's
                    # last sub-tile
                    assert (ii, jj, dd) == (i, j, nd_ij - 1) and not real
                elif i < 2:
                    # qblock tail of a live tile: pin its last real step
                    # (the last live qblock's own last sub-tile)
                    nd_last = int(n_dblock[i, nq_i - 1])
                    assert (ii, jj, dd) == (i, nq_i - 1, nd_last - 1)
                    assert not real
                else:             # padded tile slots
                    assert (ii, jj, dd) == last_real and not real


# ---------------------------------------------------------------------------
# wave counter and the step's named phases
# ---------------------------------------------------------------------------

def test_wave_counter_matches_walked_tiles_and_funnel(index, queries):
    """Each wave walks G cluster tiles for every query block, so the
    batched engine's wave count times G x n_qb is its walked-tile count;
    the per-query engine counts each query's own loop iterations; the
    funnel reports the batch's waves."""
    from repro.core.search import resolve_blocks
    from repro.obs import funnel_from_topk
    q, _ = queries
    cfg = SearchConfig(k=10, mu=0.9, eta=1.0, engine="batched")
    out = retrieve(index, q, cfg)
    waves = np.asarray(out.n_waves)
    assert (waves == waves[0]).all() and waves[0] > 0
    n_qb = -(-q.n_queries // resolve_blocks(index, q.n_queries, cfg)[0])
    np.testing.assert_array_equal(
        waves * cfg.group_size * n_qb, np.asarray(out.n_walked_tiles))
    f = funnel_from_topk(out, batched=True, n_q=q.n_queries,
                         d_pad=index.d_pad, budget_clusters=index.m)
    assert f["waves"] == int(waves[0])

    ref = retrieve(index, q, dataclasses.replace(cfg, engine="per_query"))
    # per-query: walked tiles are the visited positions of its own waves
    G, m = cfg.group_size, index.m
    np.testing.assert_array_equal(
        np.minimum(np.asarray(ref.n_waves) * G, m),
        np.asarray(ref.n_walked_tiles))
    assert (np.asarray(brute_force_topk(index, q, 10).n_waves) == 0).all()


@pytest.mark.parametrize("engine,superblocks", [("batched", False),
                                                ("per_query", False),
                                                ("batched", True)])
def test_lowered_step_carries_every_phase_scope(index, queries, engine,
                                                superblocks):
    """The served step names its phases: the lowered ``retrieve`` of
    every engine carries all four ``asc.*`` scopes in its op names."""
    from repro.core.search import PHASE_SCOPES
    q, _ = queries
    cfg = SearchConfig(k=10, mu=0.9, eta=1.0, engine=engine,
                       superblocks=superblocks)
    text = retrieve.lower(index, q, cfg).as_text(debug_info=True)
    for scope in PHASE_SCOPES:
        assert f"/{scope}/" in text, scope
