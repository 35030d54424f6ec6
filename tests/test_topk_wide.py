"""Top-k at large k on every engine: k below, above and beyond the
wave's candidate width G·d_pad, past the live doc count (the ``-1``
padding ``TopK`` promises) and past the index's slots; the merge
counter ``TopK.n_merge_candidates``; and k=10 pinned to the counters
the engines gave before the counter existed.

Exact setting (mu, eta) = (1, 1): the served scores equal the
brute-force oracle's and the benchmark reference's; ids are compared
where scores are distinct, score multisets where they tie. Served
setting (0.9, 1.0): every returned score is its doc's exact score,
Prop 3 holds over all k, and no answer is malformed.

The k=10 pin (``tests/golden/golden_counters_k10.json``) was written by
the code before ``n_merge_candidates`` was added; regenerate it only
for an intended change of results, with::

    PYTHONPATH=src:tests python tests/test_topk_wide.py --regen
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import build_index
from repro.core.search import (NEG, SearchConfig, brute_force_topk,
                               retrieve, retrieve_pipelined,
                               score_docs_ref)
from repro.core.types import TopK
from repro.data.synthetic import CorpusSpec, make_corpus, make_queries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIN_PATH = os.path.join(REPO, "tests", "golden", "golden_counters_k10.json")

M, N_SEG, G = 12, 4, 8
SPEC = CorpusSpec(n_docs=600, vocab=256, n_topics=12, doc_terms=20,
                  t_pad=24, query_terms=8, q_pad=12, seed=4242)
N_Q = 6
EXACT, SERVED = (1.0, 1.0), (0.9, 1.0)
#: a tie at a rank boundary: scores closer than this compare as a set
TIE = 1e-5

#: each engine as ``retrieve`` runs it (the pipelined one separately)
ENGINES = {
    "batched": dict(engine="batched"),
    "superblocks": dict(engine="batched", superblocks=True),
    "per_query": dict(engine="per_query"),
}


def _world():
    """600 docs in 12 topical clusters of 50 (sorted by topic, cut in
    equal runs) in 56 slots each, so G·d_pad = 448 < live docs = 600 <
    m·d_pad = 672."""
    docs, doc_topic = make_corpus(SPEC)
    assign = np.empty(SPEC.n_docs, np.int64)
    assign[np.argsort(np.asarray(doc_topic), kind="stable")] = (
        np.arange(SPEC.n_docs) * M // SPEC.n_docs)
    index = build_index(docs, assign, m=M, n_seg=N_SEG, d_pad=56, seed=5)
    queries, _ = make_queries(SPEC, N_Q, doc_topic, seed=6)
    return docs, index, queries


@pytest.fixture(scope="module")
def world():
    return _world()


def _ks(index) -> dict:
    """k below G·d_pad, above it (below the live count), past the live
    count (inside the slots), and past every slot of the index."""
    width, live = G * index.d_pad, int(index.n_docs)
    slots = index.m * index.d_pad
    assert width < live < slots, (width, live, slots)
    return {"below": width // 2, "above": (width + live) // 2,
            "past_live": (live + slots) // 2, "past_slots": slots + 9}


K_CASES = ("below", "above", "past_live", "past_slots")


def _run(index, queries, engine: str, k: int, mu_eta) -> TopK:
    if engine == "pipelined":
        cfg = SearchConfig(k=k, mu=mu_eta[0], eta=mu_eta[1],
                           engine="pipelined")
        return retrieve_pipelined(index, queries, cfg)
    cfg = SearchConfig(k=k, **ENGINES[engine])
    rows = jnp.tile(jnp.asarray(mu_eta, jnp.float32), (queries.n_queries, 1))
    return retrieve(index, queries, cfg, mu_eta=rows)


def _exact_scores(index, queries) -> np.ndarray:
    """(n_q, n_docs) exact score of every doc id for every query."""
    qmaps = queries.dense_map()
    s = jax.vmap(lambda q: score_docs_ref(index.doc_tids, index.doc_tw, q,
                                          index.scale))(qmaps)
    ids = np.asarray(index.doc_ids).reshape(-1)
    live = ids >= 0
    out = np.full((queries.n_queries, int(ids.max()) + 1), np.nan)
    out[:, ids[live]] = np.asarray(s).reshape(queries.n_queries, -1)[:, live]
    return out


def _check_exact(got: TopK, want: TopK, name: str) -> None:
    """Scores equal (as sorted multisets); ids equal where a score is
    clear of its neighbours; ``-1`` exactly where the oracle has none."""
    gs, ws = np.asarray(got.scores), np.asarray(want.scores)
    gi, wi = np.asarray(got.doc_ids), np.asarray(want.doc_ids)
    np.testing.assert_array_equal(gi < 0, wi < 0, err_msg=name)
    valid = wi >= 0
    np.testing.assert_allclose(np.where(valid, gs, 0), np.where(valid, ws, 0),
                               rtol=1e-6, atol=1e-6, err_msg=name)
    for q in range(wi.shape[0]):
        v = ws[q][valid[q]]
        gap = np.abs(np.diff(v))
        clear = np.ones(v.size, bool)
        clear[1:] &= gap > TIE
        clear[:-1] &= gap > TIE
        if v.size == wi.shape[1]:   # the k-th may tie the unseen (k+1)-th
            clear[-1:] = False
        np.testing.assert_array_equal(gi[q][valid[q]][clear],
                                      wi[q][valid[q]][clear],
                                      err_msg=f"{name} query {q}")


def _check_well_formed(out: TopK, n_docs_max: int, name: str) -> None:
    ids, scores = np.asarray(out.doc_ids), np.asarray(out.scores)
    for q in range(ids.shape[0]):
        v = ids[q] >= 0
        n = int(v.sum())
        assert v[:n].all() and not v[n:].any(), f"{name}: -1 inside"
        assert len(set(ids[q][v].tolist())) == n, f"{name}: a doc twice"
        assert ids[q][v].max(initial=0) < n_docs_max, name
        assert (np.diff(scores[q][:n]) <= 0).all(), f"{name}: order"
        assert (scores[q][n:] == np.float32(NEG)).all(), name


@pytest.mark.parametrize("case", K_CASES)
@pytest.mark.parametrize("engine", sorted(ENGINES) + ["pipelined"])
def test_exact_setting_matches_the_oracle(world, engine, case):
    _, index, queries = world
    k = _ks(index)[case]
    out = _run(index, queries, engine, k, EXACT)
    oracle = brute_force_topk(index, queries, k)
    assert out.doc_ids.shape == (N_Q, k)
    _check_exact(out, oracle, f"{engine}/{case}")
    _check_well_formed(out, SPEC.n_docs, f"{engine}/{case}")


@pytest.mark.parametrize("case", K_CASES)
@pytest.mark.parametrize("engine", sorted(ENGINES) + ["pipelined"])
def test_served_setting_scores_are_exact_and_prop3_holds(world, engine,
                                                          case):
    _, index, queries = world
    k = _ks(index)[case]
    out = _run(index, queries, engine, k, SERVED)
    name = f"{engine}/{case}"
    _check_well_formed(out, SPEC.n_docs, name)
    ids, scores = np.asarray(out.doc_ids), np.asarray(out.scores)
    exact = _exact_scores(index, queries)
    valid = ids >= 0
    pair = np.take_along_axis(exact, np.maximum(ids, 0), 1)
    top1 = np.nanmax(exact, axis=1, keepdims=True)
    err = np.where(valid, np.abs(scores - pair), 0) / np.maximum(top1, 1e-30)
    assert err.max() <= 1e-6, (name, err.max())
    oracle = brute_force_topk(index, queries, k)
    ref_mass = np.where(np.asarray(oracle.doc_ids) >= 0,
                        np.asarray(oracle.scores), 0).sum(1)
    mass = np.where(valid, pair, 0).sum(1)
    assert (mass >= SERVED[0] * ref_mass * (1 - 1e-6)).all(), (name, mass,
                                                               ref_mass)


@pytest.mark.parametrize("setting", [EXACT, SERVED], ids=["exact", "served"])
@pytest.mark.parametrize("case", ("small",) + K_CASES)
@pytest.mark.parametrize("engine", sorted(ENGINES) + ["pipelined",
                                                      "brute_force"])
def test_merge_counter_covers_every_returned_doc(world, engine, case,
                                                 setting):
    """Every returned doc beat theta at its wave, so it entered the
    merge: the counter is at least the returned non-``-1`` ids (and the
    brute-force oracle counts every live doc)."""
    _, index, queries = world
    k = 10 if case == "small" else _ks(index)[case]
    if engine == "brute_force":
        out = brute_force_topk(index, queries, k)
        assert (np.asarray(out.n_merge_candidates)
                == int(index.n_docs)).all()
    else:
        out = _run(index, queries, engine, k, setting)
    n = np.asarray(out.n_merge_candidates)
    assert n.shape == (N_Q,) and n.dtype == np.int32
    assert (n >= (np.asarray(out.doc_ids) >= 0).sum(1)).all(), n
    assert (n <= np.asarray(out.n_scored_docs)).all()


@pytest.mark.parametrize("k", [10, 500])
def test_merge_counter_agrees_across_walks_at_one_query(world, k):
    """At (1, 1) and one query the batched walk visits the per-query
    engine's own order in the same groups with the same theta, so both
    count the same candidates (and the pipelined walk is the batched
    one)."""
    _, index, queries = world
    for q in range(N_Q):
        one = jax.tree_util.tree_map(lambda a: a[q:q + 1], queries)
        got = {e: np.asarray(_run(index, one, e, k, EXACT)
                             .n_merge_candidates)
               for e in ("batched", "per_query", "pipelined")}
        assert got["batched"] == got["per_query"] == got["pipelined"], got


@pytest.mark.parametrize("case", K_CASES)
@pytest.mark.parametrize("engine", sorted(ENGINES) + ["pipelined"])
def test_merge_counter_grows_with_k(world, engine, case):
    """At (1, 1) the wide walk's theta never passes the narrow one's:
    every doc the k=10 walk leaves out scores at most its theta, so at
    each wave the wide walk admits a superset, filters at a bar no
    higher and walks at least as long, and counts at least as many
    candidates, query by query. (Below mu = 1 a pruned cluster may hold
    docs above theta, and the argument does not hold.)"""
    _, index, queries = world
    narrow = _run(index, queries, engine, 10, EXACT)
    wide = _run(index, queries, engine, _ks(index)[case], EXACT)
    assert (np.asarray(wide.n_merge_candidates)
            >= np.asarray(narrow.n_merge_candidates)).all()


@pytest.mark.parametrize("case", K_CASES)
def test_bench_reference_agrees_at_large_k(world, case):
    """The benchmark's exhaustive reference gives the exact top-k's
    scores: equal to the batched walk's at (1, 1) over the docs it
    holds live (it drops a doc whose quantized weights are all zero;
    the index scores such a doc 0)."""
    sys.path.insert(0, REPO)
    from bench.reference import Reference
    docs, index, queries = world
    k = _ks(index)[case]
    ref = Reference(np.asarray(docs.tids), np.asarray(docs.tw),
                    np.asarray(docs.mask), docs.vocab)
    ref_ids, ref_scores = ref.topk(np.asarray(queries.tids),
                                   np.asarray(queries.tw),
                                   np.asarray(queries.mask), k)
    out = _run(index, queries, "batched", k, EXACT)
    scores = np.asarray(out.scores)
    for q in range(N_Q):
        n = int((ref_ids[q] >= 0).sum())
        np.testing.assert_allclose(scores[q][:n], ref_scores[q][:n],
                                   rtol=1e-6, atol=1e-6)
        tail = scores[q][n:]
        assert ((tail == 0) | (tail == np.float32(NEG))).all()


# ---------------------------------------------------------------------------
# k=10: every field that existed before the counter, pinned
# ---------------------------------------------------------------------------

def _pin_engines() -> dict:
    from test_golden_regression import ENGINES as GOLDEN
    return GOLDEN


def _pinned_fields(out) -> dict:
    return {f: np.asarray(getattr(out, f)).tolist()
            for f in ("doc_ids", "n_scored_docs", "n_scored_clusters",
                      "n_scored_segments", "n_scored_tiles",
                      "n_walked_tiles", "n_walked_docs", "n_waves",
                      "n_bounded_clusters", "n_walked_superblocks",
                      "n_pruned_superblocks")} | {
        "scores": np.asarray(out.scores, np.float64).tolist()}


def _compute_pin() -> dict:
    from test_golden_regression import _world as golden_world
    index, queries = golden_world()
    return {name: _pinned_fields(retrieve(index, queries, cfg))
            for name, cfg in _pin_engines().items()}


@pytest.fixture(scope="module")
def pin() -> dict:
    with open(PIN_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pin_now() -> dict:
    return _compute_pin()


@pytest.mark.parametrize("name", sorted(_pin_engines()))
def test_k10_results_and_counters_are_as_pinned(pin, pin_now, name):
    """ids and every counter bit for bit; scores to 1e-6 (float32
    contraction order may differ across CPU builds)."""
    want, got = pin[name], pin_now[name]
    assert set(want) == set(got)
    for field in want:
        if field == "scores":
            np.testing.assert_allclose(got[field], want[field], rtol=1e-6,
                                       atol=1e-6)
        else:
            assert got[field] == want[field], (name, field)


def test_distributed_path_at_large_k():
    """``distributed_retrieve`` on 8 forced host devices (the layout of
    tests/test_distributed.py): exact at (1, 1) for each k case, the
    merge counter psum'd over the cluster shards."""
    body = f"""
import sys
sys.path.insert(0, {os.path.join(REPO, 'tests')!r})
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.search import SearchConfig, brute_force_topk
from repro.serving.engine import distributed_retrieve, index_shard_specs
import test_topk_wide as t

assert jax.device_count() == 8
_, idx, q = t._world()
q = jax.tree_util.tree_map(lambda a: np.concatenate([a, a[:2]]), q)
mesh = jax.make_mesh((4, 2), ("data", "model"))
with mesh:
    ispecs = index_shard_specs(idx)
    i_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), ispecs,
        is_leaf=lambda x: isinstance(x, P))
    idx_s = jax.device_put(idx, i_shard)
    q_s = jax.device_put(q, jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("model", None)),
        q, is_leaf=lambda x: hasattr(x, "shape")))
    for case, k in t._ks(idx).items():
        out = distributed_retrieve(idx_s, q_s,
                                   SearchConfig(k=k, mu=1.0, eta=1.0), mesh)
        t._check_exact(out, brute_force_topk(idx, q, k), case)
        n = np.asarray(out.n_merge_candidates)
        assert (n >= (np.asarray(out.doc_ids) >= 0).sum(1)).all(), case
        assert (n <= np.asarray(out.n_scored_docs)).all(), case
print("ok")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


if __name__ == "__main__":
    if "--regen" in sys.argv:
        with open(PIN_PATH, "w") as f:
            json.dump(_compute_pin(), f)
        print(f"wrote {PIN_PATH}")
