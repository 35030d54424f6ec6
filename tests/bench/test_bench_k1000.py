"""The k=1000 configuration at a toy size on the CPU, with the look for a
chip skipped: it is the shard8 deployment with only k and the limits
changed; a run of it comes out correct, ``recall_at_k`` included, and
its merge counter reaches its reader; a merge that keeps only each
wave's top 10, padded to 1,000, fails ``recall_at_k``; the bfloat16
control fails ``score_err``."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_tiny import BENCH, REPO, make_tiny, tiny_cell
from bench import control, reference, run
from bench.spec import metric_reader
from repro.core import search

SEED = 2**31 + 1077
CELL = "tiny-k1000.closed128"
K = 1000
#: a toy corpus dense enough that the exact 1,000th score is not tied
#: (about 6 query terms in every doc): at the tiny cells' 6-term queries
#: over a 512-term vocabulary most of the top 1,000 ties, down to 0, and
#: which tied doc the reference keeps decides ``recall_at_k``
DENSE = dict(n_docs=4000, vocab=64, doc_terms=28, t_pad=32, query_terms=14,
             q_pad=16)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


SHARD = _load(BENCH, "configs", "msmarco-splade-shard8.json")
K1000 = _load(BENCH, "configs", "msmarco-splade-shard8-k1000.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cells, plus ``tiny-k1000.closed128``: the k=1000 file
    shrunk as ``make_tiny`` shrinks the shard's, listed in every metric
    the real cell lists."""
    root = make_tiny(str(tmp_path_factory.mktemp("tiny")))
    tiny = _load(root, "configs", "tiny.json")
    c = dict(K1000, name="tiny-k1000",
             **{key: tiny[key] for key in ("corpus", "index", "clustering",
                                           "compare")})
    c["corpus"] = dict(c["corpus"], **DENSE)
    with open(os.path.join(root, "configs", "tiny-k1000.json"), "w") as f:
        json.dump(c, f)
    b = _load(root, "BENCHMARK.json")
    b["configs"].append(dict(b["configs"][0], name="tiny-k1000",
                             file="configs/tiny-k1000.json"))
    b["workloads"].append({"name": CELL, "config": "tiny-k1000",
                           "traffic": "closed128", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    b["per_layer"].append({"name": "merge_candidates_per_query",
                           "unit": "docs", "better": "lower",
                           "source": "program_counter",
                           "layer": "top-k merge", "moves": "qps",
                           "workloads": ["tiny.closed128", CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return root


@pytest.mark.parametrize("key", ["source", "reduced", "why_reduced",
                                 "corpus", "index", "clustering",
                                 "compare"])
def test_same_deployment_as_the_shard(key):
    """Same corpus, clustering and index, so one seed builds one index
    for both cells."""
    assert K1000[key] == SHARD[key]


def test_only_k_and_the_limits_differ():
    assert K1000["search"] == dict(SHARD["search"], k=K)
    assert set(K1000["assumed"]) == set(SHARD["assumed"])
    assert {n: v for n, v in K1000["assumed"].items()
            if n != "search"} == {n: v for n, v in SHARD["assumed"].items()
                                  if n != "search"}
    assert "k = 1,000" in K1000["assumed"]["search"]
    assert {n: v for n, v in K1000["limits"].items()
            if n != "recall_at_k"} == SHARD["limits"]
    assert set(K1000["limits"]["recall_at_k"]) == {"min"}


@pytest.mark.parametrize("counters,expect", [
    ({"n_merge_candidates": {"sum": 900, "max": 500, "first": 400}}, 450.0),
    ({}, None),
], ids=["program_with_the_counter", "parent_without_it"])
def test_reader_reads_the_counter_or_nothing(counters, expect):
    rec = {"calls": [{"rows": 2, "counters": counters}]}
    assert metric_reader("merge_candidates_per_query")(rec) == expect


def _boundary_ties(sample) -> int:
    """Queries with a doc outside the exact top k that scores exactly the
    k-th score: there the reference's choice among tied docs, not the
    walk, would decide ``recall_at_k``."""
    q, ref = sample["queries"], sample["reference"]
    wide = ref.topk(*q, K + 8)[1]
    return int((wide[:, K:] == wide[:, K - 1:K]).any(1).sum())


def test_k1000_run_is_correct_and_reads_the_merge_counter(root):
    """The run is ``correct`` as the harness decides it, every limit of
    the k=1000 file included, on a toy corpus with no tie at the exact
    1,000th score."""
    cell = tiny_cell(root, CELL)
    assert cell.config["search"]["k"] == K
    result, rec, checks, sample = run.run_cell(cell, SEED, 1.0, True)
    assert _boundary_ties(sample) == 0
    assert result["correct"], checks
    assert set(checks) >= {"score_err", "prop3_ratio", "recall_at_k"}
    assert checks["score_err"]["value"] < 1e-6
    assert sample["ids"].shape[1] == K and rec["geometry"]["k"] == K
    per_query = result["metrics"]["merge_candidates_per_query"]["value"]
    assert per_query >= K and rec["compiles_in_window"] == 0
    ctl = control.control_numbers(sample, K)
    assert ctl["score_err"] > 10 * checks["score_err"]["limit"]


def _narrow_merge(index, cids, scores, theta, top_scores, top_ids, k):
    """The fault: each wave's top 10 candidates only, padded to k."""
    n_q = scores.shape[0]
    beat = scores > theta[:, None, None]
    cand = jnp.where(beat, scores, search.NEG).reshape(n_q, -1)
    g_top, g_pos = jax.lax.top_k(cand, 10)
    g_ids = jnp.where(g_top > search.NEG,
                      index.doc_ids[cids].reshape(-1)[g_pos], -1)
    g_top = jnp.pad(g_top, ((0, 0), (0, k - 10)),
                    constant_values=search.NEG)
    g_ids = jnp.pad(g_ids, ((0, 0), (0, k - 10)), constant_values=-1)
    merged_s = jnp.concatenate([top_scores, g_top], axis=1)
    merged_i = jnp.concatenate([top_ids, g_ids], axis=1)
    top_scores, sel = jax.lax.top_k(merged_s, k)
    return (top_scores, jnp.take_along_axis(merged_i, sel, axis=1),
            beat.sum(axis=(1, 2)).astype(jnp.int32))


def test_merge_keeping_ten_a_wave_fails_recall_at_k(root, monkeypatch):
    monkeypatch.setattr(search, "_merge_wave", _narrow_merge)
    jax.clear_caches()
    try:
        result, _, checks, sample = run.run_cell(tiny_cell(root, CELL),
                                                 SEED + 1, 1.0, False)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not result["correct"]
    assert not checks["recall_at_k"]["ok"], checks
    assert checks["score_err"]["ok"] and checks["malformed"]["ok"]
    assert checks["recall_at_k"]["value"] < 0.1


def test_reference_recall_at_k_counts_the_whole_top_k():
    """``compare``'s ``recall_at_k`` over 1,000 ids: half the exact
    top-k returned reads 0.5, the top-10 head still 1.0."""
    ref_ids = np.arange(2 * K, dtype=np.int32).reshape(2, K)
    ref_scores = np.linspace(2.0, 1.0, K, dtype=np.float32)[None].repeat(2, 0)
    ids = ref_ids.copy()
    ids[:, K // 2:] = -1
    scores = np.where(ids >= 0, ref_scores, np.float32(search.NEG))
    pair = np.where(ids >= 0, ref_scores, 0.0)
    got = reference.compare(ids, scores, ref_ids, ref_scores, pair)
    assert got["recall_at_k"] == 0.5 and got["recall"] == 1.0
    assert got["malformed"] == 0 and got["score_err"] == 0.0


def test_the_cell_is_listed_beside_the_shard_cell():
    b = _load(REPO, "BENCHMARK.json")
    cells = {w["name"]: w for w in b["workloads"]}
    new = cells["msmarco-splade-shard8-k1000.closed128"]
    assert (new["config"], new["traffic"], new["chips"]) == (
        "msmarco-splade-shard8-k1000", "closed128", 1)
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and m["name"] != "merge_candidates_per_query":
            assert m["workloads"][-1] == new["name"], m["name"]
