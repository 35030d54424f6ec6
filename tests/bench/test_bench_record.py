"""What the harness's record carries for the readers: every integer
``n_*`` counter of the returned ``TopK``, found from its fields, and
recall at min(10, k) beside recall at k."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import _bench_tiny  # noqa: F401
from bench import reference, run
from repro.core.types import QueryBatch, TopK


def _call(out, rows=4):
    q = QueryBatch(tids=np.arange(rows * 3).reshape(rows, 3) % 5,
                   tw=np.ones((rows, 3), np.float32),
                   mask=np.ones((rows, 3), bool), vocab=8)
    return (1.0, 1.5, q, out)


def _topk(rows=4):
    """A ``TopK`` of ``rows`` queries: per-query counters 1..rows,
    batch-level ones replicated per query."""
    per_query = np.arange(1, rows + 1, dtype=np.int32)
    batch = np.full(rows, 7, np.int32)
    fields = {f.name: batch for f in dataclasses.fields(TopK)
              if f.name.startswith("n_")}
    fields.update(n_scored_docs=per_query * 100, n_scored_clusters=per_query)
    return TopK(doc_ids=np.zeros((rows, 10), np.int32),
                scores=np.zeros((rows, 10), np.float32), **fields)


def test_call_counters_keep_their_keys_and_carry_every_counter():
    c = run.call_counters(_call(_topk()))
    assert (c["docs"], c["docs_max"], c["clusters"], c["bounded"]) == (
        1000, 400, 10, 7)
    assert c["rows"] == 4 and c["wall_s"] == 0.5
    assert c["distinct_terms"] == 5 and c["waves"] == 7
    assert set(c["counters"]) == {f.name for f in dataclasses.fields(TopK)
                                  if f.name.startswith("n_")}
    assert c["counters"]["n_scored_docs"] == {"sum": 1000, "max": 400,
                                              "first": 100}
    assert c["counters"]["n_waves"] == {"sum": 28, "max": 7, "first": 7}


def test_a_counter_added_to_the_result_reaches_the_record():
    """No list in the harness names the fields: a result class with one
    more integer ``n_*`` field has it in ``counters``; a float field
    and a field not named ``n_*`` are left out."""
    @dataclasses.dataclass(frozen=True)
    class Wider(TopK):
        n_merge_rows: np.ndarray = None
        n_theta: np.ndarray = None
        merge_rows: np.ndarray = None

    out = Wider(**vars(_topk()), n_merge_rows=np.array([3, 5, 2, 9], np.int32),
                n_theta=np.ones(4, np.float32),
                merge_rows=np.ones(4, np.int32))
    c = run.call_counters(_call(out))
    assert c["counters"]["n_merge_rows"] == {"sum": 19, "max": 9,
                                             "first": 3}
    assert "n_theta" not in c["counters"]
    assert "merge_rows" not in c["counters"]


def test_call_counters_without_a_wave_counter():
    @dataclasses.dataclass(frozen=True)
    class NoWaves:
        doc_ids: np.ndarray
        n_scored_docs: np.ndarray
        n_scored_clusters: np.ndarray
        n_bounded_clusters: np.ndarray

    ones = np.ones(4, np.int32)
    c = run.call_counters(_call(NoWaves(np.zeros((4, 10)), ones, ones, ones)))
    assert c["waves"] is None and c["docs"] == 4


def _recall_before(ids, ref_ids):
    """The comparison's recall as it read before recall_at_k: the share
    of the exact top-k returned."""
    hits = [len(set(a[a >= 0].tolist()) & set(b[b >= 0].tolist()))
            / max(int((b >= 0).sum()), 1) for a, b in zip(ids, ref_ids)]
    return float(np.mean(hits))


def _numbers(ids, ref_ids):
    scores = -np.arange(ids.shape[1], dtype=np.float32)[None].repeat(
        ids.shape[0], 0)
    return reference.compare(ids, scores, ref_ids, scores, scores.copy())


def test_recall_at_k10_reads_as_before_bit_for_bit():
    rng = np.random.default_rng(3)
    ref_ids = np.stack([rng.permutation(50)[:10] for _ in range(64)])
    ids = np.where(rng.random((64, 10)) < 0.2, ref_ids + 100, ref_ids)
    ids[5, 7:] = -1
    n = _numbers(ids, ref_ids)
    assert n["recall"] == _recall_before(ids, ref_ids)
    assert n["recall_at_k"] == n["recall"]


def test_recall_at_k20_is_the_top10_overlap_and_recall_at_k_the_top20():
    ref_ids = np.arange(20)[None].repeat(2, 0)
    ids = ref_ids.copy()
    # row 0: the exact top-20 in another order, two of the top-10 swapped
    # below the tenth answer; row 1: the last five answers are misses
    ids[0, [2, 15]] = ids[0, [15, 2]]
    ids[0, [4, 16]] = ids[0, [16, 4]]
    ids[1, 15:] = np.arange(100, 105)
    n = _numbers(ids, ref_ids)
    assert n["recall"] == pytest.approx((8 / 10 + 1.0) / 2)
    assert n["recall_at_k"] == pytest.approx((1.0 + 15 / 20) / 2)
    # a limit on recall_at_k is read by name, as any other number
    checks = run.checks_of(n, {"recall_at_k": {"min": 0.85}})
    assert checks["recall_at_k"]["ok"] and checks["recall_at_k"]["value"] \
        == n["recall_at_k"]
    assert not run.checks_of(n, {"recall_at_k": {"min": 0.85}} | {
        "recall": {"min": 0.95}})["recall"]["ok"]


def test_recall_below_k10_reads_the_whole_answer():
    ref_ids = np.arange(5)[None]
    ids = np.array([[0, 1, 2, 9, 8]])
    n = _numbers(ids, ref_ids)
    assert n["recall"] == n["recall_at_k"] == pytest.approx(3 / 5)
