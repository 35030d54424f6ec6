"""The benchmark's vectorised generator against ``make_corpus`` /
``make_queries``: the same statistics within sampling error, and the
same arrays from the same seed."""

from __future__ import annotations

import numpy as np
import pytest

import _bench_tiny  # noqa: F401  (puts the repo and src on sys.path)
from bench import gen
from repro.data.synthetic import CorpusSpec, make_corpus, make_queries

V, Z, N = 2048, 16, 4000
ST = gen.Stats(n_docs=N, vocab=V, n_topics=Z, doc_terms=40, t_pad=64,
               query_terms=12, q_pad=20)
SPEC = CorpusSpec(n_docs=N, vocab=V, n_topics=Z, doc_terms=40, t_pad=64,
                  query_terms=12, q_pad=20, seed=5, query_topic_zipf_a=1.0)
SEED = 2**33 + 7        # wider than 32 bits, as the driver's seeds are


def _topic_terms(seed):
    """make_corpus's topic term sets: its first draws from its rng."""
    rng = np.random.default_rng(seed)
    return [set(rng.choice(V, max(8, V // Z), replace=False).tolist())
            for _ in range(Z)]


@pytest.fixture(scope="module")
def ours():
    tab = gen.tables(ST, SEED)
    docs = gen.make_docs(ST, SEED, tab)
    queries = gen.make_queries(ST, 2000, 1.0, SEED, tab)
    return tab, docs, queries


@pytest.fixture(scope="module")
def theirs():
    docs, doc_topic = make_corpus(SPEC)
    queries, q_topic = make_queries(SPEC, 2000, doc_topic, seed=6)
    return ((np.asarray(docs.tids), np.asarray(docs.mask), doc_topic),
            (np.asarray(queries.tids), np.asarray(queries.mask), q_topic))


def _topic_share(tids, mask, topic, terms):
    return np.array([np.isin(t[m], list(terms[z])).mean()
                     for t, m, z in zip(tids, mask, topic)])


def _close(a: np.ndarray, b: np.ndarray, sigmas: float = 5.0) -> bool:
    """Means of a and b agree within ``sigmas`` standard errors."""
    se = np.sqrt(a.var() / a.size + b.var() / b.size)
    return abs(a.mean() - b.mean()) <= sigmas * se + 1e-12


def test_doc_statistics_match_make_corpus(ours, theirs):
    tab, (tids, tw, mask, topic), _ = ours
    t_tids, t_mask, t_topic = theirs[0]
    assert _close(mask.sum(1).astype(float), t_mask.sum(1).astype(float))
    ours_share = _topic_share(tids, mask, topic,
                              [set(r.tolist()) for r in tab.topic_terms])
    their_share = _topic_share(t_tids, t_mask, t_topic, _topic_terms(5))
    assert _close(ours_share, their_share)
    # Zipf head: share of a doc's terms among the 20 most popular
    head = (np.where(mask, tids, V) < 20).sum(1) / mask.sum(1)
    t_head = (np.where(t_mask, t_tids, V) < 20).sum(1) / t_mask.sum(1)
    assert _close(head, t_head)
    # rows are ascending distinct ids, -1 padded, positive weights
    assert (np.diff(np.where(mask, tids, V), axis=1) >= 0).all()
    assert ((tids >= 0) == mask).all() and (tw[mask] > 0).all()
    assert (tw[~mask] == 0).all()


def test_query_statistics_match_make_queries(ours, theirs):
    tab, _, (q_tids, _, q_mask, q_topic) = ours
    t_tids, t_mask, t_topic = theirs[1]
    assert _close(q_mask.sum(1).astype(float), t_mask.sum(1).astype(float))
    ours_share = _topic_share(q_tids, q_mask, q_topic,
                              [set(r.tolist()) for r in tab.topic_terms])
    their_share = _topic_share(t_tids, t_mask, t_topic, _topic_terms(5))
    assert _close(ours_share, their_share)
    # Zipf(1.0) topic popularity: the most asked topic's share
    top = np.bincount(q_topic, minlength=Z).max() / q_topic.size
    t_top = np.bincount(t_topic, minlength=Z).max() / t_topic.size
    p1 = 1.0 / np.sum(1.0 / np.arange(1, Z + 1))
    assert abs(top - p1) < 5 * np.sqrt(p1 * (1 - p1) / q_topic.size)
    assert abs(t_top - p1) < 5 * np.sqrt(p1 * (1 - p1) / t_topic.size)


def test_same_seed_same_arrays_other_seed_other(ours):
    _, docs, queries = ours
    again = gen.make_docs(ST, SEED)
    for a, b in zip(docs, again):
        np.testing.assert_array_equal(a, b)
    q_again = gen.make_queries(ST, 2000, 1.0, SEED)
    for a, b in zip(queries, q_again):
        np.testing.assert_array_equal(a, b)
    other = gen.make_docs(ST, SEED + 1)
    assert not np.array_equal(docs[0], other[0])
