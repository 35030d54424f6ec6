"""Seeded, vectorised corpus and query generator, run on the device.

The statistics are those of ``repro.data.synthetic.make_corpus`` /
``make_queries`` (copied here so that the benchmark owns its yardstick):
Zipf term popularity over the vocabulary, topics that boost a random
term subset, a Poisson number of distinct terms per row drawn without
replacement, lognormal impact weights. ``make_corpus`` draws row by row
on the host; this draws whole chunks of rows in one jitted call.

Sampling without replacement follows the exponential race that
defines it: every term gets the key ``E / w`` (``E`` ~ Exp(1), ``w``
its weight) and a row takes the terms of smallest key. A topic's terms
(weight ``boost * base_p``) get their keys directly. The other terms
come from a stream of draws with replacement from the Zipf base at
unit rate: a term's first arrival time in that stream is its key, so
the stream stands in for the (rows, V) table of keys that a whole
vocabulary would need. Background terms are the first distinct draws
of a second such stream.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: rows generated per jitted call (one compiled shape for every large size)
CHUNK = 32_768
#: with-replacement draws per row of each Zipf stream: enough distinct
#: terms that a row gets its count (tests check the mean count and the
#: topic share against make_corpus)
TOPIC_DRAWS = 192
BG_DRAWS = 128


@dataclasses.dataclass(frozen=True)
class Stats:
    """Corpus and query statistics of one deployment."""

    n_docs: int
    vocab: int
    n_topics: int
    doc_terms: float
    t_pad: int
    query_terms: float
    q_pad: int
    zipf_a: float = 1.2
    topic_sharpness: float = 0.7
    topic_boost: float = 50.0
    query_sharpness: float = 0.8
    doc_sigma: float = 0.6
    query_sigma: float = 0.5


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


@dataclasses.dataclass(frozen=True)
class Tables:
    """Per-deployment draw tables (small; built on the host)."""

    base_cdf: np.ndarray       # (V,) float32
    base_p: np.ndarray         # (V,) float32
    topic_terms: np.ndarray    # (Z, topic_size) int32, ascending


def zipf_probs(vocab: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1) ** a
    return p / p.sum()


def tables(st: Stats, seed: int) -> Tables:
    base_p = zipf_probs(st.vocab, st.zipf_a)
    topic_size = max(8, st.vocab // st.n_topics)
    rng = host_rng(seed, 0)
    terms = np.sort(np.stack([
        rng.choice(st.vocab, topic_size, replace=False)
        for _ in range(st.n_topics)]), axis=1).astype(np.int32)
    base_cdf = np.cumsum(base_p)
    base_cdf[-1] = 1.0
    return Tables(base_cdf=base_cdf.astype(np.float32),
                  base_p=base_p.astype(np.float32), topic_terms=terms)


def _first_draws(x: jax.Array, need: jax.Array) -> jax.Array:
    """(rows, K) bool: the first draw of each distinct value, in draw
    order, for as long as the row still needs terms."""
    rows, k = x.shape
    order = jnp.argsort(x, axis=1, stable=True)
    xs = jnp.take_along_axis(x, order, axis=1)
    first_sorted = jnp.concatenate(
        [jnp.ones((rows, 1), bool), xs[:, 1:] != xs[:, :-1]], axis=1)
    # back to draw order by the inverse permutation (a sort, not a
    # scatter: a 2-D scatter is slow on the chip)
    first = jnp.take_along_axis(first_sorted, jnp.argsort(order, axis=1),
                                axis=1)
    rank = jnp.cumsum(first, axis=1) - 1
    return first & (rank < need[:, None])


def _union_rows(cands: jax.Array, keep: jax.Array, vocab: int,
                width: int) -> tuple[jax.Array, jax.Array]:
    """Sorted distinct kept terms of each row, padded: (tids, mask)."""
    x = jnp.sort(jnp.where(keep, cands, vocab), axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((x.shape[0], 1), bool), x[:, 1:] == x[:, :-1]], axis=1)
    x = jnp.sort(jnp.where(dup, vocab, x), axis=1)[:, :width]
    mask = x < vocab
    return jnp.where(mask, x, -1).astype(jnp.int32), mask


def _base_draws(key, base_cdf, shape):
    u = jax.random.uniform(key, shape)
    return jnp.minimum(jnp.searchsorted(base_cdf, u, side="right"),
                       base_cdf.shape[0] - 1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("st", "rows"))
def _doc_chunk(key, tab: dict, st: Stats, rows: int = CHUNK):
    k_topic, k_nnz, k_b, k_gap, k_own, k_bg, k_w = jax.random.split(key, 7)
    z = jax.random.randint(k_topic, (rows,), 0, st.n_topics)
    nnz = jnp.clip(jax.random.poisson(k_nnz, st.doc_terms, (rows,)),
                   4, st.t_pad).astype(jnp.int32)
    n_topic = jnp.round(nnz * st.topic_sharpness).astype(jnp.int32)
    # topical terms: the n_topic smallest race keys over the vocabulary
    own = tab["topic_terms"][z]                          # (rows, S)
    key_own = (jax.random.exponential(k_own, own.shape)
               / (st.topic_boost * tab["base_p"][own]))
    b = _base_draws(k_b, tab["base_cdf"], (rows, TOPIC_DRAWS))
    t_b = jnp.cumsum(jax.random.exponential(k_gap, b.shape), axis=1)
    pos = jax.vmap(jnp.searchsorted)(own, b)
    in_topic = jnp.take_along_axis(
        own, jnp.minimum(pos, own.shape[1] - 1), axis=1) == b
    first_b = _first_draws(b, jnp.full((rows,), TOPIC_DRAWS))
    key_b = jnp.where(first_b & ~in_topic, t_b, jnp.inf)
    cands = jnp.concatenate([own, b], 1)
    _, sel = jax.lax.top_k(-jnp.concatenate([key_own, key_b], 1), st.t_pad)
    t1 = jnp.take_along_axis(cands, sel, axis=1)
    keep1 = jnp.arange(st.t_pad)[None, :] < n_topic[:, None]
    # background terms: the first distinct draws of a Zipf stream
    t2 = _base_draws(k_bg, tab["base_cdf"], (rows, BG_DRAWS))
    keep2 = _first_draws(t2, nnz - n_topic)
    tids, mask = _union_rows(jnp.concatenate([t1, t2], 1),
                             jnp.concatenate([keep1, keep2], 1),
                             st.vocab, st.t_pad)
    w = jnp.exp(st.doc_sigma * jax.random.normal(k_w, tids.shape))
    return tids, jnp.where(mask, w, 0.0).astype(jnp.float32), mask, z


def make_docs(st: Stats, seed: int, tab: Tables | None = None):
    """(tids, tw, mask, doc_topic) as host arrays: (n_docs, t_pad) int32
    term ids (-1 padded, ascending), float32 weights, bool mask, and
    each doc's topic."""
    tab = tab if tab is not None else tables(st, seed)
    dtab = {f.name: jnp.asarray(getattr(tab, f.name))
            for f in dataclasses.fields(tab)}
    key = jax.random.fold_in(seed_key(seed), 1)
    chunk = min(CHUNK, 1 << (st.n_docs - 1).bit_length())
    parts = [_doc_chunk(jax.random.fold_in(key, i), dtab, st, chunk)
             for i in range(-(-st.n_docs // chunk))]
    out = [np.concatenate([np.asarray(p[j]) for p in parts])[:st.n_docs]
           for j in range(4)]
    return tuple(out)


@partial(jax.jit, static_argnames=("st", "n"))
def _query_rows(key, tab: dict, z, st: Stats, n: int):
    k_nnz, k_pick, k_base, k_w = jax.random.split(key, 4)
    nnz = jnp.clip(jax.random.poisson(k_nnz, st.query_terms, (n,)),
                   2, st.q_pad).astype(jnp.int32)
    topic_size = tab["topic_terms"].shape[1]
    n_topic = jnp.minimum(
        jnp.maximum(1, jnp.round(nnz * st.query_sharpness)), topic_size
    ).astype(jnp.int32)
    # topical terms: a uniform draw without replacement from the topic
    perm = jnp.argsort(jax.random.uniform(k_pick, (n, topic_size)), axis=1)
    own = jnp.take_along_axis(tab["topic_terms"][z], perm, axis=1)
    keep1 = jnp.arange(topic_size)[None, :] < n_topic[:, None]
    t2 = _base_draws(k_base, tab["base_cdf"], (n, BG_DRAWS))
    keep2 = _first_draws(t2, jnp.maximum(0, nnz - n_topic))
    tids, mask = _union_rows(jnp.concatenate([own, t2], 1),
                             jnp.concatenate([keep1, keep2], 1),
                             st.vocab, st.q_pad)
    w = jnp.exp(st.query_sigma * jax.random.normal(k_w, tids.shape))
    return tids, jnp.where(mask, w, 0.0).astype(jnp.float32), mask


def query_topics(st: Stats, n: int, zipf_a: float, seed: int) -> np.ndarray:
    """Each query's topic: Zipf(``zipf_a``) popularity over a seeded
    permutation of the topics (uniform at ``zipf_a == 0``)."""
    rng = host_rng(seed, 2)
    perm = rng.permutation(st.n_topics)
    pz = zipf_probs(st.n_topics, zipf_a)
    return perm[rng.choice(st.n_topics, n, p=pz)].astype(np.int32)


def make_queries(st: Stats, n: int, zipf_a: float, seed: int,
                 tab: Tables | None = None):
    """(tids, tw, mask, topic) host arrays of ``n`` queries: (n, q_pad)
    ascending -1-padded term ids, float32 weights, mask, topic."""
    tab = tab if tab is not None else tables(st, seed)
    dtab = {f.name: jnp.asarray(getattr(tab, f.name))
            for f in dataclasses.fields(tab)}
    z = query_topics(st, n, zipf_a, seed)
    key = jax.random.fold_in(seed_key(seed), 3)
    out = _query_rows(key, dtab, jnp.asarray(z), st, n)
    return tuple(np.asarray(a) for a in out) + (z,)
