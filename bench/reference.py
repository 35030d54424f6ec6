"""Plain reference: exhaustive scoring of the generated corpus, and the
comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made.
The index format's weights are uint8 under one global scale (max weight
/ 255, rounded half to even in float32); the reference quantizes the
generated corpus by that rule itself, then scores every document for
every compared query in plain ``jax.numpy`` float32 (gathers, products
and a sum, no matrix unit), block by block so that it fits.
``precision="bf16"`` computes the same in bfloat16: the control, the
nearest precision below the configuration's float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: documents per scored block (a (block, t_pad, queries) gather)
BLOCK_DOCS = 1024


def quantize(tw: np.ndarray, mask: np.ndarray) -> tuple[np.ndarray, float]:
    """uint8 weights and the global scale, by the index format's rule."""
    live_max = float((tw * mask).max()) if tw.size else 1.0
    scale = max(live_max, 1e-6) / 255.0
    w = np.clip(np.round(tw / scale), 0, 255).astype(np.uint8)
    return np.where(mask, w, 0).astype(np.uint8), scale


def _qmap_t(tids, tw, mask, vocab: int, dtype):
    """(V + 1, Q) query weights by term; row V is the padding's zero."""
    q = tids.shape[0]
    safe = jnp.where(mask, tids, vocab)
    out = jnp.zeros((vocab + 1, q), jnp.float32).at[
        safe, jnp.arange(q)[:, None]].add(jnp.where(mask, tw, 0.0))
    return out.at[vocab].set(0.0).astype(dtype)


def _score(qmap_t, tids, w, dtype):
    """(rows, Q) scores of doc rows (tids (rows, t), uint8 w)."""
    g = qmap_t[tids]                                   # (rows, t, Q)
    return jnp.sum(g * w.astype(dtype)[..., None], axis=1, dtype=dtype)


@partial(jax.jit, static_argnames=("vocab", "k", "dtype"))
def _topk(doc_tids, doc_w, q_tids, q_tw, q_mask, scale, vocab, k, dtype):
    qmap_t = _qmap_t(q_tids, q_tw, q_mask, vocab, dtype)
    n_q = q_tids.shape[0]
    n_blocks = doc_tids.shape[0] // BLOCK_DOCS

    def step(carry, b):
        top_s, top_i = carry
        tids = jax.lax.dynamic_slice_in_dim(doc_tids, b * BLOCK_DOCS,
                                            BLOCK_DOCS)
        w = jax.lax.dynamic_slice_in_dim(doc_w, b * BLOCK_DOCS, BLOCK_DOCS)
        s = (_score(qmap_t, tids, w, dtype).astype(jnp.float32)
             * scale).T                                 # (Q, block)
        ids = b * BLOCK_DOCS + jnp.arange(BLOCK_DOCS, dtype=jnp.int32)
        s = jnp.where((w > 0).any(1)[None, :], s, -jnp.inf)
        cs = jnp.concatenate([top_s, s], 1)
        ci = jnp.concatenate([top_i, jnp.broadcast_to(ids, s.shape)], 1)
        top_s, pos = jax.lax.top_k(cs, k)
        return (top_s, jnp.take_along_axis(ci, pos, 1)), None

    init = (jnp.full((n_q, k), -jnp.inf), jnp.full((n_q, k), -1, jnp.int32))
    (top_s, top_i), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
    return top_i, top_s


@partial(jax.jit, static_argnames=("vocab",))
def _pairs(doc_tids, doc_w, q_tids, q_tw, q_mask, ids, scale, vocab):
    """(Q, k) float32 score of doc ``ids[q, j]`` for query q (0 at -1)."""
    qmap_t = _qmap_t(q_tids, q_tw, q_mask, vocab, jnp.float32)   # (V+1, Q)
    safe = jnp.maximum(ids, 0)
    g = qmap_t[doc_tids[safe], jnp.arange(ids.shape[0])[:, None, None]]
    s = jnp.sum(g * doc_w[safe].astype(jnp.float32), axis=-1) * scale
    return jnp.where(ids >= 0, s, 0.0)


class Reference:
    """Exhaustive scorer over a generated corpus (host arrays)."""

    def __init__(self, tids: np.ndarray, tw: np.ndarray, mask: np.ndarray,
                 vocab: int):
        self.vocab = vocab
        self.n_docs = tids.shape[0]
        w, self.scale = quantize(tw, mask)
        n_pad = -(-self.n_docs // BLOCK_DOCS) * BLOCK_DOCS
        safe = np.full((n_pad, tids.shape[1]), vocab, np.int32)
        safe[:self.n_docs] = np.where(mask, tids, vocab)
        wp = np.zeros((n_pad, tids.shape[1]), np.uint8)
        wp[:self.n_docs] = w
        self.doc_tids = jnp.asarray(safe)
        self.doc_w = jnp.asarray(wp)

    def topk(self, q_tids, q_tw, q_mask, k: int, precision: str = "f32",
             chunk: int = 256):
        """(ids, scores) of the exhaustive top-k, ``chunk`` queries per
        launch; ids are corpus row numbers."""
        dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[precision]
        ids, scores = [], []
        for s in range(0, q_tids.shape[0], chunk):
            sl = slice(s, s + chunk)
            qt, qw, qm = _pad_rows((q_tids[sl], q_tw[sl], q_mask[sl]), chunk)
            i, v = _topk(self.doc_tids, self.doc_w, qt, qw, qm,
                         jnp.float32(self.scale), self.vocab, k, dtype)
            n = min(chunk, q_tids.shape[0] - s)
            ids.append(np.asarray(i)[:n])
            scores.append(np.asarray(v)[:n])
        return np.concatenate(ids), np.concatenate(scores)

    def pair_scores(self, q_tids, q_tw, q_mask, ids) -> np.ndarray:
        """Reference score of each (query, returned doc id) pair."""
        bad = (ids >= self.n_docs)
        ids = np.where(bad, -1, ids).astype(np.int32)
        out = np.asarray(_pairs(self.doc_tids, self.doc_w,
                                jnp.asarray(q_tids), jnp.asarray(q_tw),
                                jnp.asarray(q_mask), jnp.asarray(ids),
                                jnp.float32(self.scale), self.vocab))
        return np.where(bad, np.nan, out)


def _pad_rows(arrays, n):
    out = []
    for a in arrays:
        pad = np.zeros((n,) + a.shape[1:], a.dtype)
        pad[:a.shape[0]] = a
        out.append(jnp.asarray(pad))
    return out


#: recall is read at the top-min(RECALL_AT, k), whatever the k served
RECALL_AT = 10


def _recall(ids, ref_ids) -> float:
    """Mean share of each row's valid ``ref_ids`` found in its ``ids``."""
    hits = [len(set(a[a >= 0].tolist()) & set(b[b >= 0].tolist()))
            / max(int((b >= 0).sum()), 1) for a, b in zip(ids, ref_ids)]
    return float(np.mean(hits))


def compare(ids, scores, ref_ids, ref_scores, pair) -> dict:
    """The numbers that decide ``correct``, over compared requests.

    ``score_err``: widest gap between a returned score and the
    reference score of the doc it names, over the query's best
    reference score. ``prop3_ratio``: smallest ratio of the reference
    score mass of the returned top-k to that of the exact top-k (Prop 3
    of the paper bounds it below by mu). ``malformed``: answers with a
    doc id outside the corpus, a doc twice, or scores out of order.
    ``recall``: mean share of the exact top-min(10, k) found among the
    first min(10, k) returned; ``recall_at_k``: mean share of the exact
    top-k that was returned. The exact top-10 is the exact top-k's
    head, so no second reference pass is made."""
    valid = ids >= 0
    top1 = np.maximum(np.abs(ref_scores[:, :1]), 1e-30)
    gap = np.where(valid, np.abs(scores - pair) / top1, 0.0)
    bad_id = np.isnan(pair).any(1)
    dup = np.array([len(set(r[r >= 0].tolist())) != int((r >= 0).sum())
                    for r in ids])
    order = (np.diff(np.where(valid, scores, -np.inf), axis=1) > 0).any(1)
    mass = np.where(valid, np.nan_to_num(pair), 0.0).sum(1)
    ref_mass = np.where(ref_ids >= 0, ref_scores, 0.0).sum(1)
    ratio = np.where(ref_mass > 0, mass / np.maximum(ref_mass, 1e-30), 1.0)
    head = min(RECALL_AT, ids.shape[1])
    return {
        "score_err": float(np.nan_to_num(gap, nan=np.inf).max()),
        "prop3_ratio": float(ratio.min()),
        "malformed": int((bad_id | dup | order).sum()),
        "recall": _recall(ids[:, :head], ref_ids[:, :head]),
        "recall_at_k": _recall(ids, ref_ids),
    }
