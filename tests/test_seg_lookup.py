"""Segment-admission lookup: ``seg_lookup`` and the masks built on it.

Every engine masks its executor output with one bit per (query, doc),
looked up in the (query, tile, segment) admission table through the
doc's pre-modded segment id. ``core.plan.seg_lookup`` computes it with
elementwise selects instead of a gather; these cases pin it, and
``doc_admission`` / ``_union_doc_admission`` / the planner's
``dmask_union``, bit for bit against numpy fancy indexing
``seg_admit[q, g, doc_seg_mod[g, d]] & admit & doc_mask``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import (_union_doc_admission, doc_admission, plan_wave,
                             seg_lookup)

G, DP = 4, 256
N_Q, BLOCK_Q = 13, 8          # a batch the planner pads to block_q
N_QB = -(-N_Q // BLOCK_Q)


def _np_lookup(seg_admit: np.ndarray, dsm: np.ndarray) -> np.ndarray:
    """(..., G, d_pad): seg_admit[..., g, dsm[g, d]] by fancy indexing."""
    g = np.arange(dsm.shape[0])[:, None]
    return seg_admit[..., g, dsm]


def _case(n_seg: int, seed: int):
    """A wave with tombstones, a tile no query admits, and per-segment
    admission drawn independently of the tile admission."""
    rng = np.random.default_rng(seed)
    dsm = rng.integers(0, n_seg, (G, DP)).astype(np.int32)
    dmask = rng.random((G, DP)) > 0.2                        # tombstones
    dmask[:, DP - 37:] = False                               # padded tail
    seg_admit = rng.random((N_Q, G, n_seg)) < 0.4
    admit = rng.random((N_Q, G)) < 0.7
    admit[:, 1] = False                       # a tile no query admits
    seg_admit[:, 1] = False
    return seg_admit, admit, dsm, dmask


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n_seg", [1, 2, 4, 8, 33])
def test_seg_lookup_matches_fancy_indexing(n_seg, seed):
    seg_admit, _, dsm, _ = _case(n_seg, seed)
    lookup = jax.jit(seg_lookup)
    # per-query engine (G, n_seg), batch (n_q, G, n_seg), and a leading
    # query-block axis in front of the batch
    for table in (seg_admit[0], seg_admit,
                  seg_admit[:12].reshape(3, 4, G, n_seg)):
        got = np.asarray(lookup(jnp.asarray(table), jnp.asarray(dsm)))
        assert got.dtype == np.bool_
        np.testing.assert_array_equal(got, _np_lookup(table, dsm))


@pytest.mark.parametrize("n_seg", [2, 8, 33])
def test_collapsed_table_ignores_segment_ids(n_seg):
    """n_seg_eff == 1 (the anytime table) admits every doc of a tile by
    one bit, whatever segment the index stored for the doc."""
    seg_admit, _, dsm, _ = _case(n_seg, 2)
    one = seg_admit.any(axis=-1, keepdims=True)              # (n_q, G, 1)
    got = np.asarray(seg_lookup(jnp.asarray(one), jnp.asarray(dsm)))
    np.testing.assert_array_equal(got, np.broadcast_to(one, (N_Q, G, DP)))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("n_seg", [1, 2, 4, 8, 33])
def test_admission_masks_match_fancy_indexing(n_seg, seed):
    seg_admit, admit, dsm, dmask = _case(n_seg, seed)
    cids = jnp.arange(G, dtype=jnp.int32)
    live = jnp.ones((G,), bool)
    plan = plan_wave(cids, live, jnp.asarray(admit), jnp.asarray(seg_admit),
                     BLOCK_Q, jnp.asarray(dsm), jnp.asarray(dmask))

    # the executor's (query, doc) mask
    got = np.asarray(doc_admission(plan, jnp.asarray(dsm),
                                   jnp.asarray(dmask)))
    want = _np_lookup(seg_admit, dsm) & admit[:, :, None] & dmask[None]
    np.testing.assert_array_equal(got, want)

    # the planner's per-query-block union, batch padded to block_q
    seg_p = np.zeros((N_QB * BLOCK_Q, G, n_seg), bool)
    seg_p[:N_Q] = seg_admit
    seg_qb = seg_p.reshape(N_QB, BLOCK_Q, G, n_seg).any(axis=1)
    union = np.asarray(_union_doc_admission(
        jnp.asarray(seg_qb), jnp.asarray(dsm), jnp.asarray(dmask)))
    want_union = _np_lookup(seg_qb, dsm) & dmask[None]
    np.testing.assert_array_equal(union, want_union)

    # ... and as the plan carries it, per (compacted tile, qblock slot)
    dmu = np.asarray(plan.dmask_union)
    tile_pos, qblock = np.asarray(plan.tile_pos), np.asarray(plan.qblock)
    n_qblock = np.asarray(plan.n_qblock)
    assert 1 not in tile_pos[:int(plan.n_tiles)]
    for t in range(int(plan.n_tiles)):
        for s in range(int(n_qblock[t])):
            np.testing.assert_array_equal(
                dmu[t, s], want_union[qblock[t, s], tile_pos[t]])
