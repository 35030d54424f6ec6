"""Jit'd public wrapper for the score_docs kernel: accepts the search
layer's (..., d_pad, t_pad) cluster blocks and flattens them for the grid.

Interpret mode is resolved per call (always compiled on a TPU,
interpreted elsewhere unless ``REPRO_PALLAS_INTERPRET=0``) — see
``repro.utils.pallas_interpret_default``.
"""

from __future__ import annotations

import jax

from repro.kernels.score_docs.score_docs import score_docs_kernel
from repro.kernels.score_docs.ref import score_docs_ref


def score_docs(doc_tids: jax.Array, doc_tw: jax.Array, qmap: jax.Array,
               scale: jax.Array, **kw) -> jax.Array:
    """doc_tids/doc_tw: (..., t_pad); qmap: (V+1,). Returns (...,) scores."""
    lead = doc_tids.shape[:-1]
    t = doc_tids.shape[-1]
    flat_tids = doc_tids.reshape(-1, t)
    flat_tw = doc_tw.reshape(-1, t)
    out = score_docs_kernel(flat_tids, flat_tw, qmap, scale, **kw)
    return out.reshape(lead)


__all__ = ["score_docs", "score_docs_ref"]
