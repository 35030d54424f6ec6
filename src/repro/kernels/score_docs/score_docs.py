"""Pallas TPU kernel: fused forward-index document scoring.

Computes ``score[d] = scale * sum_t qmap[tid[d, t]] * w_u8[d, t]`` — the
RankScore of Formula (1) over the cluster-blocked forward layout. The dense
query map (V+1 floats, ~120 KB for a WordPiece vocab) is pinned whole in
VMEM and gathered per document term; this is the TPU-idiomatic replacement
for posting-list traversal (DESIGN.md §2): gather-from-VMEM beats
scatter-into-accumulators on a VPU, and all control flow (skipping) happens
one level up via cluster/segment masks.

Grid over document blocks; each step loads a (BD, T) tile of term ids +
quantized weights, gathers the query weights, and reduces along T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import pallas_interpret_default


def _kernel(scale_ref, tids_ref, tw_ref, qmap_ref, out_ref):
    tids = tids_ref[...].astype(jnp.int32)                # (BD, T)
    # Mosaic has no uint8 -> float32 cast: widen through int32
    tw = tw_ref[...].astype(jnp.int32).astype(jnp.float32)  # (BD, T)
    qv = jnp.take(qmap_ref[...], tids, axis=0,
                  indices_are_sorted=False, unique_indices=False)
    score = jnp.sum(qv * tw, axis=-1, keepdims=True)      # (BD, 1)
    out_ref[...] = score * scale_ref[0]


@functools.partial(
    jax.jit, static_argnames=("block_d", "interpret"))
def score_docs_kernel(
    doc_tids: jax.Array,        # (D, T) integer in [0, V] (V = zero slot)
    doc_tw: jax.Array,          # (D, T) uint8
    qmap: jax.Array,            # (V + 1,) float32, qmap[V] == 0
    scale: jax.Array,           # () float32
    *,
    block_d: int = 256,
    interpret: bool | None = None,
) -> jax.Array:                 # (D,) float32
    if interpret is None:       # backend auto-detect + env override
        interpret = pallas_interpret_default()
    D, T = doc_tids.shape
    d_pad = -D % block_d
    if d_pad:
        doc_tids = jnp.pad(doc_tids, ((0, d_pad), (0, 0)),
                           constant_values=qmap.shape[0] - 1)
        doc_tw = jnp.pad(doc_tw, ((0, d_pad), (0, 0)))
    Dp = doc_tids.shape[0]

    out = pl.pallas_call(
        _kernel,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # scale
            pl.BlockSpec((block_d, T), lambda i: (i, 0)),
            pl.BlockSpec((block_d, T), lambda i: (i, 0)),
            pl.BlockSpec(qmap.shape, lambda i: (0,)),           # whole qmap
        ],
        out_specs=pl.BlockSpec((block_d, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Dp, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(scale.reshape(1), doc_tids, doc_tw, qmap)
    return out[:D, 0]
