"""Pallas TPU kernel: quantized segment-bound GEMM with fused dequant.

Computes ``out[q, s] = scale * sum_v table[s, v] * qmap[q, v]`` where
``table`` is the uint8 segmented maximum term-weight table of shape
``(S = m * n_seg, V)`` and ``qmap`` is a batch of dense query maps.

This is the paper's new per-segment data structure turned into an
MXU-resident contraction (DESIGN.md §6): instead of per-cluster hash
lookups of query-term maxima (the CPU hot loop the paper optimizes in §3.1,
whose cost grows with #clusters x #query-terms), one blocked GEMM streams
the 1-byte table through VMEM once per query batch.

Blocking: grid = (S/BS, Q/BQ, V/BV), V innermost so each (q, s) output tile
accumulates in VMEM across the V stream; the uint8 tile is dequantized in
registers right before the dot. MXU-aligned tile defaults (128x128x512).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import pallas_interpret_default


def _kernel(scale_ref, table_ref, qmap_ref, out_ref, *, n_v: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Mosaic has no uint8 -> float32 cast: widen through int32
    t = table_ref[...].astype(jnp.int32).astype(jnp.float32)  # (BS, BV)
    q = qmap_ref[...]                               # (BQ, BV)
    acc = jax.lax.dot_general(
        q, t, (((1,), (1,)), ((), ())),
        # full f32 contraction: a bound rounded low stops dominating
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)         # (BQ, BS)
    out_ref[...] += acc

    @pl.when(k == n_v - 1)
    def _epilogue():
        out_ref[...] *= scale_ref[0]


@functools.partial(
    jax.jit,
    static_argnames=("block_s", "block_q", "block_v", "interpret"))
def segment_bound_gemm(
    table: jax.Array,            # (S, V) uint8
    qmap: jax.Array,             # (Q, V) float32
    scale: jax.Array,            # () float32
    *,
    block_s: int = 128,
    block_q: int = 128,
    block_v: int = 512,
    interpret: bool | None = None,
) -> jax.Array:                  # (Q, S) float32
    if interpret is None:        # backend auto-detect + env override
        interpret = pallas_interpret_default()
    S, V = table.shape
    Q = qmap.shape[0]
    # the table is never copied (at asc-splade it is ~1.1 GB): its edge
    # blocks overhang S and V, and the overhang holds unspecified but
    # finite uint8 values. Only the small query map is padded, with
    # zeros, so overhanging vocab columns contribute exactly 0 and
    # overhanging rows land in output columns sliced off below.
    q_pad = -Q % block_q
    v_pad = -V % block_v
    if q_pad or v_pad:
        qmap = jnp.pad(qmap, ((0, q_pad), (0, v_pad)))
    Qp, Vp = qmap.shape
    n_s, n_q, n_v = pl.cdiv(S, block_s), Qp // block_q, Vp // block_v

    out = pl.pallas_call(
        functools.partial(_kernel, n_v=n_v),
        grid=(n_s, n_q, n_v),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scale (1,)
            pl.BlockSpec((block_s, block_v), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_q, block_v), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_q, block_s), lambda i, j, k: (j, i)),
        out_shape=jax.ShapeDtypeStruct((Qp, n_s * block_s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(scale.reshape(1), table, qmap)
    return out[:Q, :S]
