"""Jit'd public wrapper for the segment-bound kernel.

Interpret mode is resolved per call (always compiled on a TPU,
interpreted elsewhere unless ``REPRO_PALLAS_INTERPRET=0``) — see
``repro.utils.pallas_interpret_default``.
"""

from __future__ import annotations

import jax

from repro.kernels.segment_bound.segment_bound import (
    segment_bound_gemm as _kernel_call)
from repro.kernels.segment_bound.ref import segment_bound_gemm_ref


def segment_bound_gemm(table: jax.Array, qmap: jax.Array,
                       scale: jax.Array, **kw) -> jax.Array:
    return _kernel_call(table, qmap, scale, **kw)


__all__ = ["segment_bound_gemm", "segment_bound_gemm_ref"]
