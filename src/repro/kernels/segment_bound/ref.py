"""Pure-jnp oracle for the segment-bound GEMM kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_bound_gemm_ref(table: jax.Array, qmap: jax.Array,
                           scale: jax.Array) -> jax.Array:
    """out[q, s] = scale * sum_v table[s, v] * qmap[q, v]."""
    return jnp.einsum("sv,qv->qs", table.astype(jnp.float32),
                      qmap.astype(jnp.float32),
                      precision="highest") * scale
