"""Queue compaction: stable front-compaction of boolean admission rows.

This is the scan at the heart of the device planner: every queue the
executor scalar-prefetches (tile queue, query-block queue, doc-run
queue, doc sub-tile queue) is "indices of the True entries of a mask,
moved to the front in order, tail clamped to the last True entry".

Two device implementations with bit-identical outputs:

  * :func:`compact_front` — jitted XLA: inclusive rank via ``cumsum``,
    then the position of the (j+1)-th True entry is recovered with a
    row-wise binary search (``searchsorted`` over the monotone cumsum)
    at the already-clamped slot targets. No sort (the argsort the host
    planner used is O(n log n) comparator work and a rank-n dependency
    chain) and no scatter — XLA:CPU lowers a 2-D scatter to a serial
    per-update loop that costs ~1 ms on a (64, 250) mask, an order of
    magnitude more than the whole remaining launch.
  * :func:`compact_front_pallas` — the same contract as a Pallas TPU
    kernel (interpret mode anywhere else): the row-wise inclusive
    cumsum is a matmul against a lower-triangular ones matrix (MXU
    work, no sequential scan), and the scatter is re-expressed as a
    gather-free broadcast-compare — ``idx[b, s] = sum_p p * (keep[b, p]
    & rank[b, p] == clamp[b, s])`` — because Mosaic has no
    scatter-into-VMEM primitive. All integers ride in f32 (exact below
    2^24, far above any queue length here).

The argsort reference lives in ``ref.py``; ``tests/test_plan_wave.py``
pins all three against each other bit-exactly, including empty rows
(count 0 clamps to index 0) and full rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import pallas_interpret_default


def compact_front(keep: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Indices of True entries of ``keep`` moved to the front (stable),
    tail clamped to the last True position; plus the True count.

    keep: (..., n) bool. Returns (idx (..., n) int32, count (...,) int32).
    With no True entry the clamp degenerates to index 0 — callers gate on
    count, so the value never matters, only its validity as an index.
    """
    n = keep.shape[-1]
    lead = keep.shape[:-1]
    keep2 = keep.reshape(-1, n)
    cs = jnp.cumsum(keep2.astype(jnp.int32), axis=-1)
    count = cs[:, -1]
    pos = jnp.arange(n, dtype=jnp.int32)
    # clamp the slot targets first, then binary-search: the position of
    # the t-th True entry (1-based) is the first p with cs[p] >= t, and
    # clamped targets stay <= count so the search never falls off the
    # row (except count == 0, fixed up below)
    tgt = jnp.minimum(pos, jnp.maximum(count[:, None] - 1, 0)) + 1
    idx = jax.vmap(
        lambda c, t: jnp.searchsorted(c, t, side="left"))(cs, tgt)
    idx = jnp.where(count[:, None] > 0, idx, 0).astype(jnp.int32)
    return idx.reshape(*lead, n), count.reshape(lead)


def _iota_f32(shape, dim):
    """f32 iota (Mosaic builds iotas in integer types only)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(
        jnp.float32)


def _compact_kernel(keep_ref, idx_ref, count_ref):
    """One (BR, N) row block: tri-matmul cumsum + broadcast-compare."""
    k = keep_ref[:].astype(jnp.float32)                    # (BR, N)
    br, n = k.shape
    tri = (_iota_f32((n, n), 0) <= _iota_f32((n, n), 1)).astype(
        jnp.float32)
    cs = jnp.dot(k, tri, preferred_element_type=jnp.float32)  # inclusive
    count = cs[:, -1:]                                     # (BR, 1)
    rank = cs - 1.0
    clamp = jnp.minimum(_iota_f32((br, n), 1),
                        jnp.maximum(count - 1.0, 0.0))     # (BR, N)
    # scatter-free index build: slot s takes the position whose rank
    # equals the clamped slot (unique per row among kept entries)
    match = (k[:, :, None] > 0.0) & (rank[:, :, None] == clamp[:, None, :])
    p = _iota_f32((br, n, n), 1)
    idx_ref[:] = jnp.where(match, p, 0.0).sum(axis=1).astype(jnp.int32)
    count_ref[:] = count.astype(jnp.int32)


def compact_front_pallas(keep: jax.Array, block_rows: int = 8,
                         interpret: bool | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """Pallas variant of :func:`compact_front` — same contract,
    bit-identical outputs. Pads rows to ``block_rows`` and the queue
    axis to the 128-lane tile; padding is all-False, which changes no
    real row's count or clamped indices."""
    if interpret is None:
        interpret = pallas_interpret_default()
    n = keep.shape[-1]
    lead = keep.shape[:-1]
    keep2 = keep.reshape(-1, n)
    rows = keep2.shape[0]
    rows_p = -(-max(rows, 1) // block_rows) * block_rows
    n_p = -(-n // 128) * 128
    kp = jnp.zeros((rows_p, n_p), jnp.int32).at[:rows, :n].set(
        keep2.astype(jnp.int32))
    idx, count = pl.pallas_call(
        _compact_kernel,
        grid=(rows_p // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, n_p), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, n_p), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, n_p), jnp.int32),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(kp)
    return (idx[:rows, :n].reshape(*lead, n),
            count[:rows, 0].reshape(lead))


@functools.lru_cache(maxsize=None)
def _jitted_pallas(block_rows: int, interpret: bool):
    return jax.jit(functools.partial(
        compact_front_pallas, block_rows=block_rows, interpret=interpret))


def compact_front_pallas_jit(keep: jax.Array, block_rows: int = 8,
                             interpret: bool | None = None):
    """Jit-cached wrapper (the raw call retraces per invocation)."""
    if interpret is None:
        interpret = pallas_interpret_default()
    return _jitted_pallas(block_rows, interpret)(keep)
