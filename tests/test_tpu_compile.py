"""Compile-only checks for a TPU v5e, at the ``asc-splade`` widths.

No chip is needed: the TPU compiler compiles for a described v5e and
raises what the chip's compiler would (unsupported casts, block shapes
off the (8, 128) tiling, VMEM overruns). Nothing runs, so these say
nothing about results or time. The topology is described inside a
fixture, never at import: only one process may load the TPU library,
and only the worker that runs this file does.

``score_cluster_batch`` and ``score_docs`` are not compiled here: Mosaic
refuses their in-kernel vocabulary gather (ROADMAP 1.2), and neither is
on the served path.
"""

from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.asc_splade import config

SPLADE = config()


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("m", [80, SPLADE.m])
def test_segment_bound_gemm_compiles(one_chip, m):
    """The fused bound table at the one-chip smoke's m and at the
    deployment's m=4096 (a ~1.1 GB uint8 table); the wrapper no longer
    pads the table, only the small query map."""
    from repro.kernels.segment_bound.segment_bound import segment_bound_gemm
    rows, v = m * (SPLADE.n_seg + 1), SPLADE.vocab
    compiled, text = _compile(
        lambda t, q, s: segment_bound_gemm(t, q, s, interpret=False),
        _spec(one_chip, (rows, v), jnp.uint8),
        _spec(one_chip, (64, v), jnp.float32),
        _spec(one_chip, (), jnp.float32))
    assert "tpu_custom_call" in text
    # a padded copy of the table would need two tables of temp
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * rows * v


@pytest.mark.parametrize("rows,n", [(8, 8), (8, 16), (64, 16), (8, 256)])
def test_compact_front_pallas_compiles(one_chip, rows, n):
    """Queue widths of one wave at SPLADE geometry: the tile queue
    (G=8), query-block and doc sub-tile queues (d_pad / block_d = 16),
    a batch-64 block of rows, and a doc-run candidate row."""
    from repro.kernels.plan_wave.compact import compact_front_pallas
    _, text = _compile(lambda k: compact_front_pallas(k, interpret=False),
                       _spec(one_chip, (rows, n), jnp.bool_))
    assert "tpu_custom_call" in text


_BATCH = 64


@pytest.fixture(scope="module")
def batched_step(one_chip):
    """The served step (``engine="batched"``) at SPLADE widths, m=16,
    batch 64, with the front-end's per-request (mu, eta), compiled once
    for the tests that read it."""
    from repro.core.search import SearchConfig, retrieve
    from repro.core.types import ClusterIndex, QueryBatch
    m, n_seg, dp, tp, v, b = (16, SPLADE.n_seg, SPLADE.d_pad, SPLADE.t_pad,
                              SPLADE.vocab, _BATCH)

    def s(shape, dtype):
        return _spec(one_chip, shape, dtype)

    index = ClusterIndex(
        doc_tids=s((m, dp, tp), jnp.uint16), doc_tw=s((m, dp, tp), jnp.uint8),
        doc_mask=s((m, dp), jnp.bool_), doc_ids=s((m, dp), jnp.int32),
        doc_seg=s((m, dp), jnp.int32), doc_seg_mod=s((m, dp), jnp.int32),
        seg_max_stacked=s((m, n_seg + 1, v), jnp.uint8),
        seg_offsets=s((m, n_seg + 1), jnp.int32),
        sorted_upto=s((m,), jnp.int32), scale=s((), jnp.float32),
        cluster_ndocs=s((m,), jnp.int32), super_of=s((m,), jnp.int32),
        super_members=s((4, 8), jnp.int32),
        super_max_stacked=s((4, n_seg + 1, v), jnp.uint8),
        vocab=v, n_seg=n_seg)
    queries = QueryBatch(tids=s((b, SPLADE.q_pad), jnp.int32),
                         tw=s((b, SPLADE.q_pad), jnp.float32),
                         mask=s((b, SPLADE.q_pad), jnp.bool_), vocab=v)
    cfg = SearchConfig(k=SPLADE.k, mu=SPLADE.mu, eta=SPLADE.eta,
                       engine="batched")
    return cfg, _compile(
        lambda i, q, me: retrieve(i, q, cfg, mu_eta=me), index, queries,
        s((b, 2), jnp.float32))


def test_batched_retrieve_step_compiles(batched_step):
    _, (compiled, _) = batched_step
    # the (64, 8, 2560, 128) f32 wave gather is the largest temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


# a boolean gather's output shape and slice sizes, from the step's text
_PRED_GATHER = re.compile(
    r"= pred\[([\d,]*)\]\S* gather\(.*slice_sizes=\{([\d,]*)\}")


def test_batched_step_looks_up_segments_without_a_gather(batched_step):
    """The (query, doc) admission mask and the planner's per-query-block
    union are elementwise selects (``core.plan.seg_lookup``): no gather
    of one admission bit per doc slot of a wave. As a gather, the
    batch-64 mask (64 x 8 x 2,560 = 1,310,720 entries) took most of the
    step on a v5e. The wave's own ``doc_mask`` rows are still gathered,
    a row of 2,560 slots at a time."""
    cfg, (_, text) = batched_step
    wave_slots = cfg.group_size * SPLADE.d_pad
    gathers = [(math.prod(int(d) for d in shape.split(",") if d),
                {int(d) for d in sizes.split(",") if d}, line.strip()[:160])
               for line in text.splitlines()
               for shape, sizes in _PRED_GATHER.findall(line)]
    assert gathers, "the step's boolean gathers were not found in its text"
    for n, sizes, line in gathers:
        assert n != _BATCH * wave_slots, line
        assert not (n % wave_slots == 0 and sizes == {1}), line
