"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in interpret mode on CPU (the TPU target is exercised by the
dry-run lowering); numerics must match ref.py to f32 tolerance on every
geometry, including the ragged/padded edges.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.kernels.score_docs import ops as sd_ops
from repro.kernels.score_docs import ref as sd_ref
from repro.kernels.segment_bound import ops as sb_ops
from repro.kernels.segment_bound import ref as sb_ref


def _rand_table(rng, s, v):
    return rng.integers(0, 256, (s, v)).astype(np.uint8)


def _rand_qmap(rng, q, v, density=0.05):
    m = rng.random((q, v)) < density
    return (rng.random((q, v)) * m).astype(np.float32)


# ---------------------------------------------------------------------------
# segment_bound: quantized GEMM with fused dequant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,q,v", [
    (1, 1, 1),            # degenerate
    (7, 3, 33),           # nothing aligned
    (128, 128, 512),      # exactly one block
    (130, 129, 513),      # one block + remainder
    (384, 64, 2048),      # multi-block in S and V
])
def test_segment_bound_geometries(s, q, v):
    rng = np.random.default_rng(s * 1000 + q * 10 + v)
    table = _rand_table(rng, s, v)
    qmap = _rand_qmap(rng, q, v, density=0.2)
    scale = jnp.float32(0.037)
    out = sb_ops.segment_bound_gemm(jnp.asarray(table), jnp.asarray(qmap),
                                    scale)
    ref = sb_ref.segment_bound_gemm_ref(jnp.asarray(table),
                                        jnp.asarray(qmap), scale)
    assert out.shape == (q, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    s=st.integers(1, 200),
    q=st.integers(1, 40),
    v=st.integers(1, 700),
    scale=st.floats(1e-4, 1.0),
)
def test_segment_bound_property(s, q, v, scale):
    rng = np.random.default_rng(s + q * 1000 + v * 7)
    table = _rand_table(rng, s, v)
    qmap = _rand_qmap(rng, q, v, density=0.3)
    out = sb_ops.segment_bound_gemm(jnp.asarray(table), jnp.asarray(qmap),
                                    jnp.float32(scale))
    ref = sb_ref.segment_bound_gemm_ref(jnp.asarray(table),
                                        jnp.asarray(qmap),
                                        jnp.float32(scale))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_segment_bound_block_shape_invariance():
    """The result must not depend on the BlockSpec tiling."""
    rng = np.random.default_rng(0)
    table = _rand_table(rng, 300, 900)
    qmap = _rand_qmap(rng, 17, 900, density=0.2)
    scale = jnp.float32(0.01)
    base = sb_ops.segment_bound_gemm(jnp.asarray(table), jnp.asarray(qmap),
                                     scale)
    for bs, bq, bv in [(64, 32, 256), (256, 128, 1024), (128, 8, 128)]:
        out = sb_ops.segment_bound_gemm(
            jnp.asarray(table), jnp.asarray(qmap), scale,
            block_s=bs, block_q=bq, block_v=bv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-5, atol=1e-5)


def test_segment_bound_zero_query():
    rng = np.random.default_rng(1)
    table = _rand_table(rng, 64, 256)
    qmap = np.zeros((4, 256), np.float32)
    out = sb_ops.segment_bound_gemm(jnp.asarray(table), jnp.asarray(qmap),
                                    jnp.float32(0.5))
    assert float(jnp.abs(out).max()) == 0.0


# ---------------------------------------------------------------------------
# score_docs: fused forward-index scoring
# ---------------------------------------------------------------------------

def _rand_docs(rng, d, t, v):
    tids = rng.integers(0, v + 1, (d, t)).astype(np.int32)  # v = zero slot
    tw = rng.integers(0, 256, (d, t)).astype(np.uint8)
    return tids, tw


def _rand_dense_qmap(rng, v, density=0.1):
    m = rng.random(v + 1) < density
    qm = (rng.random(v + 1) * m).astype(np.float32)
    qm[v] = 0.0
    return qm


@pytest.mark.parametrize("d,t,v", [
    (1, 1, 8),
    (17, 5, 64),
    (256, 64, 512),       # one block
    (300, 48, 1000),      # block + remainder
])
def test_score_docs_geometries(d, t, v):
    rng = np.random.default_rng(d + t + v)
    tids, tw = _rand_docs(rng, d, t, v)
    qmap = _rand_dense_qmap(rng, v)
    scale = jnp.float32(0.02)
    out = sd_ops.score_docs(jnp.asarray(tids), jnp.asarray(tw),
                            jnp.asarray(qmap), scale)
    ref = sd_ref.score_docs_ref(jnp.asarray(tids), jnp.asarray(tw),
                                jnp.asarray(qmap), scale)
    assert out.shape == (d,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    d=st.integers(1, 400),
    t=st.integers(1, 80),
    v=st.integers(4, 600),
)
def test_score_docs_property(d, t, v):
    rng = np.random.default_rng(d * 31 + t * 7 + v)
    tids, tw = _rand_docs(rng, d, t, v)
    qmap = _rand_dense_qmap(rng, v, density=0.3)
    scale = jnp.float32(0.013)
    out = sd_ops.score_docs(jnp.asarray(tids), jnp.asarray(tw),
                            jnp.asarray(qmap), scale)
    ref = sd_ref.score_docs_ref(jnp.asarray(tids), jnp.asarray(tw),
                                jnp.asarray(qmap), scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_score_docs_pad_slot_is_zero():
    """Terms pointing at the V landing slot contribute nothing."""
    v = 64
    tids = np.full((8, 10), v, np.int32)
    tw = np.full((8, 10), 255, np.uint8)
    qmap = _rand_dense_qmap(np.random.default_rng(2), v, density=1.0)
    out = sd_ops.score_docs(jnp.asarray(tids), jnp.asarray(tw),
                            jnp.asarray(qmap), jnp.float32(1.0))
    assert float(jnp.abs(out).max()) == 0.0


def test_score_docs_block_invariance():
    rng = np.random.default_rng(3)
    tids, tw = _rand_docs(rng, 500, 32, 256)
    qmap = _rand_dense_qmap(rng, 256)
    scale = jnp.float32(0.1)
    outs = [
        sd_ops.score_docs(jnp.asarray(tids), jnp.asarray(tw),
                          jnp.asarray(qmap), scale, block_d=bd)
        for bd in (64, 128, 512)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# kernel path == jnp path inside the full search
# ---------------------------------------------------------------------------

def test_kernel_bounds_match_gather_in_search(index, queries):
    from repro.core.bounds import segment_bounds_gather, segment_bounds_gemm
    q, _ = queries
    b_gather = segment_bounds_gather(index, q)
    b_gemm = segment_bounds_gemm(index, q, use_kernel=False)
    b_kernel = segment_bounds_gemm(index, q, use_kernel=True)
    np.testing.assert_allclose(np.asarray(b_gather), np.asarray(b_gemm),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b_kernel), np.asarray(b_gemm),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# interpret-mode policy and the compile cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,env,want", [
    ("tpu", None, False), ("tpu", "0", False), ("tpu", "1", "raises"),
    ("tpu", "true", "raises"), ("cpu", None, True), ("cpu", "1", True),
    ("cpu", "0", False)])
def test_pallas_interpret_default_never_interprets_on_tpu(
        monkeypatch, backend, env, want):
    from repro import utils
    monkeypatch.setattr(utils.jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", env)
    if want == "raises":
        with pytest.raises(RuntimeError, match="TPU"):
            utils.pallas_interpret_default()
    else:
        assert utils.pallas_interpret_default() is want


@pytest.mark.parametrize("env", [None, "/some/shared/cache"])
def test_init_compile_cache_honours_env_else_checkout(monkeypatch, env):
    from repro import utils
    before = jax.config.jax_compilation_cache_dir
    calls = []
    monkeypatch.setattr(utils.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert utils.init_compile_cache() == utils.CHECKOUT_COMPILE_CACHE
        assert calls == [("jax_compilation_cache_dir",
                          utils.CHECKOUT_COMPILE_CACHE)]
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert utils.CHECKOUT_COMPILE_CACHE == os.path.join(root,
                                                            ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert utils.init_compile_cache() == env
        assert calls == []
    assert jax.config.jax_compilation_cache_dir == before
