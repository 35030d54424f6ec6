"""Distributed-path tests.

The main pytest process keeps 1 CPU device (per the dry-run isolation
rule), so every multi-device check runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Each subprocess
asserts internally and exits nonzero on failure.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


PRELUDE = """
import jax, numpy as np, jax.numpy as jnp
assert jax.device_count() == 8, jax.devices()
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
"""


def test_distributed_retrieve_matches_single():
    """shard_map selective-search layout == single-device retrieval."""
    _run(PRELUDE + """
from repro.core.index import build_index
from repro.core.search import SearchConfig, retrieve
from repro.core.types import QueryBatch
from repro.data.synthetic import CorpusSpec, make_corpus, make_queries
from repro.serving.engine import distributed_retrieve, index_shard_specs

spec = CorpusSpec(n_docs=800, vocab=256, n_topics=8, seed=3)
docs, doc_topic = make_corpus(spec)
q, _ = make_queries(spec, 8, doc_topic, seed=4)
idx = build_index(docs, doc_topic % 16, m=16, n_seg=4)

mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = SearchConfig(k=10, mu=1.0, eta=1.0)

single = retrieve(idx, q, cfg)
with mesh:
    ispecs = index_shard_specs(idx)
    i_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), ispecs,
        is_leaf=lambda x: isinstance(x, P))
    idx_sharded = jax.device_put(idx, i_shard)
    q_sharded = jax.device_put(q, jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("model", None)),
        q, is_leaf=lambda x: hasattr(x, "shape")))
    dist = distributed_retrieve(idx_sharded, q_sharded, cfg, mesh)

# rank-safe mode: identical result sets (scores sorted per query)
np.testing.assert_allclose(
    np.sort(np.asarray(dist.scores), 1),
    np.sort(np.asarray(single.scores), 1), rtol=1e-4, atol=1e-4)
print("distributed == single OK")
""")


def test_fsdp_train_step_matches_single_device():
    """LM train step under a (4, 2) mesh == unsharded single-device step."""
    _run(PRELUDE + """
from repro.configs import get_arch
from repro.models import transformer as tf
from repro.training import optimizer as opt_lib
from repro.training.train_loop import TrainConfig, make_train_step
from repro.data.pipeline import LMDataSpec, lm_batch
from repro.distributed import sharding as sh
from repro.launch.cells import _shardings

cfg = get_arch("olmo-1b").smoke_config()
B, S = 8, 32
params = tf.init_params(jax.random.PRNGKey(0), cfg)
batch = lm_batch(LMDataSpec(cfg.vocab, S + 1, B), 0)
batch = {k: v[:, :S] for k, v in batch.items()}
optimizer = opt_lib.adamw(opt_lib.constant_schedule(1e-3))
opt_state = optimizer.init(params)
step = make_train_step(lambda p, b: tf.loss_fn(p, b, cfg), optimizer,
                       TrainConfig())

# single device
p1, o1, m1 = jax.jit(step)(params, opt_state, batch, jnp.int32(0))

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = sh.lm_rules(mesh, training=True)
with mesh, sh.use_rules(rules):
    p_shard = _shardings(rules, tf.param_axes(cfg), params)
    sharded = jax.jit(step,
                      in_shardings=(p_shard, {"mu": p_shard, "nu": p_shard},
                                    {k: rules.sharding("batch", "seq")
                                     for k in batch},
                                    NamedSharding(mesh, P())),
                      out_shardings=(p_shard,
                                     {"mu": p_shard, "nu": p_shard}, None))
    p2, o2, m2 = sharded(params, opt_state, batch, jnp.int32(0))

assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, \
    (float(m1["loss"]), float(m2["loss"]))
for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=2e-2, atol=2e-3)
print("sharded train step == single device OK")
""")


def test_distributed_embedding_lookup():
    """Row-sharded mask+gather+psum lookup == plain take."""
    _run(PRELUDE + """
from repro.distributed import sharding as sh
from repro.models.embedding import embedding_lookup, embedding_init

table = embedding_init(jax.random.PRNGKey(0), 64, 16)
ids = jax.random.randint(jax.random.PRNGKey(1), (8, 5), 0, 64)
expected = np.asarray(table[ids])

mesh = jax.make_mesh((4, 2), ("data", "model"))
rules = sh.recsys_rules(mesh)
with mesh, sh.use_rules(rules):
    out = jax.jit(embedding_lookup)(table, ids)
np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)
print("distributed embedding lookup OK")
""")


def test_gradient_compression_cross_pod():
    """int8 compressed mean over a 'pod' axis ~= fp32 mean; error feedback
    carries the residual."""
    _run(PRELUDE + """
from repro.training.compression import compressed_mean

mesh = jax.make_mesh((2, 4), ("pod", "data"))
g_global = jax.random.normal(jax.random.PRNGKey(0), (2, 64)) * 0.01

def body(g):
    grads = {"w": g[0]}       # per-pod shard (leading dim split)
    mean, ef = compressed_mean(grads, None, axis="pod")
    return mean["w"], ef["w"]

fn = jax.shard_map(body, mesh=mesh,
                   in_specs=P("pod", None), out_specs=P(None),
                   check_vma=False)
with mesh:
    mean, ef = fn(g_global)
expected = np.asarray(g_global.mean(0))
got = np.asarray(mean)
scale = float(np.abs(np.asarray(g_global)).max()) / 127.0
assert np.abs(got - expected).max() <= scale + 1e-9
print("compressed mean OK")
""")


def test_elastic_checkpoint_reshard():
    """Checkpoint saved from an 8-device mesh restores onto 1 device and
    onto a different mesh shape (elastic scaling)."""
    _run(PRELUDE + """
import tempfile
from repro.training.checkpoint import CheckpointManager

mesh_a = jax.make_mesh((8,), ("data",))
x = jnp.arange(64.0).reshape(8, 8)
xs = jax.device_put(x, NamedSharding(mesh_a, P("data", None)))

with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(1, {"x": xs})
    restored = mgr.restore_into(1, {"x": xs})

    # onto a different mesh
    mesh_b = jax.make_mesh((2, 4), ("a", "b"))
    live = jax.device_put(x, NamedSharding(mesh_b, P("b", "a")))
    out = CheckpointManager.cast_like(restored, {"x": live})
    np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))
    assert out["x"].sharding == live.sharding

    # onto a single device
    out1 = CheckpointManager.cast_like(restored, {"x": x})
    np.testing.assert_array_equal(np.asarray(out1["x"]), np.asarray(x))
print("elastic reshard OK")
""")


def test_moe_a2a_matches_reference():
    """The expert-parallel all-to-all MoE (shard_map) must be numerically
    identical to the reference GSPMD dispatch at no-drop capacity."""
    _run(PRELUDE + """
from repro.models import moe as moe_lib
from repro.distributed import sharding as sh

cfg = moe_lib.MoEConfig(n_experts=8, top_k=2, d_ff_expert=64,
                        capacity_factor=4.0)   # C = T: no drops
D = 32
p = moe_lib.moe_init(jax.random.PRNGKey(0), D, cfg, "swiglu", jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, D))
ref, aux_ref = moe_lib.apply_moe(p, x, cfg, "swiglu")   # no mesh: reference

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = sh.lm_rules(mesh, training=True)
with mesh, sh.use_rules(rules):
    assert moe_lib._a2a_path_available(cfg, 4, 16)
    lowered = jax.jit(lambda p, x: moe_lib.apply_moe(p, x, cfg, "swiglu")
                      ).lower(p, x)
    assert lowered.compile().as_text().count("all-to-all") > 0, \\
        "a2a path not taken"
    out, aux = jax.jit(lambda p, x: moe_lib.apply_moe(p, x, cfg,
                                                      "swiglu"))(p, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-3, atol=2e-4)
assert abs(float(aux) - float(aux_ref)) < 1e-6
print("a2a MoE == reference OK")
""")


def test_moe_a2a_grad_matches_reference():
    """Gradients flow correctly through the shard_map a2a dispatch."""
    _run(PRELUDE + """
from repro.models import moe as moe_lib
from repro.distributed import sharding as sh

cfg = moe_lib.MoEConfig(n_experts=8, top_k=2, d_ff_expert=32,
                        capacity_factor=4.0)
D = 16
p = moe_lib.moe_init(jax.random.PRNGKey(0), D, cfg, "swiglu", jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, D))

def loss(p, x):
    y, aux = moe_lib.apply_moe(p, x, cfg, "swiglu")
    return jnp.sum(y.astype(jnp.float32) ** 2) + aux

g_ref = jax.grad(loss)(p, x)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = sh.lm_rules(mesh, training=True)
with mesh, sh.use_rules(rules):
    g = jax.jit(jax.grad(loss))(p, x)
for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                jax.tree_util.tree_leaves(g)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-4)
print("a2a MoE grads OK")
""")


def test_multipod_retrieval_mesh():
    """The (pod, data, model) retrieval layout on a small 3-axis mesh."""
    _run(PRELUDE + """
from repro.core.index import build_index
from repro.core.search import SearchConfig, retrieve
from repro.data.synthetic import CorpusSpec, make_corpus, make_queries
from repro.serving.engine import distributed_retrieve, index_shard_specs

spec = CorpusSpec(n_docs=600, vocab=256, n_topics=8, seed=5)
docs, doc_topic = make_corpus(spec)
q, _ = make_queries(spec, 4, doc_topic, seed=6)
idx = build_index(docs, doc_topic % 8, m=8, n_seg=2)

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = SearchConfig(k=5, mu=1.0, eta=1.0)
single = retrieve(idx, q, cfg)
with mesh:
    ispecs = index_shard_specs(idx, multi_pod=True)
    i_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), ispecs,
        is_leaf=lambda x: isinstance(x, P))
    idx_sharded = jax.device_put(idx, i_shard)
    q_sharded = jax.device_put(q, jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("model", None)),
        q, is_leaf=lambda x: hasattr(x, "shape")))
    dist = distributed_retrieve(idx_sharded, q_sharded, cfg, mesh,
                                multi_pod=True)
np.testing.assert_allclose(
    np.sort(np.asarray(dist.scores), 1),
    np.sort(np.asarray(single.scores), 1), rtol=1e-4, atol=1e-4)
print("multi-pod retrieval OK")
""")
