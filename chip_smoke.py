"""Smoke run of the served retrieval path on a TPU.

    python chip_smoke.py             # one chip: index, served traffic, kernels
    python chip_smoke.py --chips 4   # four chips: the cluster-sharded index

One chip. A seeded synthetic collection the size of BEIR TREC-COVID
(171,332 passages) at the SPLADE widths of the ``asc-splade`` config
(V=30,522, n_seg=8, d_pad=2,560, t_pad=128, q_pad=32) is clustered into
m=80 clusters with the launcher's own calls and served through the
entry points a user calls: ``StreamingFrontend`` -> ``RetrievalEngine``
-> ``retrieve``. The answers are checked against the brute-force oracle
computed on the chip: exact top-k at (mu, eta) = (1, 1), true scores
(and recall@10) at the serving default (0.9, 1.0). Then each Pallas
kernel that compiles for the TPU runs once against its reference.

Four chips. The same kind of index, cut to m=32 clusters at the same
docs per cluster, sharded by ``index_shard_specs`` over a (2, 2)
("data", "model") mesh and served through ``distributed_retrieve``, is
compared with single-device ``retrieve`` on the same queries.

One process, which starts none. Exits non-zero, without the result
line, when JAX finds no TPU or any phase fails. The last line of a
passing run is ``{"ok": true, "device": {"platform": "tpu", ...}}``.
Compile and serving seconds are printed to tell set-up from work; they
are not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: relative tolerance of every score comparison (ties at the k-th score
#: count as equal within it)
RTOL = 1e-5
#: the front-end's deadline, SLO and drain budget: generous, so that a
#: request that is not served names a fault, not a slow chip
DEADLINE_MS = 120_000.0


class SmokeFailure(RuntimeError):
    """A phase found the system wrong; the run exits non-zero."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Collection, index and traffic of one smoke run."""

    n_docs: int
    m: int
    vocab: int
    n_seg: int
    d_pad: int
    t_pad: int
    q_pad: int
    doc_terms: int = 67            # MS MARCO mean WordPiece terms/passage
    query_terms: int = 23          # SPLADE dev-query mean
    k: int = 10
    n_requests: int = 256
    max_batch: int = 64
    seed: int = 0


def splade_geometry(chips: int = 1) -> Geometry:
    """The ``asc-splade`` widths over a TREC-COVID-sized collection
    (171,332 docs, m=80: ~2,142 docs per cluster, 1.19x under d_pad).
    The four-chip run keeps the widths and the docs per cluster and
    cuts m to 32, to spend its chip time on the sharded path."""
    from repro.configs.asc_splade import config
    c = config()
    m, n_docs = (80, 171_332) if chips == 1 else (32, 68_533)
    return Geometry(n_docs=n_docs, m=m, vocab=c.vocab, n_seg=c.n_seg,
                    d_pad=c.d_pad, t_pad=c.t_pad, q_pad=c.q_pad, k=c.k)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_phase(chips: int = 1) -> dict:
    """The device JAX reports; refuses anything but ``chips`` TPUs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"JAX found no usable backend: {e}") from e
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    log(f"device: {info}")
    check(d.platform == "tpu",
          f"JAX found platform {d.platform!r} ({d.device_kind}), not a "
          f"TPU: this smoke has no CPU branch")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} TPU devices, found "
          f"{len(devices)}")
    stats = d.memory_stats() or {}
    log("memory_stats: " + json.dumps(
        {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                               "bytes_limit") if k in stats}))
    return info


def _dense_rep(docs, chunk: int = 16_384):
    """``dense_rep_projection`` over row chunks: the same function on
    slices (rows are independent, the projection is seeded), so its
    (docs, t_pad, dim) gather stays under a GiB on the device."""
    import jax.numpy as jnp

    from repro.core.clustering import dense_rep_projection
    from repro.core.types import SparseDocs
    parts = []
    for s in range(0, docs.n_docs, chunk):
        sub = SparseDocs(tids=docs.tids[s:s + chunk],
                         tw=docs.tw[s:s + chunk],
                         mask=docs.mask[s:s + chunk], vocab=docs.vocab)
        parts.append(dense_rep_projection(sub, dim=96))
    return jnp.concatenate(parts)


def index_phase(geo: Geometry):
    """Seeded corpus -> k-means -> capacity-bounded assignment ->
    ``build_index``, the calls of ``launch/serve.py``. Returns
    (spec, index, doc_topic)."""
    import jax
    import numpy as np

    from repro.core.clustering import balanced_assign, lloyd_kmeans
    from repro.core.index import build_index
    from repro.data.synthetic import CorpusSpec, make_corpus

    spec = CorpusSpec(n_docs=geo.n_docs, vocab=geo.vocab,
                      n_topics=max(8, geo.m // 2),
                      doc_terms=geo.doc_terms, t_pad=geo.t_pad,
                      query_terms=geo.query_terms, q_pad=geo.q_pad,
                      seed=geo.seed)
    t0 = time.perf_counter()
    docs, doc_topic = make_corpus(spec)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep = _dense_rep(docs)
    centers, _ = lloyd_kmeans(jax.random.PRNGKey(0), rep, k=geo.m, iters=8)
    assign = balanced_assign(rep, centers, capacity=geo.d_pad)
    index = build_index(docs, np.asarray(assign), m=geo.m,
                        n_seg=geo.n_seg, d_pad=geo.d_pad)
    jax.block_until_ready(index)
    t_build = time.perf_counter() - t0
    got = (index.m, index.d_pad, index.t_pad, index.n_seg, index.vocab)
    want = (geo.m, geo.d_pad, geo.t_pad, geo.n_seg, geo.vocab)
    check(got == want, f"index geometry {got} != requested {want}")
    live = int(index.cluster_ndocs.sum())
    check(live == geo.n_docs, f"index holds {live} of {geo.n_docs} docs")
    devs = sorted(str(d) for d in index.doc_tids.devices())
    log(f"index: {geo.n_docs} docs, m={index.m} x d_pad={index.d_pad} x "
        f"t_pad={index.t_pad}, n_seg={index.n_seg}, V={index.vocab}, "
        f"{index.nbytes() / 1e6:.1f} MB on {devs}; generation "
        f"{t_gen:.1f} s, clustering + build {t_build:.1f} s")
    return spec, index, doc_topic


def _rows(queries, lo: int, hi: int):
    from repro.core.types import QueryBatch
    return QueryBatch(tids=queries.tids[lo:hi], tw=queries.tw[lo:hi],
                      mask=queries.mask[lo:hi], vocab=queries.vocab)


def oracle_phase(index, queries, geo: Geometry):
    """Brute-force top-k of every query, on the device, one query per
    launch: one query's exhaustive scoring is a (m, d_pad, t_pad) f32
    gather (~105 MB of temp at m=80), while two or more in one program
    compile to ~13.5 GB on a v5e. Returns host (ids, scores)."""
    import jax
    import numpy as np

    from repro.core.search import brute_force_topk
    n = queries.n_queries
    fn = jax.jit(brute_force_topk, static_argnames="k")
    t0 = time.perf_counter()
    outs = [fn(index, _rows(queries, s, s + 1), k=geo.k) for s in range(n)]
    ids = np.concatenate([np.asarray(o.doc_ids) for o in outs])
    scores = np.concatenate([np.asarray(o.scores) for o in outs])
    log(f"oracle: brute-force top-{geo.k} of {n} queries on the device "
        f"in {time.perf_counter() - t0:.1f} s (compile included)")
    return ids, scores


def true_scores(index, queries, ids):
    """Brute-force score of each returned (query, doc id) pair, from
    the index's own quantized forward rows. -1 ids score 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.search import score_docs_ref
    doc_ids = np.asarray(index.doc_ids).reshape(-1)
    where = np.full(int(doc_ids.max()) + 2, -1, np.int64)
    live = doc_ids >= 0
    where[doc_ids[live]] = np.nonzero(live)[0]
    flat = np.where(ids >= 0, where[np.maximum(ids, 0)], 0)
    check(bool((flat[ids >= 0] >= 0).all()),
          "a returned doc id is not in the index")
    d_pad = index.d_pad

    @jax.jit
    def pairs(idx, qmaps, c, s):
        return jax.vmap(lambda qm, cc, ss: score_docs_ref(
            idx.doc_tids[cc, ss], idx.doc_tw[cc, ss], qm, idx.scale))(
                qmaps, c, s)

    out = pairs(index, queries.dense_map(), jnp.asarray(flat // d_pad),
                jnp.asarray(flat % d_pad))
    return np.where(ids >= 0, np.asarray(out), 0.0)


def check_exact(name: str, ids, scores, o_ids, o_scores) -> None:
    """Served top-k == oracle top-k, up to ties at the k-th score."""
    import numpy as np
    for q in range(ids.shape[0]):
        if not np.allclose(np.sort(scores[q]), np.sort(o_scores[q]),
                           rtol=RTOL, atol=0):
            raise SmokeFailure(
                f"{name}: query {q} scores {scores[q].tolist()} != oracle "
                f"{o_scores[q].tolist()}")
        diff = set(ids[q].tolist()) ^ set(o_ids[q].tolist())
        if diff:
            kth = float(o_scores[q, -1])
            near = [abs(float(s) - kth) <= RTOL * abs(kth)
                    for i, s in zip(ids[q], scores[q]) if i in diff]
            near += [abs(float(s) - kth) <= RTOL * abs(kth)
                     for i, s in zip(o_ids[q], o_scores[q]) if i in diff]
            check(all(near), f"{name}: query {q} ids {ids[q].tolist()} != "
                             f"oracle {o_ids[q].tolist()} beyond k-th ties")


def check_true(name: str, ids, scores, truth) -> None:
    """Every returned score is that doc's brute-force score."""
    import numpy as np
    ok = (ids < 0) | np.isclose(scores, truth, rtol=RTOL, atol=0)
    if not ok.all():
        q, j = np.argwhere(~ok)[0]
        raise SmokeFailure(f"{name}: query {q} doc {ids[q, j]} scored "
                           f"{scores[q, j]}, brute force {truth[q, j]}")


def recall_at_k(ids, o_ids) -> float:
    return float(sum(len(set(a.tolist()) & set(b.tolist()) - {-1})
                     / max(len(set(b.tolist()) - {-1}), 1)
                     for a, b in zip(ids, o_ids)) / len(ids))


def serve_phase(spec, index, queries, oracle, geo: Geometry) -> dict:
    """Every query one by one through a closed-loop StreamingFrontend at
    the serving default (mu, eta) = (0.9, 1.0)."""
    import numpy as np

    from repro.core.search import SearchConfig
    from repro.serving.engine import RetrievalEngine
    from repro.serving.frontend import (FrontendConfig, ServedResult,
                                        StreamingFrontend, query_rows)
    cfg = SearchConfig(k=geo.k, mu=0.9, eta=1.0)
    eng = RetrievalEngine(index, cfg)
    fe = StreamingFrontend(eng, FrontendConfig(
        max_batch=geo.max_batch, max_queue=geo.n_requests,
        default_deadline_ms=DEADLINE_MS, slo_p99_ms=DEADLINE_MS,
        drain_deadline_ms=DEADLINE_MS, closed_loop=True))
    rows = list(query_rows(queries))
    t0 = time.perf_counter()
    fe.warmup(rows[0])
    t_compile = time.perf_counter() - t0
    fe.start()
    t0 = time.perf_counter()
    try:
        futures = [fe.submit(r) for r in rows]
        outcomes = [f.result(timeout=DEADLINE_MS / 1e3) for f in futures]
    finally:
        fe.shutdown()
    t_serve = time.perf_counter() - t0
    bad = [o for o in outcomes if not isinstance(o, ServedResult)]
    check(not bad, f"{len(bad)} of {len(rows)} requests not served, e.g. "
                   f"{bad[:3]}")
    levels = {(o.mu, o.eta) for o in outcomes}
    check(levels == {(cfg.mu, cfg.eta)},
          f"requests served at (mu, eta) {sorted(levels)}, not the "
          f"serving default ({cfg.mu}, {cfg.eta})")
    cons = fe.conservation()
    check(cons["balanced"] and cons["served"] == len(rows),
          f"front-end accounting {cons}")
    ids = np.stack([o.doc_ids for o in outcomes])
    scores = np.stack([o.scores for o in outcomes])
    check_true("frontend (0.9, 1.0)", ids, scores,
               true_scores(index, queries, ids))
    recall = recall_at_k(ids, oracle[0])
    batches = eng.stats.n_requests
    log(f"frontend: {len(rows)}/{len(rows)} served in {batches} batches "
        f"at (mu, eta) = ({cfg.mu}, {cfg.eta}); every score is the "
        f"brute-force score; recall@{geo.k} {recall:.4f}; compile "
        f"{t_compile:.1f} s, serving {t_serve:.2f} s")
    return {"recall": recall, "compile_s": t_compile, "serve_s": t_serve}


def direct_phase(index, queries, oracle, geo: Geometry) -> None:
    """``RetrievalEngine.search`` at (mu, eta) = (1, 1) on batches that
    take both routes of ``engine="auto"``; ids must equal the oracle."""
    import numpy as np

    from repro.core.search import SearchConfig, resolved_engine
    from repro.serving.engine import RetrievalEngine
    cfg = SearchConfig(k=geo.k, mu=1.0, eta=1.0)
    eng = RetrievalEngine(index, cfg)
    routes = set()
    for b in sorted({1, 8, geo.max_batch}):
        q = _rows(queries, 0, b)
        t0 = time.perf_counter()
        eng.warmup(q)
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = eng.search(q)
        t_serve = time.perf_counter() - t0
        route = resolved_engine(cfg, b)
        routes.add(route)
        check_exact(f"engine.search batch {b} ({route})",
                    np.asarray(out.doc_ids), np.asarray(out.scores),
                    oracle[0][:b], oracle[1][:b])
        log(f"engine.search batch {b} ({route}, mu = eta = 1): ids equal "
            f"brute force; compile {t_compile:.1f} s, search "
            f"{t_serve * 1e3:.1f} ms")
    check(routes == {"per_query", "batched"},
          f"engine='auto' took routes {sorted(routes)}")


def kernel_phase(index, queries) -> None:
    """Each Pallas kernel once at the index's widths, against its
    reference. Interpret mode follows ``pallas_interpret_default``, which
    never interprets on a TPU. The executor kernels are compiled only:
    Mosaic refuses their in-kernel vocabulary gather (ROADMAP 1.2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.bounds import _gemm_bounds
    from repro.core.search import autotune_blocks
    from repro.kernels.plan_wave.compact import (compact_front,
                                                 compact_front_pallas)
    from repro.kernels.score_cluster_batch.score_cluster_batch import (
        score_queue_kernel)
    from repro.kernels.score_docs.score_docs import score_docs_kernel
    from repro.kernels.segment_bound.segment_bound import (
        segment_bound_gemm)
    from repro.utils import pallas_interpret_default

    interpret = pallas_interpret_default()
    qmap = queries.dense_map()[:, :index.vocab]
    table = index.seg_max_stacked.reshape(-1, index.vocab)
    got = np.asarray(segment_bound_gemm(table, qmap, index.scale))
    want = np.asarray(_gemm_bounds(table, qmap, index.scale, False))
    err = float(np.max(np.abs(got - want)) / max(np.abs(want).max(), 1e-30))
    check(err <= RTOL, f"segment_bound_gemm off the jnp bounds by {err:.3g}")
    log(f"kernel segment_bound_gemm ({table.shape[0]} x {table.shape[1]} "
        f"u8 table, {qmap.shape[0]} queries, interpret={interpret}): max "
        f"error {err:.3g} of the largest jnp bound")

    # planner widths: tile queue (G), doc sub-tiles, the doc-run scan
    # over a whole tile (d_pad)
    g = 8
    _, block_d, _ = autotune_blocks(index.d_pad, index.t_pad, index.n_seg,
                                    index.vocab, queries.n_queries, g)
    rng = np.random.default_rng(0)
    for n in sorted({g, index.d_pad // block_d, index.d_pad}):
        keep = jnp.asarray(rng.random((g, n)) < 0.3)
        got_i, got_c = compact_front_pallas(keep)
        want_i, want_c = compact_front(keep)
        check(bool((got_i == want_i).all() & (got_c == want_c).all()),
              f"compact_front_pallas != compact_front at ({g}, {n})")
        log(f"kernel compact_front_pallas ({g}, {n}), interpret="
            f"{interpret}: bit-identical to compact_front")

    sds = jax.ShapeDtypeStruct
    m, dp, tp = index.doc_tids.shape
    tid_t, v1 = index.doc_tids.dtype, index.vocab + 1
    bq = 8
    attempts = {
        "score_queue_kernel": (
            lambda *a: score_queue_kernel(*a, block_q=bq, block_d=block_d,
                                          interpret=False),
            (sds((m, dp, tp), tid_t), sds((m, dp, tp), jnp.uint8),
             sds((bq, v1), jnp.float32), sds((g,), jnp.int32),
             sds((g,), jnp.int32), sds((), jnp.int32),
             sds((g, 1), jnp.int32), sds((g,), jnp.int32),
             sds((g, 1, dp // block_d), jnp.int32),
             sds((g, 1), jnp.int32), sds((g, 1, dp), jnp.uint8))),
        "score_docs_kernel": (
            lambda *a: score_docs_kernel(*a, interpret=False),
            (sds((g * dp, tp), tid_t), sds((g * dp, tp), jnp.uint8),
             sds((v1,), jnp.float32), sds((), jnp.float32))),
    }
    for name, (fn, args) in attempts.items():
        try:
            jax.jit(fn).lower(*args).compile()
        except Exception as e:      # noqa: BLE001 — the refusal is the result
            first = str(e).strip().splitlines()[0][:200]
            log(f"kernel {name}: refused by the compiler "
                f"({type(e).__name__}: {first}); off the served path")
            continue
        raise SmokeFailure(
            f"{name} now compiles: run it against score_admitted_ref here "
            f"and consider it for the served path")


def sharded_phase(index, queries, geo: Geometry) -> None:
    """``distributed_retrieve`` over a (2, 2) ("data", "model") mesh of
    four devices vs single-device ``retrieve`` on the same queries."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.search import SearchConfig, retrieve
    from repro.launch.mesh import make_host_mesh
    from repro.serving.engine import distributed_retrieve, index_shard_specs

    check(jax.device_count() >= 4,
          f"the sharded path needs 4 devices, found {jax.device_count()}")
    mesh = make_host_mesh((2, 2))
    cfg = SearchConfig(k=geo.k, mu=1.0, eta=1.0)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), index_shard_specs(index),
        is_leaf=lambda x: isinstance(x, P))
    sharded = jax.device_put(index, shardings)
    q = _rows(queries, 0, geo.max_batch)
    q_sharded = jax.device_put(q, jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("model", None)), q,
        is_leaf=lambda x: hasattr(x, "shape")))
    lone = [str(x.sharding) for x in jax.tree_util.tree_leaves(
        (sharded, q_sharded)) if len(x.sharding.device_set) < 4]
    check(not lone, f"arrays outside the four-device mesh: {lone[:3]}")
    log(f"sharded index over {dict(mesh.shape)}: "
        f"{sharded.doc_tids.sharding.shard_shape(sharded.doc_tids.shape)} "
        f"doc_tids per device")
    t0 = time.perf_counter()
    with mesh:
        dist = jax.block_until_ready(
            distributed_retrieve(sharded, q_sharded, cfg, mesh))
    t_dist = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = jax.block_until_ready(retrieve(index, q, cfg))
    t_single = time.perf_counter() - t0
    check_exact("distributed_retrieve vs retrieve",
                np.asarray(dist.doc_ids), np.asarray(dist.scores),
                np.asarray(single.doc_ids), np.asarray(single.scores))
    log(f"distributed_retrieve (2, 2) == single-device retrieve on "
        f"{q.n_queries} queries (mu = eta = 1); first calls, compile "
        f"included: sharded {t_dist:.1f} s, single {t_single:.1f} s")


# ---------------------------------------------------------------------------


def run(chips: int) -> dict:
    from repro.utils import init_compile_cache
    cache = init_compile_cache()
    info = device_phase(chips)
    log(f"compile cache: {cache}")
    import jax

    from repro.data.synthetic import make_queries
    geo = splade_geometry(chips)
    if chips == 1:
        spec, index, doc_topic = index_phase(geo)
        queries, _ = make_queries(spec, geo.n_requests, doc_topic,
                                  seed=geo.seed + 1)
        oracle = oracle_phase(index, queries, geo)
        serve_phase(spec, index, queries, oracle, geo)
        direct_phase(index, queries, oracle, geo)
        kernel_phase(index, _rows(queries, 0, geo.max_batch))
    else:
        log(f"four-chip index cut to m={geo.m}, {geo.n_docs} docs (same "
            f"widths and docs per cluster as the one-chip run)")
        spec, index, doc_topic = index_phase(geo)
        queries, _ = make_queries(spec, geo.max_batch, doc_topic,
                                  seed=geo.seed + 1)
        sharded_phase(index, queries, geo)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device 0 peak bytes in use: {stats['peak_bytes_in_use']}")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip (default); 4: "
                         "only the cluster-sharded index over four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"[chip_smoke] FAILED: the repro package is not at {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    try:
        info = run(args.chips)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
