"""Observability tests: metrics registry, trace spans, funnel consistency.

The load-bearing property (docs/observability.md): the registry is not a
*parallel* accounting of the pruning funnel — per request it must equal
the TopK work counters the core engines already return, for both engine
paths and (psum'd) for the distributed path. Everything else here pins
the instruments (weighted-histogram quantiles, Prometheus exposition,
Chrome-trace schema) and the serve-loop integration (engine-vs-registry
agreement, AdaptiveBudget decay, lifecycle mirrors).
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from repro.core.search import SearchConfig, resolved_engine, retrieve
from repro.obs import (LATENCY_BUCKETS_MS, MetricsRegistry, Observability,
                       TraceRecorder, funnel_from_topk, record_funnel,
                       validate_chrome_trace)
from repro.obs.exposition import (MetricsServer, PROM_CONTENT_TYPE,
                                  validate_prometheus_text)
from repro.serving.engine import AdaptiveBudget, RetrievalEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

def test_counter_is_monotone():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "h")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    assert reg.counter("a_total") is reg.counter("a_total")
    with pytest.raises(ValueError):
        reg.gauge("a_total")
    # labelled instruments are distinct per label set, same family
    c1 = reg.counter("b_total", labels={"engine": "batched"})
    c2 = reg.counter("b_total", labels={"engine": "per_query"})
    assert c1 is not c2
    assert reg.get("b_total", {"engine": "batched"}) is c1
    assert reg.get("missing") is None


def test_histogram_weighted_quantiles_track_numpy():
    """Bucket-resolution quantiles: the estimate must land within the
    owning bucket's width of the exact numpy percentile."""
    rng = np.random.default_rng(0)
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=LATENCY_BUCKETS_MS)
    values = rng.lognormal(2.0, 1.0, 2000)       # ~1..200 ms
    for v in values:
        h.observe(v)
    bounds = (0.0,) + tuple(LATENCY_BUCKETS_MS) + (np.inf,)
    for q in (10, 50, 90, 99):
        exact = float(np.percentile(values, q))
        est = h.quantile(q)
        i = np.searchsorted(bounds, exact)       # bucket owning `exact`
        width = bounds[i] - bounds[i - 1]
        assert abs(est - exact) <= width, (q, est, exact)
    assert h.quantile(0) == pytest.approx(values.min())
    assert h.quantile(100) == pytest.approx(values.max())


def test_histogram_weight_shifts_quantile():
    reg = MetricsRegistry()
    h = reg.histogram("w_ms", buckets=(1, 10, 100))
    h.observe(0.5, weight=1)
    h.observe(50.0, weight=99)
    assert h.quantile(50) > 10.0       # the weighted mass dominates
    assert h.count == 100
    assert h.mean == pytest.approx((0.5 + 50.0 * 99) / 100)


def test_prometheus_exposition_parses_and_is_cumulative():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(3)
    reg.gauge("share", "planner share").set(0.43)
    h = reg.histogram("lat_ms", "latency", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5.0, weight=2)
    text = reg.render_prometheus()
    n = validate_prometheus_text(text)
    assert n >= 6                       # 2 scalars + 3 buckets + sum/count
    lines = text.splitlines()
    assert "# TYPE lat_ms histogram" in lines
    # _bucket samples are cumulative; +Inf equals _count
    assert 'lat_ms_bucket{le="1"} 1' in lines
    assert 'lat_ms_bucket{le="10"} 3' in lines
    assert 'lat_ms_bucket{le="+Inf"} 3' in lines
    assert "lat_ms_count 3" in lines


def test_snapshot_is_json_round_trippable():
    reg = MetricsRegistry()
    reg.counter("a_total").inc()
    reg.histogram("h_ms", buckets=(1,)).observe(0.5)
    reg.counter("lab_total", labels={"k": "v"}).inc(2)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["a_total"] == 1
    assert snap["h_ms"]["count"] == 1
    assert snap["lab_total"]['{"k": "v"}'] == 2


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------

def test_trace_recorder_writes_valid_chrome_trace(tmp_path):
    rec = TraceRecorder(str(tmp_path))
    with rec.request() as t:
        with t.span("plan", waves=2):
            pass
        with t.span("execute"):
            t.instant("wave_boundary", wave=0)
        t.set_args(batch=8)
    doc = validate_chrome_trace(str(tmp_path / "trace_000000.json"))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"request", "plan", "execute", "wave_boundary"} <= names
    req = next(e for e in doc["traceEvents"] if e["name"] == "request")
    assert req["args"]["batch"] == 8


def test_trace_sampling_and_null_request(tmp_path):
    rec = TraceRecorder(str(tmp_path), sample_every=3)
    traces = [rec.request() for _ in range(6)]
    assert [t.enabled for t in traces] == [True, False, False,
                                           True, False, False]
    # the disabled recorder hands out the inert singleton: no clock, no
    # files, the span surface all no-ops
    off = TraceRecorder(None)
    t = off.request()
    assert t.enabled is False
    with t:
        with t.span("anything", x=1) as s:
            s.set_args(y=2)
    assert t.finish() is None
    assert not list(tmp_path.glob("trace_0000[1-9]*.json"))


def test_metrics_server_serves_both_views():
    reg = MetricsRegistry()
    reg.counter("served_total", "h").inc(7)
    srv = MetricsServer(reg, port=0, host="127.0.0.1")
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/metrics") as r:
            assert r.headers["Content-Type"] == PROM_CONTENT_TYPE
            text = r.read().decode()
        assert validate_prometheus_text(text) >= 1
        assert "served_total 7" in text
        with urllib.request.urlopen(f"{base}/metrics.json") as r:
            assert json.load(r)["served_total"] == 7
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# funnel consistency: registry == TopK counters, per request
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["batched", "per_query"])
def test_funnel_counters_match_engine(index, queries, engine):
    """One observed request: every funnel stage counter in the registry
    must equal the value recomputed from the returned TopK — the
    registry is a view of the engine's own accounting, not a parallel
    one."""
    q, _ = queries
    cfg = SearchConfig(k=10, mu=0.9, eta=1.0, engine=engine)
    obs = Observability()
    eng = RetrievalEngine(index, cfg, obs=obs)
    out = eng.search(q)

    batched = resolved_engine(cfg, q.n_queries) == "batched"
    assert batched == (engine == "batched")
    expect = funnel_from_topk(out, batched=batched, n_q=q.n_queries,
                              d_pad=index.d_pad, budget_clusters=index.m)
    for key, name in (("clusters_budgeted", "funnel_clusters_budgeted_total"),
                      ("clusters_scored", "funnel_clusters_scored_total"),
                      ("segments_scored", "funnel_segments_scored_total"),
                      ("tiles_walked", "funnel_tiles_walked_total"),
                      ("tiles_scored", "funnel_tiles_scored_total"),
                      ("doc_slots_walked", "funnel_doc_slots_walked_total"),
                      ("docs_scored", "funnel_docs_scored_total"),
                      ("merge_candidates",
                       "funnel_merge_candidates_total")):
        got = obs.registry.get(name).value
        assert got == expect[key], (name, got, expect[key])
    # serve accounting agrees with the engine's stats object
    assert obs.registry.get("serve_queries_total").value == q.n_queries
    assert obs.registry.get("serve_requests_total").value == 1


def test_funnel_invariants(index, queries):
    """The funnel only narrows: tiles scored <= tiles walked, and the
    executor's walked doc slots never exceed whole-tile execution of the
    scored tiles (n_walked_docs <= n_scored_tiles * d_pad)."""
    q, _ = queries
    obs = Observability()
    eng = RetrievalEngine(index, SearchConfig(k=10, mu=0.9, eta=1.0,
                                              engine="batched"), obs=obs)
    eng.search(q)
    g = lambda n: obs.registry.get(n).value
    assert g("funnel_tiles_scored_total") <= g("funnel_tiles_walked_total")
    assert (g("funnel_doc_slots_walked_total")
            <= g("funnel_tiles_scored_total") * index.d_pad)
    assert g("funnel_clusters_scored_total") \
        <= g("funnel_clusters_budgeted_total")
    assert 0.0 < g("funnel_tile_compaction_ratio") <= 1.0
    assert 0.0 < g("funnel_doc_compaction_ratio") <= 1.0


def test_funnel_from_topk_sums_one_slot_per_query_shard():
    """Batched counters are replicated per query *shard*, not per
    batch: with n_query_shards the batch total is one representative
    slot per shard, summed — slot [0] alone undercounts by the
    model-axis factor."""
    out = types.SimpleNamespace(
        n_walked_tiles=np.array([7, 7, 7, 7, 5, 5, 5, 5]),
        n_scored_tiles=np.array([3, 3, 3, 3, 2, 2, 2, 2]),
        n_walked_docs=np.array([30, 30, 30, 30, 20, 20, 20, 20]),
        n_waves=np.array([6, 6, 6, 6, 4, 4, 4, 4]),
        n_scored_docs=np.arange(8),
        n_scored_clusters=np.ones(8, np.int64),
        n_scored_segments=np.ones(8, np.int64),
        n_merge_candidates=np.arange(8) * 3,
        # level-0 counters are batch-level too (ISSUE 9): same
        # one-representative-slot-per-shard arithmetic as the tile
        # counters, same undercount if slot [0] were used alone
        n_walked_superblocks=np.array([4, 4, 4, 4, 3, 3, 3, 3]),
        n_pruned_superblocks=np.array([2, 2, 2, 2, 3, 3, 3, 3]),
        n_bounded_clusters=np.array([9, 9, 9, 9, 6, 6, 6, 6]))
    f = funnel_from_topk(out, batched=True, n_q=8, d_pad=16,
                         budget_clusters=4, n_query_shards=2)
    assert f["tiles_walked"] == 7 + 5
    assert f["tiles_scored"] == 3 + 2
    assert f["doc_slots_walked"] == 30 + 20
    assert f["waves"] == 6 + 4
    assert f["docs_scored"] == int(np.arange(8).sum())
    # per-query on every engine: summed over all slots
    assert f["merge_candidates"] == 3 * int(np.arange(8).sum())
    assert f["superblocks_walked"] == 4 + 3
    assert f["superblocks_pruned"] == 2 + 3
    assert f["clusters_bounded"] == 9 + 6
    # default single shard keeps the slot-[0] semantics
    f1 = funnel_from_topk(out, batched=True, n_q=8, d_pad=16,
                          budget_clusters=4)
    assert f1["tiles_walked"] == 7
    assert f1["waves"] == 6
    assert f1["superblocks_walked"] == 4
    assert f1["clusters_bounded"] == 9
    # the per-query engine sums every slot regardless of sharding
    fp = funnel_from_topk(out, batched=False, n_q=8, d_pad=16,
                          budget_clusters=4, n_query_shards=2)
    assert fp["tiles_walked"] == 4 * 7 + 4 * 5
    assert fp["waves"] == 4 * 6 + 4 * 4
    assert fp["superblocks_walked"] == 4 * 4 + 4 * 3
    assert fp["clusters_bounded"] == 4 * 9 + 4 * 6


def test_funnel_accumulates_across_requests(index, queries):
    q, _ = queries
    obs = Observability()
    eng = RetrievalEngine(index, SearchConfig(k=10, mu=0.9, eta=1.0),
                          obs=obs)
    eng.search(q)
    one = obs.registry.get("funnel_docs_scored_total").value
    eng.search(q)
    assert obs.registry.get("funnel_docs_scored_total").value == 2 * one
    assert obs.registry.get("serve_requests_total").value == 2


def test_distributed_funnel_matches_psum_counters():
    """The distributed wrapper's registry recording must equal the
    funnel recomputed from its returned (already psum'd) TopK — run on
    a forced 8-device host mesh in a subprocess (dry-run isolation
    rule, see tests/test_distributed.py)."""
    body = """
import jax, numpy as np
assert jax.device_count() == 8, jax.devices()
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.index import build_index
from repro.core.search import SearchConfig, resolved_engine
from repro.data.synthetic import CorpusSpec, make_corpus, make_queries
from repro.obs import MetricsRegistry, funnel_from_topk
from repro.serving.engine import distributed_retrieve, index_shard_specs

spec = CorpusSpec(n_docs=800, vocab=256, n_topics=8, seed=3)
docs, doc_topic = make_corpus(spec)
q, _ = make_queries(spec, 8, doc_topic, seed=4)
idx = build_index(docs, doc_topic % 16, m=16, n_seg=4)
mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = SearchConfig(k=10, mu=1.0, eta=1.0)
reg = MetricsRegistry()
with mesh:
    ispecs = index_shard_specs(idx)
    i_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), ispecs,
        is_leaf=lambda x: isinstance(x, P))
    idx_s = jax.device_put(idx, i_shard)
    q_s = jax.device_put(q, jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("model", None)), q,
        is_leaf=lambda x: hasattr(x, "shape")))
    out = jax.block_until_ready(
        distributed_retrieve(idx_s, q_s, cfg, mesh, registry=reg))

n_shards = mesh.shape["model"]
n_local = q.n_queries // n_shards
batched = resolved_engine(cfg, n_local) == "batched"
expect = funnel_from_topk(out, batched=batched, n_q=q.n_queries,
                          d_pad=idx.d_pad, budget_clusters=idx.m,
                          n_query_shards=n_shards)
# each model shard walks its own sub-batch: the batched tile counters
# are replicated within a shard's slots, not across shards -- slot [0]
# alone undercounts by the model-axis factor
assert batched
nw = np.asarray(out.n_walked_tiles).reshape(n_shards, n_local)
assert (nw == nw[:, :1]).all()              # replicated within a shard
assert expect["tiles_walked"] == nw[:, 0].sum()
# level-0 counters (ISSUE 9): n_bounded_clusters is psum'd over the
# cluster axes (each data shard bounds its local slab -> global m),
# then replicated per model shard like every batch-level counter --
# the funnel's one-slot-per-shard total is m per model-shard walk
assert expect["clusters_bounded"] == idx.m * n_shards
assert expect["superblocks_walked"] == idx.n_super * n_shards
assert expect["superblocks_pruned"] == 0
for key, name in (("clusters_scored", "funnel_clusters_scored_total"),
                  ("tiles_walked", "funnel_tiles_walked_total"),
                  ("tiles_scored", "funnel_tiles_scored_total"),
                  ("doc_slots_walked", "funnel_doc_slots_walked_total"),
                  ("docs_scored", "funnel_docs_scored_total"),
                  ("clusters_bounded", "funnel_clusters_bounded_total"),
                  ("superblocks_walked",
                   "funnel_superblocks_walked_total"),
                  ("superblocks_pruned",
                   "funnel_superblocks_pruned_total")):
    got = reg.get(name).value
    assert got == expect[key], (name, got, expect[key])
assert reg.get("funnel_docs_scored_total").value > 0
print("distributed funnel consistent")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


# ---------------------------------------------------------------------------
# serve-loop integration
# ---------------------------------------------------------------------------

def test_engine_traces_and_split_sampling(index, queries, tmp_path):
    """Traced requests write schema-valid Chrome traces with the engine's
    span hierarchy, every span measured; a split-sampled traced request
    adds one instant per wave with its exact admission counts, and
    tracing alone samples no split."""
    q, _ = queries
    obs = Observability(trace_dir=str(tmp_path), trace_sample_every=2,
                        split_every=4)
    eng = RetrievalEngine(index, SearchConfig(k=10, mu=0.9, eta=1.0,
                                              engine="batched"), obs=obs)
    eng.warmup(q)
    for _ in range(4):
        eng.search(q)
    traces = sorted(glob.glob(str(tmp_path / "trace_*.json")))
    assert len(traces) == 2                  # every 2nd request sampled
    for i, p in enumerate(traces):
        doc = validate_chrome_trace(p)
        events = doc["traceEvents"]
        spans = {e["name"]: e for e in events if e["ph"] == "X"}
        assert set(spans) >= {"request", "engine.search", "engine.prepare",
                              "engine.launch", "engine.wait",
                              "engine.account"}, (p, sorted(spans))
        # no span reports a duration it did not measure: the replay's
        # plan/execute/per-wave spans are gone
        assert not {"plan", "execute", "topk_merge",
                    "epoch_pin"} & set(spans)
        assert not any(n.startswith("wave_") for n in spans)
        search = spans["engine.search"]
        assert search["args"]["batch"] == q.n_queries
        for child in ("engine.prepare", "engine.launch", "engine.wait",
                      "engine.account"):
            c = spans[child]
            assert search["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= search["ts"] + search["dur"] + 1
        # per-wave instants with exact admission counts, only on the
        # request that sampled the split (request 0; request 2 did not)
        waves = [e for e in events if e["name"].startswith("wave_")]
        assert all(w["ph"] == "i" for w in waves)
        if i == 0:
            assert waves
            for w in waves:
                assert w["args"]["tiles_admitted"] >= 0
                assert w["args"]["walked_doc_slots"] >= 0
            req = spans["request"]["args"]
            assert len(waves) == req["waves"]
        else:
            assert not waves
    # split histograms recorded once per split-sampled request only
    assert obs.registry.get("split_requests_total").value == 1
    assert obs.registry.get("split_planner_ms").count == 1
    share = obs.registry.get("planner_share").value
    assert 0.0 <= share <= 1.0


def test_split_replay_stays_out_of_latency_stats(index, queries,
                                                 monkeypatch):
    """The planner/executor replay runs out-of-band: the latency
    histogram and the adaptive controller observe only the production
    jitted call, so a slow seam (the replay runs warm + timed passes,
    ~3x the jitted path) cannot corrupt the reported tail or shrink the
    cluster budget."""
    import repro.serving.engine as engine_mod
    real = engine_mod.planner_executor_split

    def slow_split(*a, **kw):
        time.sleep(0.25)
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "planner_executor_split", slow_split)
    q, _ = queries
    obs = Observability(split_every=1)
    eng = RetrievalEngine(index, SearchConfig(k=10, mu=0.9, eta=1.0,
                                              engine="batched"),
                          adaptive=AdaptiveBudget(target_ms=5.0),
                          obs=obs)
    eng.warmup(q)
    eng.search(q)
    assert obs.registry.get("split_requests_total").value == 1
    # the >=0.5 s the seam spent (warm + timed pass) never reaches the
    # batch-latency histogram the controller and p99 read
    assert eng.stats.p(100) < 250.0


def test_engine_step_text_names_every_phase(index, queries):
    """The compiled text of the served step carries each phase scope in
    its instructions' op_name, which maps a device capture's operations
    to the phases."""
    from repro.core.search import PHASE_SCOPES
    q, _ = queries
    eng = RetrievalEngine(index, SearchConfig(k=10, mu=0.9, eta=1.0))
    eng.warmup(q)
    text = eng.step_text(q)
    for scope in PHASE_SCOPES:
        assert f"/{scope}/" in text, scope
    with pytest.raises(ValueError, match="pipelined"):
        RetrievalEngine(index, SearchConfig(engine="pipelined")).step_text(q)


def test_next_request_rids_unique_under_threads():
    """rid assignment + sampling decisions are atomic: concurrent
    engine threads (natural with the threaded MetricsServer) must never
    see duplicate rids."""
    obs = Observability(split_every=4)
    rids: list = []

    def worker():
        for _ in range(200):
            rid, _, _ = obs.next_request()
            rids.append(rid)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(rids) == list(range(8 * 200))


def test_engine_without_obs_records_nothing_extra(index, queries):
    q, _ = queries
    eng = RetrievalEngine(index, SearchConfig(k=10, mu=0.9, eta=1.0))
    eng.search(q)
    names = {i.name for i in eng.stats.registry.instruments()}
    assert names == {"serve_batch_latency_ms", "serve_queries_total",
                     "serve_requests_total", "serve_time_seconds_total"}


def test_adaptive_budget_decays_on_empty_observations():
    """A cost spike followed by fully-pruned batches must not pin the
    budget at its floor forever (the observe() no-op bug): empty
    observations decay the EMA toward the floor."""
    ab = AdaptiveBudget(target_ms=1.0, init_cost_ms=0.05, ema=0.9)
    ab.observe(clusters_scored=10, elapsed_ms=100.0)   # spike
    spiked = ab.cost_ms
    assert ab.budget() <= 8 / 0.9                      # pinned low
    for _ in range(200):
        ab.observe(clusters_scored=0, elapsed_ms=0.01)
    assert ab.cost_ms < spiked
    assert ab.cost_ms == pytest.approx(ab.cost_floor_ms)
    assert ab.budget() > 100                           # recovered


def test_engine_exports_adaptive_gauges(index, queries):
    q, _ = queries
    obs = Observability()
    eng = RetrievalEngine(index, SearchConfig(k=10, mu=1.0, eta=1.0),
                          adaptive=AdaptiveBudget(target_ms=5.0), obs=obs)
    eng.search(q)
    assert obs.registry.get("adaptive_cost_ms").value > 0
    assert obs.registry.get("adaptive_budget_clusters").value >= 8


# ---------------------------------------------------------------------------
# lifecycle mirrors
# ---------------------------------------------------------------------------

def test_lifecycle_metrics_mirror_writer(index, queries):
    from repro.lifecycle import IndexWriter
    rng = np.random.default_rng(5)
    reg = MetricsRegistry()
    writer = IndexWriter(index, seed=11, registry=reg,
                         compact_threshold=0.01)
    assert reg.get("lifecycle_epoch_swaps_total").value == 1  # init publish

    live = writer.mutable.live_ids()
    for d in live[:30]:
        writer.delete(int(d))
    for _ in range(10):
        t = rng.choice(index.vocab, 8, replace=False)
        writer.insert(t, rng.lognormal(0.0, 0.5, 8).astype(np.float32))
    writer.commit()      # slack 30/1480 > 0.01 -> compacts

    assert reg.get("index_inserts_total").value == 10
    assert reg.get("index_deletes_total").value == 30
    assert reg.get("index_compactions_total").value == 1
    assert reg.get("index_compaction_duration_seconds").count == 1
    assert reg.get("lifecycle_epoch_swaps_total").value == 2
    assert reg.get("lifecycle_epoch").value == 1
    # post-compaction: staleness gauges reset, live count mirrors
    assert reg.get("index_slack").value == 0.0
    assert reg.get("index_unsorted_tail_fraction").value == 0.0
    assert reg.get("index_live_docs").value == writer.mutable.live

    # a pinned search mirrors reader gauges through the same registry
    q, _ = queries
    obs = Observability(registry=reg)
    eng = RetrievalEngine(writer.publisher,
                          SearchConfig(k=10, mu=0.9, eta=1.0), obs=obs)
    eng.search(q)
    assert reg.get("serve_epoch").value == 1
    assert reg.get("lifecycle_pinned_readers").value == 0  # unpinned after
