"""ASC retrieval serving engine.

Single-host path: jitted batched retrieval with any SearchConfig.
Distributed path (``distributed_retrieve``): the selective-search layout —
clusters shard over ('pod', 'data'), the query batch shards over 'model';
every shard runs the *full* two-level (mu, eta) search on its local
clusters and a k-sized all-gather + top-k merge assembles the global
result. Rank-safety composes: per-shard theta is a lower bound of global
theta, so per-shard pruning is never more aggressive than global pruning
— the merged result satisfies the same (mu, eta) guarantees.

Time budgets: the paper's ms budget becomes a *cluster visitation budget*
(visitation order is identical to Anytime Ranking's, so early-termination
semantics match; see DESIGN.md §2). ``AdaptiveBudget`` converts a latency
target to a budget from observed per-cluster cost — the serving-loop
feedback controller.

Observability (repro.obs, docs/observability.md): pass an
:class:`repro.obs.Observability` to the engine and every ``search``
records the full pruning funnel (clusters budgeted -> tiles walked ->
tiles scored -> doc slots walked -> docs scored) plus latency histograms
into its metrics registry; traced requests write their spans as
Perfetto-loadable Chrome-trace JSON, and every ``split_every``-th
request splits planner vs executor wall time through the
:func:`planner_executor_split` seam (a replay, out-of-band: latency
histograms and the adaptive budget only ever observe the production
jitted call). Every search, with or without ``obs``, opens the profiler
spans ``engine.search`` > ``engine.prepare`` / ``engine.launch`` /
``engine.wait`` / ``engine.account`` (repro.obs.trace.host_span).
"""

from __future__ import annotations

import collections
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.search import (SearchConfig, planner_executor_split,
                               resolved_engine, retrieve,
                               _retrieve_arrays)
from repro.core.types import ClusterIndex, QueryBatch, TopK
from repro.lifecycle.snapshot import IndexSnapshot, SnapshotPublisher
from repro.obs.funnel import Observability, funnel_from_topk, record_funnel
from repro.obs.metrics import (LATENCY_BUCKETS_MS, MetricsRegistry)
from repro.obs.trace import NULL_REQUEST, host_span


class ServeStats:
    """Serve-loop accounting on registry instruments.

    Tail-latency semantics (docs/perf.md §tail-latency): ``record``
    observes one *batch* latency into the ``serve_batch_latency_ms``
    histogram with weight ``n_queries``, so ``p(99)`` answers "the batch
    latency the 99th-percentile query experienced". The previous
    implementation appended the batch-*mean* per-query ms to a deque and
    took percentiles over those means — a percentile over batch means,
    which underestimates the real tail whenever batch sizes or batch
    latencies vary. ``latencies_ms`` survives as a bounded window of
    recent per-query means for eyeballing; percentiles no longer read
    it, and memory is O(buckets + window) under any traffic.

    Snapshot GC metrics (mirrored from the publisher after every search
    when serving a live index): ``epoch_reader_counts`` is the live pin
    count per epoch, ``max_epoch_lifetime_s`` the longest any superseded
    epoch has been held alive by in-flight readers, and
    ``collected_epochs`` how many old epochs have been garbage-collected
    so far.
    """

    def __init__(self, registry: MetricsRegistry | None = None,
                 window: int = 4096):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.window = window
        self.latencies_ms: collections.deque = collections.deque(
            maxlen=window)
        self._hist = self.registry.histogram(
            "serve_batch_latency_ms",
            "batch latency, weighted by the batch's query count",
            buckets=LATENCY_BUCKETS_MS)
        self._queries = self.registry.counter(
            "serve_queries_total", "queries served")
        self._requests = self.registry.counter(
            "serve_requests_total", "search requests (batches) served")
        self._time = self.registry.counter(
            "serve_time_seconds_total", "wall time spent in search")
        # end-to-end (queue + service) per-request latency, recorded by
        # the streaming front-end: a cumulative histogram for the
        # exposition plus a bounded recent window, because the closed-
        # loop degradation controller needs a p99 that *recovers* when
        # the overload clears — a forever histogram would hold the
        # breach long after the queue drained (docs/serving.md). The
        # histogram is registered lazily on first observe_request so an
        # engine serving without a front-end exposes only the batch-
        # level instruments.
        self._req_hist = None
        self.request_latencies_ms: collections.deque = collections.deque(
            maxlen=window)
        # lifecycle mirror (plain attributes, same surface as before)
        self.epoch_reader_counts: dict = {}
        self.max_epoch_lifetime_s: float = 0.0
        self.collected_epochs: int = 0

    @property
    def n_queries(self) -> int:
        return int(self._queries.value)

    @property
    def n_requests(self) -> int:
        return int(self._requests.value)

    @property
    def total_time_s(self) -> float:
        return self._time.value

    @property
    def mean_ms(self) -> float:
        """Mean per-query latency (total time / total queries)."""
        return self._time.value * 1e3 / max(self.n_queries, 1)

    def p(self, q: float) -> float:
        """Weighted percentile of *batch* latency ms: the batch latency
        the q-th percentile query experienced (histogram-bucket
        resolution)."""
        return self._hist.quantile(q)

    def record(self, n_queries: int, elapsed_s: float) -> float:
        batch_ms = elapsed_s * 1e3
        self._hist.observe(batch_ms, weight=max(n_queries, 1))
        self._queries.inc(n_queries)
        self._requests.inc()
        self._time.inc(elapsed_s)
        per_query_ms = batch_ms / max(n_queries, 1)
        self.latencies_ms.append(per_query_ms)
        return per_query_ms

    def observe_request(self, latency_ms: float) -> None:
        """One end-to-end request latency (queue wait + service),
        recorded by the streaming front-end at completion time."""
        if self._req_hist is None:
            self._req_hist = self.registry.histogram(
                "serve_request_latency_ms",
                "end-to-end request latency (queue wait + service)",
                buckets=LATENCY_BUCKETS_MS)
        self._req_hist.observe(latency_ms)
        self.request_latencies_ms.append(latency_ms)

    def windowed_p(self, q: float) -> float:
        """Percentile of *recent* end-to-end request latency — the
        closed-loop degradation controller's SLO signal (exact over the
        window, not bucketed; 0.0 before any request completes)."""
        if not self.request_latencies_ms:
            return 0.0
        return float(np.percentile(
            np.asarray(self.request_latencies_ms, dtype=np.float64), q))


class AdaptiveBudget:
    """Latency target -> cluster budget, from an online cost estimate.

    ``observe`` with ``clusters_scored == 0`` (a fully-pruned batch)
    carries no cost signal, but it must not freeze the estimate: after a
    load spike inflated ``cost_ms``, a run of fully-pruned batches used
    to leave the budget stuck at its floor forever. Empty observations
    now decay the EMA toward ``cost_floor_ms``, so the budget recovers
    at the same time constant the estimator rises with.
    """

    def __init__(self, target_ms: float, init_cost_ms: float = 0.05,
                 ema: float = 0.9, cost_floor_ms: float = 1e-3):
        self.target_ms = target_ms
        self.cost_ms = init_cost_ms
        self.ema = ema
        self.cost_floor_ms = cost_floor_ms

    def budget(self) -> int:
        return max(8, int(self.target_ms / max(self.cost_ms, 1e-6)))

    def observe(self, clusters_scored: float, elapsed_ms: float) -> None:
        if clusters_scored > 0:
            c = elapsed_ms / clusters_scored
            self.cost_ms = self.ema * self.cost_ms + (1 - self.ema) * c
        else:
            # no work happened: decay toward the floor instead of
            # freezing, so a post-spike estimate cannot pin the budget
            self.cost_ms = max(self.ema * self.cost_ms,
                               self.cost_floor_ms)


#: health states, in gauge order: serve_health_state reports the index
HEALTH_STATES = ("healthy", "degraded", "recovering")

#: independent degradation causes the machine tracks. ``writer_fault``
#: is the PR 7 write-plane arc; ``overload`` is the streaming
#: front-end's closed-loop (mu, eta) degradation (docs/serving.md).
HEALTH_CAUSES = ("writer_fault", "overload")

#: composite severity: a degraded cause dominates a recovering one
_STATE_SEVERITY = {"healthy": 0, "recovering": 1, "degraded": 2}


class HealthStateMachine:
    """Serving health, as the read path sees it — per *cause*.

    ::

        healthy --(fault/overload)--> degraded --(recovery begins /
        ladder steps back up)--> recovering --(recovered epoch
        republished / ladder back at full fidelity)--> healthy

    ``degraded -> healthy`` directly is also legal (a transient fault
    cleared by a plain retry, no recovery needed) and ``recovering ->
    degraded`` (a recovery attempt failed; backoff and retry). Readers
    never block on any of this — they keep serving the publisher's
    last-good epoch — so the machine is bookkeeping for operators
    (``serve_health_state`` gauge, transition counter) and for the serve
    loop's retry/backoff policy, not a request gate.

    Two *causes* progress independently through that matrix:
    ``writer_fault`` (the durable write plane, PR 7) and ``overload``
    (the streaming front-end's closed-loop degradation ladder). The
    legality check is per cause — a writer fault while the front-end is
    shedding load is ``to("degraded", cause="writer_fault")`` on a
    machine whose overload cause is already degraded, and both must
    clear before ``state`` reads healthy again. The composite ``state``
    is the worst cause (degraded > recovering > healthy), mirrored in
    ``serve_health_state``; per-cause states are mirrored in
    ``serve_health_cause_state{cause=...}``. ``cause`` defaults to
    ``writer_fault`` so every pre-existing call site keeps its meaning.
    """

    _LEGAL = {
        "healthy": {"degraded"},
        "degraded": {"recovering", "healthy"},
        "recovering": {"healthy", "degraded"},
    }

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry
        self.cause_states = {c: "healthy" for c in HEALTH_CAUSES}
        self.reason = ""
        self.transitions: list[tuple[str, str, str, str]] = []
        self._mirror()

    @property
    def state(self) -> str:
        """Composite health: the worst state over all causes."""
        return max(self.cause_states.values(),
                   key=_STATE_SEVERITY.__getitem__)

    def to(self, state: str, reason: str = "",
           cause: str = "writer_fault") -> None:
        if state not in HEALTH_STATES:
            raise ValueError(f"unknown health state {state!r}")
        if cause not in HEALTH_CAUSES:
            raise ValueError(f"unknown health cause {cause!r}; "
                             f"choose from {HEALTH_CAUSES}")
        cur = self.cause_states[cause]
        if state == cur:
            return
        if state not in self._LEGAL[cur]:
            raise ValueError(
                f"illegal health transition {cur!r} -> {state!r} "
                f"(cause={cause})")
        self.transitions.append((cur, state, reason, cause))
        self.cause_states[cause] = state
        self.reason = reason
        self._mirror()
        if self.registry is not None:
            self.registry.counter(
                "serve_health_transitions_total",
                "health state machine transitions",
                labels={"to": state, "cause": cause}).inc()

    @property
    def healthy(self) -> bool:
        return self.state == "healthy"

    def _mirror(self) -> None:
        if self.registry is not None:
            self.registry.gauge(
                "serve_health_state",
                "composite serving health: 0 healthy, 1 degraded, "
                "2 recovering").set(HEALTH_STATES.index(self.state))
            for cause, st in self.cause_states.items():
                self.registry.gauge(
                    "serve_health_cause_state",
                    "per-cause health: 0 healthy, 1 degraded, "
                    "2 recovering",
                    labels={"cause": cause}).set(
                    HEALTH_STATES.index(st))


class RetrievalEngine:
    """Batched ASC serving with latency accounting.

    ``source`` may be a plain :class:`ClusterIndex` (static serving), an
    :class:`IndexSnapshot`, or a :class:`SnapshotPublisher` (live index
    under mutation): each search pins the publisher's current epoch for
    the whole request, so a concurrent epoch swap never changes the result
    of an in-flight query. The budget is passed to the jitted search as a
    *traced* scalar, so the ``adaptive`` latency feedback loop retargets
    the cluster budget every batch without recompiling.

    ``obs`` (optional :class:`repro.obs.Observability`) turns on
    per-request funnel/latency recording and — on sampled requests —
    the planner/executor split + trace spans. ``self.stats`` records
    into ``obs.registry`` when given, so the CLI, the exposition
    endpoint and the benchmarks read one source of truth.
    """

    def __init__(self, source: ClusterIndex | IndexSnapshot
                 | SnapshotPublisher, cfg: SearchConfig,
                 adaptive: AdaptiveBudget | None = None,
                 stats_window: int = 4096,
                 obs: Observability | None = None):
        if isinstance(source, ClusterIndex):
            source = IndexSnapshot.of(source, epoch=0)
        self._source = source
        self.cfg = cfg
        self.adaptive = adaptive
        self.obs = obs
        self.stats = ServeStats(
            registry=obs.registry if obs is not None else None,
            window=stats_window)
        # write-plane health as seen from the read path; the serve loop
        # drives transitions, searches only observe (never block)
        self.health = HealthStateMachine(
            registry=obs.registry if obs is not None else None)
        self.last_epoch: int | None = None
        if cfg.engine == "pipelined":
            # host-driven wave loop: jitting happens per launch inside
            # retrieve_pipelined (plan / fused-exec), not around the
            # whole search — the host driver IS the pipeline. Per-request
            # (mu, eta) is not plumbed through the device plan launches;
            # the front-end refuses the combination up front.
            from repro.core.search import retrieve_pipelined

            def _fn(idx, q, budget, mu_eta=None):
                if mu_eta is not None:
                    raise ValueError(
                        "per-request mu_eta is not supported on "
                        "engine='pipelined'")
                return retrieve_pipelined(idx, q, cfg, budget=budget)

            self._fn = _fn
        else:
            self._fn = jax.jit(
                lambda idx, q, budget, mu_eta=None: retrieve(
                    idx, q, cfg, budget=budget, mu_eta=mu_eta))
        self._split_warm = False

    def _resolve(self) -> IndexSnapshot:
        if isinstance(self._source, SnapshotPublisher):
            return self._source.current
        return self._source

    @property
    def index(self) -> ClusterIndex:
        """The index the next search will run against."""
        return self._resolve().index

    def _budget(self, snap: IndexSnapshot) -> jnp.ndarray:
        m = snap.index.m
        if self.adaptive is not None:
            b = min(self.adaptive.budget(), m)
            # an explicitly configured budget stays a hard cap — the
            # controller may only tighten it, never exceed it
            if self.cfg.cluster_budget is not None:
                b = min(b, self.cfg.cluster_budget)
        elif self.cfg.cluster_budget is not None:
            b = self.cfg.cluster_budget
        else:
            b = m + 1                      # unbudgeted
        return jnp.int32(b)

    def warmup(self, queries: QueryBatch, mu_eta=None) -> None:
        """Pay jit compilation outside the recorded loop. ``mu_eta``
        selects the per-request-fidelity trace (a different jit cache
        entry than the scalar path — the frontend warms that one)."""
        snap = self._resolve()
        jax.block_until_ready(
            self._fn(snap.index, queries, self._budget(snap), mu_eta))

    def step_text(self, queries: QueryBatch, mu_eta=None) -> str:
        """The compiled HLO text of the step that a search of a batch
        shaped like ``queries`` runs (compiled, or loaded from the
        compile cache, as ``warmup`` does). A TPU profiler capture names
        the step's operations by their instructions here, and each
        instruction's ``op_name`` holds its ``asc.*`` phase scope
        (``repro.core.search.PHASE_SCOPES``)."""
        if self.cfg.engine == "pipelined":
            raise ValueError("engine='pipelined' runs a host loop of "
                             "device launches, not one step program")
        snap = self._resolve()
        return self._fn.lower(snap.index, queries, self._budget(snap),
                              mu_eta).compile().as_text()

    # -- the serving hot path ---------------------------------------------
    def search(self, queries: QueryBatch,
               mu_eta: jnp.ndarray | None = None,
               budget_frac: float | None = None) -> TopK:
        """Serve one batch. ``mu_eta`` (optional (n_q, 2) float32) is the
        per-request fidelity override — the streaming front-end stamps
        each request with its degradation-ladder step so one batch mixes
        degraded and full-fidelity requests. ``budget_frac`` scales the
        effective cluster budget (the ladder's batch-level knob: the most
        degraded request in the batch sets it)."""
        obs = self.obs
        if not self.health.healthy and obs is not None:
            obs.registry.counter(
                "serve_degraded_requests_total",
                "requests served off the last-good epoch while the "
                "write plane was degraded or recovering").inc()
        n = queries.n_queries
        if obs is None:
            with host_span("engine.search", batch=n):
                return self._search_impl(queries, None, NULL_REQUEST,
                                         False, mu_eta, budget_frac)
        rid, trace, want_split = obs.next_request()
        with trace:
            with obs.tracer.maybe_profile(rid):
                with trace.span("engine.search", batch=n):
                    out = self._search_impl(queries, obs, trace,
                                            want_split, mu_eta,
                                            budget_frac)
        return out

    def _search_impl(self, queries: QueryBatch, obs, trace,
                     want_split: bool, mu_eta=None,
                     budget_frac: float | None = None) -> TopK:
        live = isinstance(self._source, SnapshotPublisher)
        with trace.span("engine.prepare", live=live):
            # pin one epoch for this request (counted as a live reader
            # when serving a publisher, so GC metrics see in-flight
            # queries)
            snap = self._source.pin() if live else self._resolve()
            budget = self._budget(snap)
            if budget_frac is not None:
                # ladder degradation: scale the *effective* budget
                # (clamped to m first so an unbudgeted m+1 sentinel
                # scales sanely)
                b = min(int(budget), snap.index.m)
                budget = jnp.int32(max(8, int(b * budget_frac)))
        try:
            t0 = time.perf_counter()
            with trace.span("engine.launch"):
                out = self._fn(snap.index, queries, budget, mu_eta)
            with trace.span("engine.wait"):
                out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            # plan recording (the split seam's replay hook) does not
            # exist on the two-level walk — sampled superblock requests
            # skip the split, keeping production latency untouched
            if want_split and not self.cfg.superblocks:
                # out-of-band replay through the split seam for the
                # share metrics; `dt` above stays the production jitted
                # latency, so the latency histogram and the adaptive
                # controller never observe the seam's warm/replay passes
                with trace.span("engine.split"):
                    self._search_split(snap, queries, budget, obs, trace)
        finally:
            if live:
                self._source.unpin(snap)
        with trace.span("engine.account"):
            self._account(obs, trace, snap, live, queries, out, budget, dt)
        return out

    def _account(self, obs, trace, snap, live: bool, queries, out,
                 budget, dt: float) -> None:
        """Host bookkeeping after the step: serve stats, the funnel,
        lifecycle mirrors and the adaptive budget."""
        per_query_ms = self.stats.record(queries.n_queries, dt)
        self.last_epoch = snap.epoch
        if obs is not None:
            self._record_request(obs, trace, snap, queries, out, budget,
                                 dt)
        if live:
            gc = self._source.gc_stats()
            self.stats.epoch_reader_counts = gc["live_readers"]
            self.stats.max_epoch_lifetime_s = gc["max_epoch_lifetime_s"]
            self.stats.collected_epochs = gc["collected_epochs"]
            if obs is not None:
                self._mirror_lifecycle(obs.registry, gc, snap)
        if self.adaptive is not None:
            self.adaptive.observe(float(out.n_scored_clusters.mean()),
                                  per_query_ms)
            if obs is not None:
                reg = obs.registry
                reg.gauge("adaptive_cost_ms",
                          "EMA per-cluster cost estimate").set(
                    self.adaptive.cost_ms)
                reg.gauge("adaptive_budget_clusters",
                          "cluster budget the controller will grant "
                          "next batch").set(self.adaptive.budget())

    def _search_split(self, snap, queries, budget, obs, trace) -> None:
        """Sampled request, run *after* (and outside the timing of) the
        production jitted search: replay the batch through the shared
        timing seam — a plan-recording walk + executor-only replay —
        record the split histograms, and on a traced request add one
        ``wave_NNN`` instant per wave with its exact admission counts.
        The replay's wall time is deliberately never fed to
        ``stats.record``/``adaptive.observe``: those see only the plain
        jitted path's latency."""
        if not self._split_warm:
            # compile the plans/replay path outside any timing so the
            # first sampled request doesn't record a compile as planner
            # time (the seam warms too, but through the jit cache)
            planner_executor_split(snap.index, queries, self.cfg,
                                   budget=budget, reps=1)
            self._split_warm = True
        _, waves, split = planner_executor_split(
            snap.index, queries, self.cfg, budget=budget, reps=1)
        reg = obs.registry
        reg.histogram("split_planner_ms",
                      "planner wall time per sampled request "
                      "(bounds + admission + queues + merge)").observe(
            split["planner_ms"])
        reg.histogram("split_executor_ms",
                      "executor-replay wall time per sampled "
                      "request").observe(split["executor_ms"])
        reg.gauge("planner_share",
                  "last sampled request: planner wall-time share of "
                  "the walk (batched: non-replayable remainder; "
                  "pipelined: device plan-launch stalls at the "
                  "dispatch boundary — docs/observability.md)").set(
            split["planner_share"])
        reg.counter("split_requests_total",
                    "requests that ran the planner/executor split").inc()
        if "plan_launches" in split:
            reg.gauge("pipeline_plan_launches",
                      "device plan launches in the last sampled "
                      "pipelined request").set(split["plan_launches"])
            reg.gauge("pipeline_fused_waves",
                      "waves that shared a fused executor launch in "
                      "the last sampled pipelined request").set(
                split["fused_waves"])
        for w in waves:
            trace.instant(f"wave_{w['wave']:03d}", **w)

    def _record_request(self, obs, trace, snap, queries, out, budget,
                        dt) -> None:
        n_q = queries.n_queries
        engine = resolved_engine(self.cfg, n_q)
        # the pipelined engine shares the batched engine's batch-level
        # counter semantics (its TopK is bit-identical by construction)
        batched = engine in ("batched", "pipelined")
        funnel = funnel_from_topk(
            out, batched=batched, n_q=n_q, d_pad=snap.index.d_pad,
            budget_clusters=min(int(budget), snap.index.m))
        record_funnel(obs.registry, funnel)
        obs.registry.gauge("serve_epoch",
                           "epoch of the most recent search").set(
            snap.epoch)
        trace.set_args(batch=n_q, epoch=snap.epoch,
                       engine=engine if batched else "per_query",
                       batch_ms=round(dt * 1e3, 3),
                       **{k: v for k, v in funnel.items()
                          if k != "d_pad"})

    @staticmethod
    def _mirror_lifecycle(registry, gc: dict, snap) -> None:
        registry.gauge("lifecycle_pinned_readers",
                       "live pinned readers across epochs").set(
            sum(gc["live_readers"].values()))
        registry.gauge("lifecycle_max_epoch_lifetime_seconds",
                       "longest any superseded epoch was held alive "
                       "by readers").set(gc["max_epoch_lifetime_s"])
        registry.gauge("lifecycle_collected_epochs",
                       "superseded epochs garbage-collected").set(
            gc["collected_epochs"])


# ---------------------------------------------------------------------------
# Distributed retrieval (shard_map over the cluster axis)
# ---------------------------------------------------------------------------

def index_shard_specs(index: ClusterIndex,
                      multi_pod: bool = False) -> ClusterIndex:
    """PartitionSpecs for every ClusterIndex field (clusters sharded);
    metadata copied from the live index so the pytree structures match."""
    c = ("pod", "data") if multi_pod else ("data",)
    return ClusterIndex(
        doc_tids=P(c, None, None), doc_tw=P(c, None, None),
        doc_mask=P(c, None), doc_ids=P(c, None), doc_seg=P(c, None),
        doc_seg_mod=P(c, None),
        seg_max_stacked=P(c, None, None), seg_offsets=P(c, None),
        sorted_upto=P(c), scale=P(),
        cluster_ndocs=P(c),
        # the superblock layer does not shard over clusters: super_of is
        # a per-cluster row (shards fine), but the coarse tables span
        # *global* cluster ids and are replicated — the distributed path
        # is single-level (superblocks raise below), the specs just keep
        # the pytree structurally complete
        super_of=P(c), super_members=P(), super_max_stacked=P(),
        vocab=index.vocab, n_seg=index.n_seg)


def distributed_retrieve(index: ClusterIndex, queries: QueryBatch,
                         cfg: SearchConfig, mesh,
                         multi_pod: bool = False,
                         registry: MetricsRegistry | None = None) -> TopK:
    """shard_map retrieval: local two-level search per cluster shard,
    global top-k merge via all_gather over the cluster axes.

    With ``registry`` the (already psum'd, hence global) work counters
    of the result are folded into the same pruning-funnel metrics the
    single-host engine records — the recording is host-side and forces
    a device sync, which the serving callers (launch/serve.py) do
    anyway to time the batch."""
    if cfg.superblocks:
        raise ValueError(
            "superblocks=True is not supported on the distributed path: "
            "the replicated coarse tables index global cluster ids, "
            "which a cluster shard's local arrays cannot resolve")
    out = _distributed_topk(index, queries, cfg, mesh, multi_pod)
    if registry is not None:
        # counter semantics are set by the engine each *shard* ran — the
        # auto route keys on the shard-local batch (queries shard over
        # the model axis), and each query shard's batched counters are
        # replicated only within its own sub-batch, so the funnel sums
        # one representative slot per query shard
        n_shards = mesh.shape["model"]
        n_local = queries.n_queries // n_shards
        batched = resolved_engine(cfg, max(n_local, 1)) in (
            "batched", "pipelined")
        m = index.m
        budget = cfg.cluster_budget if cfg.cluster_budget is not None \
            else m
        funnel = funnel_from_topk(
            out, batched=batched, n_q=queries.n_queries,
            d_pad=index.d_pad, budget_clusters=min(budget, m),
            n_query_shards=n_shards)
        record_funnel(registry, funnel)
    return out


@partial(jax.jit, static_argnames=("cfg", "mesh", "multi_pod"))
def _distributed_topk(index: ClusterIndex, queries: QueryBatch,
                      cfg: SearchConfig, mesh, multi_pod: bool) -> TopK:
    """The shard_map program of :func:`distributed_retrieve`, jitted so
    that each (cfg, mesh, shapes) compiles once — called eagerly, the
    shard_map retraced and recompiled its body on every batch."""
    caxes = ("pod", "data") if multi_pod else ("data",)
    qaxis = "model"
    ispecs = index_shard_specs(index, multi_pod)
    qspec = QueryBatch(tids=P(qaxis, None), tw=P(qaxis, None),
                       mask=P(qaxis, None), vocab=queries.vocab)

    def local(index_local: ClusterIndex, q_local: QueryBatch) -> TopK:
        # full two-level search on the local clusters with the configured
        # engine (batched by default: shard-local waves are planned into
        # compacted work queues and executed exactly like the single-host
        # core — each local tile fetched once per batch, only if admitted)
        (ids, scores, nd, nc, ns, nt, nw, nwd, nwv,
         nbc, nws, nps) = _retrieve_arrays(index_local, q_local, cfg)
        # merge the per-shard top-k across the cluster axes
        for ax in caxes:
            all_scores = jax.lax.all_gather(scores, ax, axis=1, tiled=True)
            all_ids = jax.lax.all_gather(ids, ax, axis=1, tiled=True)
            scores, pos = jax.lax.top_k(all_scores, cfg.k)
            ids = jnp.take_along_axis(all_ids, pos, axis=1)
        nd = jax.lax.psum(nd, caxes)
        nc = jax.lax.psum(nc, caxes)
        ns = jax.lax.psum(ns, caxes)
        nt = jax.lax.psum(nt, caxes)
        nw = jax.lax.psum(nw, caxes)
        nwd = jax.lax.psum(nwd, caxes)
        # clusters-bounded is per-shard work -> psum to the global m;
        # the superblock walk/prune counters are NOT psum'd: they count
        # against the *replicated* coarse table, so summing over cluster
        # shards would overcount it shards-fold (the PR-6 shard-shape
        # lesson, applied at level 0)
        nbc = jax.lax.psum(nbc, caxes)
        # each cluster shard walks its own waves; the batch waited for
        # the longest walk
        nwv = jax.lax.pmax(nwv, caxes)
        return TopK(doc_ids=ids, scores=scores, n_scored_docs=nd,
                    n_scored_clusters=nc, n_scored_segments=ns,
                    n_scored_tiles=nt, n_walked_tiles=nw,
                    n_walked_docs=nwd, n_waves=nwv, n_bounded_clusters=nbc,
                    n_walked_superblocks=nws, n_pruned_superblocks=nps)

    out_specs = TopK(doc_ids=P(qaxis, None), scores=P(qaxis, None),
                     n_scored_docs=P(qaxis), n_scored_clusters=P(qaxis),
                     n_scored_segments=P(qaxis), n_walked_tiles=P(qaxis),
                     n_scored_tiles=P(qaxis), n_walked_docs=P(qaxis),
                     n_waves=P(qaxis), n_bounded_clusters=P(qaxis),
                     n_walked_superblocks=P(qaxis),
                     n_pruned_superblocks=P(qaxis))
    fn = jax.shard_map(local, mesh=mesh, in_specs=(ispecs, qspec),
                       out_specs=out_specs, check_vma=False)
    return fn(index, queries)
