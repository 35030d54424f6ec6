"""Streaming front-end: typed shedding, deadline batching, the
closed-loop degradation controller, the health transition matrix, and
the no-hang property under random arrival/fault schedules."""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.search import SearchConfig, retrieve
from repro.lifecycle.faults import FaultInjected, FaultSchedule, install
from repro.obs.metrics import MetricsRegistry
from repro.serving.engine import (HEALTH_CAUSES, HealthStateMachine,
                                  RetrievalEngine, ServeStats)
from repro.serving.frontend import (DeadlineExceeded, DegradationController,
                                    FrontendConfig, LadderStep, Rejected,
                                    ServedResult, SimClock,
                                    StreamingFrontend, default_ladder,
                                    query_rows)

from _prop import given, settings, st


# ---------------------------------------------------------------------------
# HealthStateMachine transition matrix (both causes)
# ---------------------------------------------------------------------------

_STATES = ("healthy", "degraded", "recovering")
_LEGAL = {("healthy", "degraded"), ("degraded", "recovering"),
          ("degraded", "healthy"), ("recovering", "healthy"),
          ("recovering", "degraded")}


def _drive_to(h: HealthStateMachine, state: str, cause: str) -> None:
    """Walk the machine to ``state`` along legal edges."""
    if state == "healthy":
        return
    h.to("degraded", cause=cause)
    if state == "recovering":
        h.to("recovering", cause=cause)


@pytest.mark.parametrize("cause", HEALTH_CAUSES)
@pytest.mark.parametrize("dst", _STATES)
@pytest.mark.parametrize("src", _STATES)
def test_health_transition_matrix(src, dst, cause):
    """Every (src, dst) pair, for each cause: legal edges move the
    per-cause state, same-state is a no-op, everything else raises and
    leaves the machine untouched."""
    h = HealthStateMachine()
    _drive_to(h, src, cause)
    before = len(h.transitions)
    if src == dst:
        h.to(dst, cause=cause)              # no-op, not an error
        assert h.cause_states[cause] == src
        assert len(h.transitions) == before
    elif (src, dst) in _LEGAL:
        h.to(dst, "test", cause=cause)
        assert h.cause_states[cause] == dst
        assert h.transitions[-1] == (src, dst, "test", cause)
    else:
        with pytest.raises(ValueError, match="illegal health transition"):
            h.to(dst, cause=cause)
        assert h.cause_states[cause] == src
        assert len(h.transitions) == before


def test_health_rejects_unknown_state_and_cause():
    h = HealthStateMachine()
    with pytest.raises(ValueError, match="unknown health state"):
        h.to("on_fire")
    with pytest.raises(ValueError, match="unknown health cause"):
        h.to("degraded", cause="cosmic_rays")


def test_health_composite_is_worst_cause():
    """writer_fault and overload progress independently; the composite
    state is the worst of the two and both must clear before the
    machine reads healthy."""
    h = HealthStateMachine()
    assert h.state == "healthy" and h.healthy
    h.to("degraded", "wal fsync failed", cause="writer_fault")
    assert h.state == "degraded"
    # simultaneous: overload degrades while the writer is already down
    h.to("degraded", "p99 breach", cause="overload")
    assert h.cause_states == {"writer_fault": "degraded",
                              "overload": "degraded"}
    assert h.state == "degraded"
    # one cause recovering, the other still degraded -> still degraded
    h.to("recovering", cause="writer_fault")
    assert h.state == "degraded"
    # overload clears entirely; writer still recovering -> recovering
    h.to("recovering", cause="overload")
    h.to("healthy", cause="overload")
    assert h.cause_states["overload"] == "healthy"
    assert h.state == "recovering" and not h.healthy
    h.to("healthy", cause="writer_fault")
    assert h.state == "healthy" and h.healthy


def test_health_transitions_mirrored_per_cause():
    reg = MetricsRegistry()
    h = HealthStateMachine(reg)
    h.to("degraded", cause="overload")
    snap = reg.snapshot()
    assert '{"cause": "overload"}' in str(
        snap["serve_health_cause_state"])
    counts = snap["serve_health_transitions_total"]
    assert sum(v for k, v in counts.items() if "overload" in k) == 1


# ---------------------------------------------------------------------------
# Frontend fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(index):
    cfg = SearchConfig(k=10, mu=0.9, eta=1.0, engine="batched")
    return RetrievalEngine(index, cfg, stats_window=128)


@pytest.fixture(scope="module")
def rows(queries):
    q, _ = queries
    return list(query_rows(q))


def _frontend(engine, rows, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_queue", 8)
    kw.setdefault("default_deadline_ms", 200.0)
    fe = StreamingFrontend(engine, FrontendConfig(**kw), clock=SimClock())
    fe.warmup(rows[0])
    return fe


# ---------------------------------------------------------------------------
# Batching, shedding, deadlines
# ---------------------------------------------------------------------------


def test_served_result_carries_fidelity(engine, rows):
    fe = _frontend(engine, rows)
    futs = [fe.submit(r) for r in rows[:4]]     # max_batch -> dispatches
    assert fe.pump() == 4
    for f in futs:
        out = f.result(timeout=0)
        assert isinstance(out, ServedResult)
        assert out.level == 0
        assert out.mu == engine.cfg.mu and out.eta == engine.cfg.eta
        assert out.deadline_met
        assert out.doc_ids.shape == (engine.cfg.k,)
    assert fe.conservation()["balanced"]


def test_queue_full_sheds_typed(engine, rows):
    fe = _frontend(engine, rows, max_batch=8, max_queue=2,
                   max_linger_ms=1e9)
    f1, f2, f3 = (fe.submit(rows[i]) for i in range(3))
    out = f3.result(timeout=0)
    assert isinstance(out, Rejected) and out.reason == "queue_full"
    assert not f1.done() and not f2.done()      # still queued, not hung
    fe.shutdown(drain_deadline_ms=1e4)
    assert isinstance(f1.result(timeout=0), ServedResult)
    assert fe.conservation()["balanced"]


def test_past_deadline_on_arrival(engine, rows):
    fe = _frontend(engine, rows)
    out = fe.submit(rows[0], deadline_ms=0.0).result(timeout=0)
    assert isinstance(out, DeadlineExceeded)
    assert fe.conservation()["balanced"]


def test_queued_requests_expire(engine, rows):
    fe = _frontend(engine, rows, max_batch=8, max_linger_ms=1e9,
                   dispatch_margin_ms=0.0, init_service_ms=0.0)
    f = fe.submit(rows[0], deadline_ms=10.0)
    fe.clock.advance(0.02)                      # sail past the deadline
    fe.pump()
    out = f.result(timeout=0)
    assert isinstance(out, DeadlineExceeded)
    assert out.waited_ms == pytest.approx(20.0)
    assert out.deadline_ms == 10.0
    assert fe.conservation()["balanced"]


def test_slack_rule_dispatches_partial_batch(engine, rows):
    """A lone request dispatches once its remaining slack drops to the
    service estimate + margin, well before max_batch fills."""
    fe = _frontend(engine, rows, max_batch=8, max_linger_ms=1e9,
                   dispatch_margin_ms=1.0, init_service_ms=5.0)
    f = fe.submit(rows[0], deadline_ms=50.0)
    assert fe.pump() == 0                       # plenty of slack: hold
    fe.clock.advance(0.045)                     # 5 ms slack left
    assert fe.pump() == 1
    assert isinstance(f.result(timeout=0), ServedResult)


def test_linger_rule_dispatches_idle_queue(engine, rows):
    fe = _frontend(engine, rows, max_batch=8, max_linger_ms=5.0,
                   init_service_ms=0.0, dispatch_margin_ms=0.0)
    f = fe.submit(rows[0], deadline_ms=1e4)
    assert fe.pump() == 0
    fe.clock.advance(0.006)                     # lingered past 5 ms
    assert fe.pump() == 1
    assert isinstance(f.result(timeout=0), ServedResult)


def test_shutdown_drains_then_sheds(engine, rows):
    fe = _frontend(engine, rows, max_batch=2, max_linger_ms=1e9)
    futs = [fe.submit(r) for r in rows[:6]]
    res = fe.shutdown(drain_deadline_ms=1e4)
    assert res == {"drained": 6, "shed": 0}
    assert all(isinstance(f.result(timeout=0), ServedResult)
               for f in futs)
    # intake is closed: a late submit sheds typed
    late = fe.submit(rows[0]).result(timeout=0)
    assert isinstance(late, Rejected) and late.reason == "shutting_down"
    assert fe.shutdown() == {"drained": 0, "shed": 0}   # idempotent
    assert fe.conservation()["balanced"]


def test_drain_deadline_sheds_remainder(engine, rows):
    fe = _frontend(engine, rows, max_batch=2, max_linger_ms=1e9)
    futs = [fe.submit(r) for r in rows[:6]]
    res = fe.shutdown(drain_deadline_ms=0.0)
    assert res["drained"] + res["shed"] == 6
    assert res["shed"] >= 1
    kinds = {type(f.result(timeout=0)) for f in futs}
    assert kinds <= {ServedResult, Rejected}
    assert fe.conservation()["balanced"]


def test_submit_rejects_multi_row_batch(engine, queries):
    fe = _frontend(engine, list(query_rows(queries[0])))
    with pytest.raises(ValueError, match="one query at a time"):
        fe.submit(queries[0])


# ---------------------------------------------------------------------------
# Per-request (mu, eta) through the engine
# ---------------------------------------------------------------------------


def test_uniform_mu_eta_matches_scalar_path(index, queries):
    """A mu_eta array whose rows equal (cfg.mu, cfg.eta) returns the
    same results as the scalar path — the degradation ladder at level 0
    is a no-op."""
    q, _ = queries
    cfg = SearchConfig(k=10, mu=0.9, eta=1.0, engine="batched")
    base = retrieve(index, q, cfg)
    me = np.full((q.n_queries, 2), (0.9, 1.0), dtype=np.float32)
    out = retrieve(index, q, cfg, mu_eta=me)
    np.testing.assert_allclose(np.asarray(base.scores),
                               np.asarray(out.scores),
                               rtol=1e-6, atol=1e-6)


def test_mixed_mu_eta_keeps_safe_rows_exact(index, queries):
    """One batch mixing degraded and rank-safe rows: the rank-safe rows
    return the same top-k score multiset as an all-safe batch — a
    degraded neighbor must never contaminate a full-fidelity request."""
    q, _ = queries
    cfg = SearchConfig(k=10, mu=1.0, eta=1.0, engine="batched")
    safe = retrieve(index, q, cfg)
    me = np.ones((q.n_queries, 2), dtype=np.float32)
    me[1::2] = (0.4, 0.5)                       # degrade odd rows
    mixed = retrieve(index, q, cfg, mu_eta=me)
    s_safe = np.sort(np.asarray(safe.scores), 1)
    s_mix = np.sort(np.asarray(mixed.scores), 1)
    np.testing.assert_allclose(s_mix[0::2], s_safe[0::2],
                               rtol=1e-5, atol=1e-5)


def test_dispatch_stamps_effective_level(engine, rows):
    """Effective fidelity is max(admission stamp, controller level at
    dispatch): a backlog admitted before the ladder stepped is served
    degraded, and a request stamped deep keeps its stamp even if the
    controller recovers first."""
    ladder = default_ladder(engine.cfg)
    fe = StreamingFrontend(
        engine, FrontendConfig(max_batch=2, max_queue=8,
                               default_deadline_ms=1e4,
                               max_linger_ms=1e9),
        ladder=ladder, clock=SimClock())
    fe.warmup(rows[0])
    # admitted at level 0, controller deepens before dispatch
    futs = [fe.submit(r) for r in rows[:2]]
    fe.controller.level = 2
    fe.pump()
    assert [f.result(timeout=0).level for f in futs] == [2, 2]
    assert futs[0].result(timeout=0).mu == pytest.approx(ladder[2].mu)
    # admitted at level 2, controller recovers before dispatch: the
    # admission stamp is a floor
    futs = [fe.submit(r) for r in rows[2:4]]
    fe.controller.level = 0
    fe.pump()
    assert [f.result(timeout=0).level for f in futs] == [2, 2]
    assert fe.conservation()["balanced"]


def test_ladder_step_validation():
    with pytest.raises(ValueError, match="mu <= eta"):
        LadderStep(0.8, 0.5)                    # eta < mu over-prunes
    with pytest.raises(ValueError, match="mu <= eta"):
        LadderStep(0.0, 0.5)
    with pytest.raises(ValueError, match="budget_frac"):
        LadderStep(0.5, 0.6, budget_frac=0.0)
    for step in default_ladder(SearchConfig(mu=0.9, eta=1.0)):
        assert 0.0 < step.mu <= step.eta <= 1.0


# ---------------------------------------------------------------------------
# Controller: hysteresis, predictive signal, health wiring
# ---------------------------------------------------------------------------


def _controller(**fcfg_kw):
    fcfg_kw.setdefault("slo_p99_ms", 50.0)
    fcfg_kw.setdefault("eval_every", 1)
    fcfg_kw.setdefault("cooldown_batches", 1)
    fcfg_kw.setdefault("step_up_patience", 3)
    fcfg_kw.setdefault("step_up_headroom", 0.7)
    fcfg = FrontendConfig(**fcfg_kw)
    stats = ServeStats(window=64)
    health = HealthStateMachine(stats.registry)
    ladder = default_ladder(SearchConfig(mu=0.9, eta=1.0))
    ctl = DegradationController(ladder, fcfg, stats, health,
                                stats.registry)
    return ctl, stats, health


def _feed(stats, latency_ms, n=32):
    for _ in range(n):
        stats.observe_request(latency_ms)


def test_controller_steps_down_on_breach_and_maps_health():
    ctl, stats, health = _controller()
    _feed(stats, 60.0)                          # p99 over the 50 ms SLO
    ctl.on_batch()
    assert ctl.level == 1 and ctl.level_max == 1
    assert health.cause_states["overload"] == "degraded"
    assert health.cause_states["writer_fault"] == "healthy"


def test_controller_severe_breach_jumps_two_rungs():
    ctl, stats, _ = _controller()
    _feed(stats, 90.0)                          # > 1.5x the SLO
    ctl.on_batch()
    assert ctl.level == 2


def test_controller_predictive_signal_reacts_before_latency():
    """A deep queue predicts the breach while the windowed p99 is still
    clean — the onset case a purely reactive controller loses."""
    ctl, stats, _ = _controller(max_batch=8)
    _feed(stats, 5.0)                           # measured latency fine
    ctl.on_batch(queue_depth=64, service_est_ms=10.0)   # 80 ms predicted
    assert ctl.level >= 1


def test_controller_hysteresis_up():
    ctl, stats, health = _controller()
    _feed(stats, 60.0)
    ctl.on_batch()
    assert ctl.level == 1
    stats.request_latencies_ms.clear()
    # inside the hysteresis band (> headroom*SLO, <= SLO): hold forever
    _feed(stats, 45.0)
    for _ in range(8):
        ctl.on_batch()
    assert ctl.level == 1
    # clean latencies: needs `patience` consecutive healthy evals
    stats.request_latencies_ms.clear()
    _feed(stats, 10.0)
    ctl.on_batch()
    ctl.on_batch()
    assert ctl.level == 1                       # patience not yet met
    assert health.cause_states["overload"] == "degraded"
    ctl.on_batch()
    assert ctl.level == 0                       # third healthy eval
    assert health.cause_states["overload"] == "healthy"


def test_controller_recovering_then_degraded_again():
    ctl, stats, health = _controller()
    _feed(stats, 200.0)
    ctl.on_batch()                              # severe: level 2
    stats.request_latencies_ms.clear()
    _feed(stats, 10.0)
    for _ in range(3):
        ctl.on_batch()
    assert ctl.level == 1
    assert health.cause_states["overload"] == "recovering"
    stats.request_latencies_ms.clear()
    _feed(stats, 80.0)                          # breach while recovering
    ctl.on_batch()
    assert ctl.level >= 2
    assert health.cause_states["overload"] == "degraded"


def test_controller_open_loop_never_moves():
    ctl, stats, health = _controller(closed_loop=False)
    _feed(stats, 500.0)
    for _ in range(8):
        ctl.on_batch(queue_depth=999, service_est_ms=100.0)
    assert ctl.level == 0 and ctl.level_max == 0
    assert health.healthy


def test_controller_transitions_visible_in_registry():
    ctl, stats, _ = _controller()
    _feed(stats, 60.0)
    ctl.on_batch()
    snap = ctl.registry.snapshot()
    trans = snap["frontend_degradation_transitions_total"]
    assert sum(v for k, v in trans.items() if "down" in k) == 1
    assert snap["frontend_degradation_level"] == 1


# ---------------------------------------------------------------------------
# Fault points
# ---------------------------------------------------------------------------


def test_fault_slow_executor_raise_sheds_batch(engine, rows):
    fe = _frontend(engine, rows)
    with install(FaultSchedule(
            [("frontend.dispatch.slow_executor", 1, "raise")])) as sched:
        futs = [fe.submit(r) for r in rows[:4]]
        fe.pump()
        assert sched.fired
    for f in futs:
        out = f.result(timeout=0)
        assert isinstance(out, Rejected)
        assert out.reason == "fault_injected"
    assert fe.conservation()["balanced"]


def test_fault_slow_executor_delay_still_serves(engine, rows):
    fe = _frontend(engine, rows)
    with install(FaultSchedule(
            [("frontend.dispatch.slow_executor", 1, "delay:5")])):
        futs = [fe.submit(r) for r in rows[:4]]
        fe.pump()
    for f in futs:
        out = f.result(timeout=0)
        assert isinstance(out, ServedResult)
        assert out.latency_ms >= 5.0            # the stall was charged
    assert fe.conservation()["balanced"]


def test_fault_queue_overflow_fires_after_typed_rejection(engine, rows):
    fe = _frontend(engine, rows, max_batch=8, max_queue=1,
                   max_linger_ms=1e9)
    f1 = fe.submit(rows[0])
    with install(FaultSchedule(
            [("frontend.queue.overflow", 1, "raise")])):
        with pytest.raises(FaultInjected):
            fe.submit(rows[1])
    # the overflowed request was already completed, typed, before the
    # fault unwound — never a hung future
    depth_probe = [f for f in (f1,) if not f.done()]
    assert depth_probe == [f1]
    fe.shutdown(drain_deadline_ms=1e4)
    assert fe.conservation()["balanced"]


def test_fault_clock_skew_expires_queue(engine, rows):
    fe = _frontend(engine, rows, max_batch=8, max_linger_ms=1e9,
                   dispatch_margin_ms=0.0, init_service_ms=0.0)
    f = fe.submit(rows[0], deadline_ms=20.0)
    with install(FaultSchedule(
            [("frontend.clock.skew", 1, "skew:40")])) as sched:
        fe.pump()                               # skewed 40 ms forward
        assert sched.fired
    out = f.result(timeout=0)
    assert isinstance(out, DeadlineExceeded)
    assert fe.conservation()["balanced"]


# ---------------------------------------------------------------------------
# The no-hang property: random arrival/fault schedules
# ---------------------------------------------------------------------------


_FAULT_POINTS = ("frontend.dispatch.slow_executor",
                 "frontend.queue.overflow", "frontend.clock.skew")
_FAULT_ACTIONS = ("raise", "delay:1", "skew:30")


@settings(max_examples=15, deadline=None)
@given(ops=st.lists(st.integers(0, 10_000), min_size=4, max_size=28),
       fault_pt=st.sampled_from(_FAULT_POINTS),
       fault_action=st.sampled_from(_FAULT_ACTIONS),
       fault_nth=st.integers(1, 5))
def test_no_hang_property(engine, rows, ops, fault_pt, fault_action,
                          fault_nth):
    """Every submitted request terminates with exactly one typed
    outcome — ServedResult | Rejected | DeadlineExceeded — under any
    interleaving of submits, clock advances, pumps, and injected
    faults, and the registry counters balance (served + shed +
    deadline_exceeded == submitted)."""
    base = StreamingFrontend(
        engine, FrontendConfig(max_batch=4, max_queue=6,
                               default_deadline_ms=30.0,
                               max_linger_ms=3.0),
        clock=SimClock())
    base.warmup(rows[0])
    submitted_before = base._m_submitted.value
    futs = []
    with install(FaultSchedule([(fault_pt, fault_nth, fault_action)])):
        for v in ops:
            op = v % 4
            arg = v // 4
            try:
                if op <= 1:                     # submit (2x weight)
                    dl = float(arg % 12) * 5.0 - 5.0   # -5..50 ms
                    futs.append(base.submit(rows[arg % len(rows)],
                                            deadline_ms=dl))
                elif op == 2:
                    base.clock.advance((arg % 20) * 1e-3)
                else:
                    base.pump()
            except FaultInjected:
                pass                            # overflow 'raise' action
        base.shutdown(drain_deadline_ms=1e4)
    for f in futs:
        assert f.done(), "a request future hung"
        assert isinstance(f.result(timeout=0),
                          (ServedResult, Rejected, DeadlineExceeded))
    cons = base.conservation()
    assert cons["balanced"], cons
    assert base._m_submitted.value - submitted_before == len(futs)


# ---------------------------------------------------------------------------
# Invariants of the frontend engine contract
# ---------------------------------------------------------------------------


def test_frontend_rejects_pipelined_engine(index):
    cfg = SearchConfig(k=10, engine="pipelined")
    eng = RetrievalEngine(index, cfg)
    with pytest.raises(ValueError, match="pipelined"):
        StreamingFrontend(eng)


def test_service_model_overrides_clock_charge(engine, rows):
    fe = StreamingFrontend(
        engine, FrontendConfig(max_batch=4, max_queue=8,
                               default_deadline_ms=1e4),
        clock=SimClock(), service_model=lambda levels, n: 7.0)
    fe.warmup(rows[0])
    futs = [fe.submit(r) for r in rows[:4]]
    fe.pump()
    assert fe.clock.now() == pytest.approx(7e-3)
    for f in futs:
        assert f.result(timeout=0).latency_ms == pytest.approx(7.0)


def test_serve_launcher_exits_nonzero_on_dispatch_failure(monkeypatch):
    """A front-end batch the engine failed to serve ends a
    ``launch/serve.py`` run with a non-zero exit, so a failing device
    cannot pass for a served run."""
    import signal
    import sys

    from repro import utils
    from repro.launch import serve
    from repro.serving.engine import RetrievalEngine

    def lost_device(self, *a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(RetrievalEngine, "search", lost_device)
    monkeypatch.setattr(utils, "init_compile_cache", lambda: "")
    monkeypatch.setattr(signal, "signal", lambda *a: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--n-docs", "300", "--vocab", "128", "--clusters", "4",
        "--segments", "2", "--batches", "1", "--batch-size", "4",
        "--frontend", "closed"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert "failed to dispatch" in str(exc.value.code)


# ---------------------------------------------------------------------------
# Layer spans on the profiler's clock
# ---------------------------------------------------------------------------


def test_layer_spans_reach_a_profiler_capture(engine, rows, tmp_path):
    """Without any Observability, one front-end batch still opens its
    layer spans as profiler annotations: a capture holds them on a host
    plane, nested as the code nests them."""
    import glob

    import jax
    from jax.profiler import ProfileData

    fe = _frontend(engine, rows)
    futs = [fe.submit(r) for r in rows[:4]]
    jax.profiler.start_trace(str(tmp_path))
    try:
        fe.pump()
    finally:
        jax.profiler.stop_trace()
    assert all(isinstance(f.result(), ServedResult) for f in futs)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    names = ("frontend.pump", "frontend.dispatch", "frontend.stack",
             "frontend.reply", "engine.search", "engine.prepare",
             "engine.launch", "engine.wait", "engine.account")
    for name in names:
        assert len(spans.get(name, ())) == 1, (name, sorted(spans))
    (lo, hi, args), = spans["engine.search"]
    assert args["batch"] == 4
    for child in ("engine.prepare", "engine.launch", "engine.wait",
                  "engine.account"):
        (a, b, _), = spans[child]
        assert lo <= a <= b <= hi, child
    (d0, d1, _), = spans["frontend.dispatch"]
    (s0, s1, sargs), = spans["frontend.stack"]
    assert sargs == {"batch": 4, "bucket": 4}
    assert d0 <= s0 <= s1 <= lo <= hi <= d1
    (r0, _, _), = spans["frontend.reply"]
    assert r0 >= d1
