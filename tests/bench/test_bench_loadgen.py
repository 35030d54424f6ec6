"""The load generator: its seeded schedule, and how it accounts for a
request that is submitted late, against a stand-in front-end."""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future

import numpy as np

import _bench_tiny  # noqa: F401
from bench import loadgen
from bench.run import req_record
from repro.serving.frontend import ServedResult


def test_schedule_is_seeded_and_keeps_its_count():
    a = loadgen.arrival_times(250.0, 10.0, np.random.default_rng(3))
    b = loadgen.arrival_times(250.0, 10.0, np.random.default_rng(3))
    c = loadgen.arrival_times(250.0, 10.0, np.random.default_rng(4))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.size == c.size == 2500
    assert (np.diff(a) >= 0).all() and a[0] >= 0 and a[-1] < 10.0
    # a Poisson process conditioned on its count: uniform instants
    ks = np.max(np.abs(np.arange(1, a.size + 1) / a.size - a / 10.0))
    assert ks < 1.63 / np.sqrt(a.size)          # KS test at 1%
    gaps = np.diff(a)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1   # exponential


class _Frontend:
    """Answers each query after ``service_s`` on a thread of its own;
    ``submit`` itself takes ``submit_s``."""

    def __init__(self, service_s=0.002, submit_s=0.0):
        self.service_s, self.submit_s = service_s, submit_s
        self.in_flight = self.max_in_flight = 0
        self.lock = threading.Lock()

    def start(self):
        pass

    def submit(self, row) -> Future:
        time.sleep(self.submit_s)
        fut = Future()
        with self.lock:
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)

        def serve():
            time.sleep(self.service_s)
            with self.lock:
                self.in_flight -= 1
            fut.set_result(ServedResult(
                doc_ids=np.zeros(1), scores=np.zeros(1), mu=1.0, eta=1.0,
                budget_frac=1.0, level=0, queue_ms=0.5, latency_ms=1.0,
                deadline_met=True))

        threading.Thread(target=serve, daemon=True).start()
        return fut


def test_open_loop_times_from_due_and_records_lateness():
    fe = _Frontend(service_s=0.001, submit_s=0.004)
    due = np.arange(50) * 0.001          # 1,000/s, but a submit takes 4 ms
    reqs, t0, t_end = loadgen.open_loop(fe, [None], np.zeros(50, int), due,
                                        0.3)
    loadgen.wait_replies(reqs, time.perf_counter() + 5)
    recs = [req_record(r, t0, t_end) for r in reqs]
    lag = np.array([r["lag_ms"] for r in recs])
    lat = np.array([r["latency_ms"] for r in recs])
    assert (lag >= 0).all() and lag[-1] > 100      # fell ~3 ms/request behind
    assert (lat >= lag).all()                      # timed from when due
    assert all(r["served"] for r in recs)
    assert all(r.due == t0 + d for r, d in zip(reqs, due))


def test_closed_loop_keeps_its_clients_busy_and_no_more():
    fe = _Frontend(service_s=0.003)
    reqs, t0, t_end = loadgen.closed_loop(fe, [None, None], np.arange(2), 8,
                                          0.3)
    loadgen.wait_replies(reqs, time.perf_counter() + 5)
    assert fe.max_in_flight <= 8
    assert len(reqs) > 8 * 20                      # ~0.3 s / 3 ms each
    assert all(r.submit < t_end for r in reqs)
    recs = [req_record(r, t0, t_end) for r in reqs]
    assert sum(r["in_window"] for r in recs) >= len(reqs) - 8
