"""Load generator: seeded schedules and the closed- and open-loop
drivers that feed a ``StreamingFrontend`` one query at a time.

Closed loop: each of ``clients`` clients submits its next query when
its reply arrives (a stage that feeds a reranker at fixed concurrency).
Open loop: queries are submitted on a seeded Poisson schedule whether
or not earlier ones have finished; each is timed from when it was due,
and how late the generator submitted it is recorded.

The schedule keeps the count of arrivals fixed (rate x seconds) and
draws their times as a Poisson process conditioned on that
count: normalised exponential gaps, as ``benchmarks/serve_slo.py``
draws them. So every seed sends the same amount of work, in another
order and at other instants.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from contextlib import nullcontext

import numpy as np


@dataclasses.dataclass
class Request:
    query: int                     # index into the query pool
    due: float | None = None       # perf_counter time it was due (open)
    submit: float | None = None
    reply: float | None = None
    outcome: object = None         # ServedResult | Rejected | ...


def arrival_times(rate_qps: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted due offsets (s) in [0, seconds): round(rate x seconds)
    arrivals at the instants of a Poisson process conditioned on that
    count."""
    n = int(round(rate_qps * seconds))
    gaps = rng.exponential(1.0, n + 1)
    return seconds * np.cumsum(gaps)[:n] / gaps.sum()


def span(annotate: bool, name: str):
    """A profiler ``TraceAnnotation`` named ``name`` when ``annotate``."""
    if not annotate:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def closed_loop(fe, rows, order: np.ndarray, clients: int,
                seconds: float, annotate: bool = False):
    """Drive ``fe`` with ``clients`` closed-loop clients for ``seconds``.
    ``rows[i]`` is the 1-row QueryBatch of pool query i; ``order`` is the
    seeded sequence of pool indices, used cyclically. Every client's
    first query is queued before the dispatcher starts, so the first
    batches are full. Returns (requests, t0, t_end)."""
    lock = threading.Lock()
    reqs: list[Request] = []
    state = {"next": 0, "t_end": float("inf")}

    def submit(now: float) -> None:
        # timed from ``now``, the instant the client decided to submit:
        # read before the window's close is checked, so that no request
        # counts as submitted after it
        with lock:
            r = Request(query=int(order[state["next"] % len(order)]))
            state["next"] += 1
            reqs.append(r)
        with span(annotate, "bench.submit"):
            r.submit = now
            fut = fe.submit(rows[r.query])
        fut.add_done_callback(lambda f, r=r: on_reply(f, r))

    def on_reply(f, r: Request) -> None:
        t = time.perf_counter()
        with span(annotate, "bench.reply"):
            r.outcome = f.result()
        r.reply = t
        now = time.perf_counter()
        if now < state["t_end"]:
            submit(now)

    t0 = time.perf_counter()
    state["t_end"] = t0 + seconds
    for _ in range(clients):
        submit(time.perf_counter())
    fe.start()
    _sleep_until(state["t_end"])
    return reqs, t0, state["t_end"]


def open_loop(fe, rows, order: np.ndarray, due_offsets: np.ndarray,
              seconds: float, annotate: bool = False):
    """Submit pool query ``order[i]`` at ``t0 + due_offsets[i]`` from this
    thread, whatever is in flight. Returns (requests, t0, t_end)."""
    reqs = [Request(query=int(order[i % len(order)]))
            for i in range(len(due_offsets))]

    def on_reply(f, r: Request) -> None:
        t = time.perf_counter()
        with span(annotate, "bench.reply"):
            r.outcome = f.result()
        r.reply = t

    fe.start()
    t0 = time.perf_counter()
    for r, d in zip(reqs, due_offsets):
        r.due = t0 + float(d)
        _sleep_until(r.due)
        with span(annotate, "bench.submit"):
            r.submit = time.perf_counter()
            fut = fe.submit(rows[r.query])
        fut.add_done_callback(lambda f, r=r: on_reply(f, r))
    t_end = t0 + seconds
    _sleep_until(t_end)
    return reqs, t0, t_end


def wait_replies(reqs, until: float, poll_s: float = 0.01) -> None:
    """Wait until every request has its reply, or ``until``."""
    while time.perf_counter() < until:
        if all(r.reply is not None for r in list(reqs)):
            return
        time.sleep(poll_s)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))
