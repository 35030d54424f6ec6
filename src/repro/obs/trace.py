"""Layer-boundary spans: on the profiler's clock always, and per request
as Chrome-trace JSON (Perfetto) when the request is sampled.

Every span the serving stack opens goes through :func:`host_span`, a
``jax.profiler.TraceAnnotation``: while a profiler capture runs it lands
in the capture beside the device's operations, on the device trace's
clock; with no capture running it costs about a microsecond.

One :class:`TraceRecorder` serves a whole process; each sampled request
opens a :class:`RequestTrace` whose spans (``engine.search`` with its
``engine.prepare`` / ``engine.launch`` / ``engine.wait`` /
``engine.account`` children; the front-end's ``frontend.dispatch`` /
``frontend.stack`` / ``frontend.reply``) also go to a per-request file,
in the Chrome trace event format — ``{"traceEvents": [...]}`` with
complete (``"ph": "X"``) events, microsecond timestamps — which loads
directly in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``
(docs/observability.md §traces has the how-to). Every span's duration
is measured: the served step's phases, which run inside one device
program, are read from a profiler capture by their ``asc.*`` named
scopes, not from these files.

A disabled recorder, or a request that is not sampled, hands out the
shared :data:`NULL_REQUEST`, whose ``span`` opens the profiler span
alone. The serving engine holds whatever the recorder gives it and never
branches on enabledness itself.

The optional ``profile_first_n`` hook additionally wraps the first N
requests in a ``jax.profiler`` device capture (TensorBoard-loadable),
for the occasions when host-side spans are not enough and the XLA-level
timeline is needed. Failures to start the profiler (missing backend
support) are recorded and swallowed — profiling must never take down
serving.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation


class _HostSpan(TraceAnnotation):
    """A profiler span with the per-request span's surface, whose
    request-side methods do nothing."""

    def set_args(self, **kw) -> None:
        pass

    def child(self, name: str, **args) -> "_HostSpan":
        return host_span(name, **args)


def host_span(name: str, **args) -> TraceAnnotation:
    """A ``jax.profiler.TraceAnnotation`` named ``name`` with ``args``:
    enter it around the work it names. It reaches a running profiler
    capture on the device trace's clock, and costs about a microsecond
    when none runs."""
    return _HostSpan(name, **args)


class _NullRequest:
    """Request trace of a request that is not sampled: profiler spans
    only."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, **args) -> TraceAnnotation:
        return host_span(name, **args)

    def instant(self, name: str, **args) -> None:
        pass

    def set_args(self, **kw) -> None:
        pass

    def finish(self) -> str | None:
        return None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_REQUEST = _NullRequest()


class Span:
    """One complete ("X") trace event; use as a context manager, which
    also opens its :func:`host_span`, or close via the owning request.
    Children created while open nest visually in Perfetto because they
    share the track and sit inside [ts, ts+dur]."""

    __slots__ = ("name", "args", "ts_us", "dur_us", "_trace", "_host")

    def __init__(self, trace: "RequestTrace", name: str, args: dict):
        self._trace = trace
        self.name = name
        self.args = args
        self.ts_us = trace._now_us()
        self.dur_us = None
        self._host = None

    def set_args(self, **kw) -> None:
        self.args.update(kw)

    def child(self, name: str, **args) -> "Span":
        return Span(self._trace, name, args)

    def close(self) -> None:
        if self.dur_us is None:
            self.dur_us = max(self._trace._now_us() - self.ts_us, 0)
            self._trace._emit(self)

    def __enter__(self):
        self._host = host_span(self.name, **self.args)
        self._host.__enter__()
        return self

    def __exit__(self, *exc):
        self.close()
        self._host.__exit__(*exc)
        return False


class RequestTrace:
    """Span sink for one request; one Perfetto track per request id."""

    enabled = True

    def __init__(self, recorder: "TraceRecorder", request_id: int):
        self.recorder = recorder
        self.request_id = request_id
        self.events: list[dict] = []
        self._t0 = time.perf_counter()
        self._wall0_us = int(time.time() * 1e6)
        self.path: str | None = None
        self._request_args: dict = {}
        self._req_span: Span | None = None

    def _now_us(self) -> int:
        return self._wall0_us + int(
            (time.perf_counter() - self._t0) * 1e6)

    def _emit(self, span: Span) -> None:
        self.events.append({
            "name": span.name, "ph": "X", "cat": "serve",
            "ts": span.ts_us, "dur": span.dur_us,
            "pid": os.getpid(), "tid": self.request_id,
            "args": span.args,
        })

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        self.events.append({
            "name": name, "ph": "i", "cat": "serve", "s": "t",
            "ts": self._now_us(), "pid": os.getpid(),
            "tid": self.request_id, "args": args,
        })

    def set_args(self, **kw) -> None:
        """Request-level metadata, attached to the enclosing request
        span at finish time."""
        self._request_args.update(kw)

    def finish(self) -> str | None:
        """Write this request's events to the recorder's directory as
        ``trace_<request_id>.json``; returns the path (None when the
        recorder has no directory)."""
        return self.recorder._finish(self)

    def __enter__(self):
        self._req_span = self.span("request",
                                   request_id=self.request_id)
        return self

    def __exit__(self, *exc):
        self._req_span.set_args(**self._request_args)
        self._req_span.close()
        self.finish()
        return False


class TraceRecorder:
    """Per-request Chrome-trace recording + optional jax.profiler hook.

    ``trace_dir`` — directory for per-request ``trace_<id>.json`` files
    (created on first write). ``sample_every`` — trace every Nth request
    (1 = all); non-sampled requests get :data:`NULL_REQUEST`, which opens
    profiler spans only. ``profile_first_n`` — wrap the first N requests in a
    ``jax.profiler.trace`` capture under ``trace_dir/jax_profile``.
    """

    def __init__(self, trace_dir: str | None,
                 sample_every: int = 1,
                 profile_first_n: int = 0):
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, "
                             f"got {sample_every}")
        self.trace_dir = trace_dir
        self.sample_every = sample_every
        self.profile_first_n = profile_first_n
        self.enabled = trace_dir is not None
        self.n_requests = 0
        self.n_traced = 0
        self.n_profile_failures = 0
        self._lock = threading.Lock()

    def request(self) -> RequestTrace | _NullRequest:
        """A trace sink for the next request (the null sink when this
        one is not sampled)."""
        if not self.enabled:
            return NULL_REQUEST
        with self._lock:
            rid = self.n_requests
            self.n_requests += 1
            if rid % self.sample_every != 0:
                return NULL_REQUEST
            self.n_traced += 1
        return RequestTrace(self, rid)

    @contextlib.contextmanager
    def maybe_profile(self, request_id: int):
        """jax.profiler capture for the first ``profile_first_n``
        requests; a failed start is counted, never raised."""
        if (not self.enabled or self.profile_first_n <= 0
                or request_id >= self.profile_first_n):
            yield False
            return
        pdir = os.path.join(self.trace_dir, "jax_profile")
        started = False
        try:
            import jax
            os.makedirs(pdir, exist_ok=True)
            jax.profiler.start_trace(pdir)
            started = True
        except Exception:
            self.n_profile_failures += 1
        try:
            yield started
        finally:
            if started:
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception:
                    self.n_profile_failures += 1

    def _finish(self, trace: RequestTrace) -> str | None:
        if self.trace_dir is None:
            return None
        os.makedirs(self.trace_dir, exist_ok=True)
        path = os.path.join(self.trace_dir,
                            f"trace_{trace.request_id:06d}.json")
        doc = {
            "traceEvents": trace.events,
            "displayTimeUnit": "ms",
            "otherData": {"request_id": trace.request_id,
                          "source": "repro.obs.trace"},
        }
        with open(path, "w") as f:
            json.dump(doc, f)
        trace.path = path
        return path


def validate_chrome_trace(path: str) -> dict:
    """Schema check for an exported trace file: loads the JSON and
    asserts the Chrome trace event invariants Perfetto relies on.
    Returns the parsed doc (the CI smoke job and tests call this)."""
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events, "no traceEvents"
    for ev in events:
        assert isinstance(ev.get("name"), str) and ev["name"]
        assert ev.get("ph") in ("X", "i", "B", "E"), ev
        assert isinstance(ev.get("ts"), int) and ev["ts"] >= 0, ev
        assert isinstance(ev.get("pid"), int), ev
        assert isinstance(ev.get("tid"), int), ev
        if ev["ph"] == "X":
            assert isinstance(ev.get("dur"), int) and ev["dur"] >= 0, ev
    # every traced request has exactly one enclosing request span that
    # contains all its other complete events
    reqs = [ev for ev in events if ev["name"] == "request"]
    assert len(reqs) == 1, f"expected 1 request span, got {len(reqs)}"
    lo = reqs[0]["ts"]
    hi = lo + reqs[0]["dur"]
    for ev in events:
        if ev["ph"] == "X" and ev is not reqs[0]:
            assert ev["ts"] >= lo and ev["ts"] + ev["dur"] <= hi + 1, (
                f"span {ev['name']} escapes the request span")
    return doc
