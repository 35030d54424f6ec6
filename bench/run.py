"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From ``--seed`` it generates the cell's corpus and query pool on the
device, clusters and builds the index with the launcher's calls, warms
the batch shapes the cell's traffic uses, and then drives the served
path (``StreamingFrontend`` -> ``RetrievalEngine.search`` ->
``retrieve``) with the cell's traffic for ``--seconds``. After the
window it frees the program's state and compares a seeded sample of the
window's answers with the benchmark's own exhaustive reference.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
the window under the profiler and prints its per-layer metrics, the
device's busy time and a breakdown. The last stderr lines and the
result's last key give each compared number beside its limit.

It exits non-zero without a result line when the repro package is
absent, when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

#: set-up is timed from here: the process's own start-up work
T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
#: how long after the window's close a request's answer is awaited
ANSWER_WAIT_S = 60.0


#: JAX's persistent compile cache: a fixed path inside the checkout, so
#: that only the first run of a cell in a checkout compiles
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")


def use_checkout_cache() -> None:
    """Point JAX's persistent compile cache, and the program's, at
    :data:`COMPILE_CACHE`, whatever the environment set, and cache every
    program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {COMPILE_CACHE}")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int) -> dict | None:
    """The device JAX reports, or None (with the reason logged) when it
    is not a TPU or has fewer than ``chips`` devices."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"JAX found no usable backend: {e}")
        return None
    d = devices[0]
    if d.platform != "tpu":
        log(f"JAX found platform {d.platform!r} ({d.device_kind}), not a "
            f"TPU; the benchmark has no CPU branch")
        return None
    if len(devices) < chips:
        log(f"the cell asks for {chips} chips, JAX found {len(devices)}")
        return None
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def build_index(config: dict, tids, tw, mask, since=lambda: ""):
    """The launcher's clustering and build calls (as ``chip_smoke.py``
    makes them), with the dense projection taken in row chunks so that
    its (rows, t_pad, dim) gather stays small on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.clustering import (balanced_assign, dense_rep_projection,
                                       lloyd_kmeans)
    from repro.core.index import build_index as build
    from repro.core.types import SparseDocs

    ix, cl = config["index"], config["clustering"]
    vocab = config["corpus"]["vocab"]
    chunk = cl["chunk"]
    proj = jax.jit(lambda t, w, m: dense_rep_projection(
        SparseDocs(tids=t, tw=w, mask=m, vocab=vocab), dim=cl["dim"]))
    n = tids.shape[0]
    parts = []
    for s in range(0, n, chunk):
        sl = [np.zeros((chunk,) + a.shape[1:], a.dtype) for a in
              (tids, tw, mask)]
        for dst, a in zip(sl, (tids, tw, mask)):
            dst[:min(chunk, n - s)] = a[s:s + chunk]
        parts.append(proj(*sl)[:min(chunk, n - s)])
    rep = jnp.concatenate(parts)
    centers, _ = lloyd_kmeans(jax.random.PRNGKey(0), rep, k=ix["m"],
                              iters=cl["iters"])
    assign = np.asarray(balanced_assign(rep, centers, capacity=ix["d_pad"]))
    del rep, parts, centers
    log(f"clustered, {since()}")
    docs = SparseDocs(tids=tids, tw=tw, mask=mask, vocab=vocab)
    index = build(docs, assign, m=ix["m"], n_seg=ix["n_seg"],
                  d_pad=ix["d_pad"])
    return jax.block_until_ready(index)


def query_rows(q_tids, q_tw, q_mask, vocab: int) -> list:
    from repro.core.types import QueryBatch
    return [QueryBatch(tids=q_tids[i:i + 1], tw=q_tw[i:i + 1],
                       mask=q_mask[i:i + 1], vocab=vocab)
            for i in range(q_tids.shape[0])]


def batch_of(row, n: int, search) -> tuple:
    """A batch of ``n`` copies of ``row`` and its (mu, eta) rows, shaped
    as the front-end stacks one of its buckets."""
    import numpy as np

    from repro.core.types import QueryBatch
    qb = QueryBatch(tids=np.repeat(row.tids, n, 0),
                    tw=np.repeat(row.tw, n, 0),
                    mask=np.repeat(row.mask, n, 0), vocab=row.vocab)
    return qb, np.full((n, 2), (search.mu, search.eta), np.float32)


def warm(engine, row, sizes, search) -> None:
    """Compile (or load from the cache) each batch bucket the traffic
    forms, as ``StreamingFrontend.warmup`` does for all of them."""
    for n in sizes:
        engine.warmup(*batch_of(row, n, search))


def step_text_of(engine, batch) -> str | None:
    """The compiled text of the step that ``batch`` runs (compiled before
    the window, or loaded from the cache), which names each operation's
    phase; None for an engine that runs no single step program."""
    try:
        return engine.step_text(*batch)
    except ValueError as e:
        log(f"no step text, so no phase is read: {e}")
        return None


def wrap_search(engine, calls: list, annotate: bool) -> None:
    """Time every ``engine.search`` the front-end makes (host clock, and
    a ``bench.dispatch`` span when tracing); keep its batch and result
    so that the pruning counters are read after the window."""
    from bench.loadgen import span
    inner = engine.search

    def search(queries, mu_eta=None, budget_frac=None):
        t0 = time.perf_counter()
        with span(annotate, "bench.dispatch"):
            out = inner(queries, mu_eta=mu_eta, budget_frac=budget_frac)
        calls.append((t0, time.perf_counter(), queries, out))
        return out

    engine.search = search


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.active = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw) -> None:
        if self.active and event in self.EVENTS:
            self.n += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float | None = None):
    """One run of ``cell``. Returns (result line dict, record, checks,
    sample), where ``sample`` holds the compared queries and answers."""
    import jax
    import numpy as np

    from bench import gen, loadgen, peaks, reference, scope_reduce, \
        trace_reduce
    from bench.spec import metric_reader, program_span_prefixes
    from repro.core.search import SearchConfig
    from repro.serving.engine import RetrievalEngine
    from repro.serving.frontend import (FrontendConfig, ServedResult,
                                        StreamingFrontend)

    t_start = time.perf_counter() if t_start is None else t_start
    config, traffic = cell.config, cell.traffic
    st = gen.Stats(**config["corpus"])
    tab = gen.tables(st, seed)
    def since():
        return f"{time.perf_counter() - t_start:.1f} s since start"

    log(f"set-up begins, {since()}")
    tids, tw, mask, _ = gen.make_docs(st, seed, tab)
    q_tids, q_tw, q_mask, _ = gen.make_queries(
        st, traffic["query_pool"], traffic["topic_zipf"], seed, tab)
    log(f"generated {st.n_docs} docs, {traffic['query_pool']} queries, "
        f"{since()}")
    index = build_index(config, tids, tw, mask, since)
    log(f"index m={index.m} d_pad={index.d_pad} n_seg={index.n_seg}: "
        f"{index.nbytes() / 1e6:.1f} MB, {since()}")

    search = SearchConfig(**config["search"])
    engine = RetrievalEngine(index, search)
    calls: list = []
    wrap_search(engine, calls, annotate=trace)
    fcfg = traffic["frontend"]
    fe = StreamingFrontend(engine, FrontendConfig(
        max_batch=fcfg["max_batch"], max_queue=fcfg["max_queue"],
        default_deadline_ms=fcfg["deadline_ms"],
        slo_p99_ms=fcfg["deadline_ms"],
        drain_deadline_ms=ANSWER_WAIT_S * 1e3,
        max_linger_ms=fcfg["max_linger_ms"],
        closed_loop=fcfg["closed_loop"]))
    rows = query_rows(q_tids, q_tw, q_mask, st.vocab)
    warm(engine, rows[0], traffic["warm_batches"], search)
    calls.clear()
    log(f"warmed batches {traffic['warm_batches']}, {since()}")
    step_text = step_text_of(engine, batch_of(
        rows[0], max(traffic["warm_batches"]), search)) if trace else None

    rng = gen.host_rng(seed, 4)
    order = rng.permutation(len(rows))
    compiles = CompileCounter()
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
    compiles.active = True
    t_setup = time.perf_counter() - t_start
    with loadgen.span(trace, trace_reduce.WINDOW_SPAN):
        if traffic["loop"] == "closed":
            reqs, t0, t_end = loadgen.closed_loop(
                fe, rows, order, traffic["clients"], seconds, trace)
        else:
            rate = open_rate(traffic, config, cell.bench_dir)
            due = loadgen.arrival_times(rate, seconds, rng)
            reqs, t0, t_end = loadgen.open_loop(fe, rows, order, due,
                                                seconds, trace)
    compiles.active = False
    compiles.close()
    # the capture ends once the batch in flight at the window's close has
    # replied, so that its program spans are closed; every reading is
    # clipped to the window span all the same
    loadgen.wait_replies(reqs, t_end + ANSWER_WAIT_S)
    if trace:
        jax.profiler.stop_trace()
    fe.shutdown(drain_deadline_ms=1.0)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    geometry = {"m": index.m, "d_pad": index.d_pad, "t_pad": index.t_pad,
                "n_seg": index.n_seg, "vocab": index.vocab, "k": search.k}
    all_calls = [call_counters(c) for c in calls]
    served_by_call(all_calls, reqs)
    window_calls = [c for c in all_calls if t0 <= c["start"] < t_end]
    del fe, engine, index, calls
    gc.collect()

    # -- correctness: a seeded sample of the window's requests ---------------
    n_cmp = min(config["compare"]["requests"], len(reqs))
    pick = np.sort(gen.host_rng(seed, 5).choice(len(reqs), n_cmp,
                                                replace=False))
    sample = [reqs[i] for i in pick]
    served = [r for r in sample if isinstance(r.outcome, ServedResult)]
    unanswered = sum(r.reply is None for r in sample)
    qi = np.asarray([r.query for r in served], np.int64)
    ids = np.stack([r.outcome.doc_ids for r in served]) if served else \
        np.zeros((0, search.k), np.int32)
    scores = np.stack([r.outcome.scores for r in served]) if served else \
        np.zeros((0, search.k), np.float32)
    log(f"window closed, answers in; reference begins, {since()}")
    ref = reference.Reference(tids, tw, mask, st.vocab)
    uq, inv = np.unique(qi, return_inverse=True)
    ref_ids, ref_scores = ref.topk(q_tids[uq], q_tw[uq], q_mask[uq],
                                   search.k)
    ref_ids, ref_scores = ref_ids[inv], ref_scores[inv]
    pair = ref.pair_scores(q_tids[qi], q_tw[qi], q_mask[qi], ids)
    numbers = reference.compare(ids, scores, ref_ids, ref_scores, pair) \
        if served else {"score_err": float("inf"), "prop3_ratio": 0.0,
                        "malformed": 0, "recall": 0.0, "recall_at_k": 0.0}
    numbers["unanswered"] = unanswered
    numbers["compared"] = len(served)
    checks = checks_of(numbers, config["limits"])
    correct = all(c["ok"] for c in checks.values())

    # -- metrics -------------------------------------------------------------
    rec = {
        "setup_s": t_setup,
        "t0": t0,
        "t_end": t_end,
        "window_s": t_end - t0,
        "requests": [req_record(r, t0, t_end) for r in reqs],
        "calls": window_calls,
        "geometry": geometry,
        "recall": numbers["recall"],
        "recall_at_k": numbers["recall_at_k"],
        "compiles_in_window": compiles.n,
        "peak": peaks.peak(dev.device_kind) if dev.platform == "tpu"
        else None,
        "trace": None,
        "scopes": None,
    }
    if trace:
        captured = trace_reduce.load(tmp)
        rec["trace"] = trace_reduce.reduce(captured)
        rec["scopes"] = scope_reduce.reduce(
            captured, step_text, program_span_prefixes(cell.bench_dir))
        shutil.rmtree(tmp, ignore_errors=True)
        log(f"trace read, {since()}")
    log(f"reference done, {since()}")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = metric_reader(m["name"], cell.bench_dir)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    failed = sum(not isinstance(r.outcome, ServedResult) for r in reqs)
    result = {
        "correct": correct,
        "attempted": len(reqs),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": memory_peak},
    }
    if trace:
        t = rec["trace"]
        result["device"]["busy_s"] = t["busy_s"]
        result["device"]["window_s"] = t["window_s"]
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
        result["idle_gaps_by_program_span"] = [
            [name, s] for name, s in
            rec["scopes"]["idle_by_program_span"].items()]
    result["compiles_in_window"] = compiles.n
    result["checks"] = {k: {"value": c["value"], c["bound"]: c["limit"]}
                        for k, c in checks.items()}
    log(f"window: {len(reqs)} requests, {failed} failed, "
        f"{len(window_calls)} batches, {compiles.n} compilations inside")
    sample_out = {"queries": (q_tids[qi], q_tw[qi], q_mask[qi]),
                  "ids": ids, "scores": scores, "reference": ref,
                  "ref": (ref_ids, ref_scores)}
    return result, rec, checks, sample_out


def open_rate(traffic: dict, config: dict, bench_dir: str) -> float:
    """Arrival rate of an open-loop mix: ``load`` times the capacity that
    ``capacity/<config>.json`` records for the mix it names."""
    path = os.path.join(bench_dir, "capacity", f"{config['name']}.json")
    with open(path) as f:
        cap = json.load(f)
    return float(traffic["load"]) * float(cap[traffic["capacity"]])


def call_counters(call) -> dict:
    """Host-side numbers of one ``engine.search`` call of the window.
    ``counters`` holds every integer ``n_*`` field of the returned
    ``TopK`` (its dataclass fields, so a counter the program adds is
    here unnamed by the harness) as its sum, max and first value over
    the batch's rows: per-query counters are summed, batch-level ones,
    replicated per query, are read by their first value."""
    import numpy as np
    t0, t1, queries, out = call
    tids = np.asarray(queries.tids)[np.asarray(queries.mask)]
    fields = {f.name: np.asarray(getattr(out, f.name))
              for f in dataclasses.fields(out) if f.name.startswith("n_")}
    counters = {name: {"sum": int(a.sum()), "max": int(a.max()),
                       "first": int(a.reshape(-1)[0])}
                for name, a in fields.items()
                if np.issubdtype(a.dtype, np.integer)}
    return {
        "start": t0,
        "end": t1,
        "wall_s": t1 - t0,
        "rows": int(queries.n_queries),
        "docs": counters["n_scored_docs"]["sum"],
        "docs_max": counters["n_scored_docs"]["max"],
        "clusters": counters["n_scored_clusters"]["sum"],
        "bounded": counters["n_bounded_clusters"]["first"],
        "distinct_terms": int(np.unique(tids).size),
        "waves": counters.get("n_waves", {}).get("first"),
        "counters": counters,
    }


def served_by_call(calls: list, reqs: list) -> None:
    """Set each call's ``served``: the requests it answered, found by
    their reply times (a reply follows the end of its batch's call)."""
    import bisect
    ends = [c["end"] for c in calls]
    for c in calls:
        c["served"] = 0
    for r in reqs:
        if r.reply is not None:
            i = bisect.bisect_right(ends, r.reply) - 1
            if i >= 0:
                calls[i]["served"] += 1


def req_record(r, t0: float, t_end: float) -> dict:
    from repro.serving.frontend import ServedResult
    ok = isinstance(r.outcome, ServedResult)
    start = r.due if r.due is not None else r.submit
    return {
        "served": ok,
        "in_window": ok and r.reply is not None and t0 <= r.reply <= t_end,
        "latency_ms": (r.reply - start) * 1e3 if ok else float("inf"),
        "lag_ms": ((r.submit - r.due) * 1e3 if r.due is not None
                   and r.submit is not None else None),
    }


def checks_of(numbers: dict, limits: dict) -> dict:
    out = {}
    for name, lim in limits.items():
        (bound, limit), = lim.items()
        v = numbers[name]
        ok = v <= limit if bound == "max" else v >= limit
        out[name] = {"value": v, "bound": bound, "limit": limit, "ok": ok}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"the repro package is not at {SRC}")
        return 2
    sys.path[:0] = [ROOT, SRC]
    from bench.spec import SpecError, load_cell
    try:
        cell = load_cell(args.workload, ROOT)
    except SpecError as e:
        log(str(e))
        return 2
    use_checkout_cache()
    if device_info(cell.chips) is None:
        return 3
    result, _, checks, _ = run_cell(cell, args.seed, args.seconds,
                                    bool(args.trace), T_PROCESS)
    for name, c in checks.items():
        rel = "<=" if c["bound"] == "max" else ">="
        log(f"check {name}: {c['value']!r} {rel} {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
