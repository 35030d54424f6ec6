"""A tiny copy of the benchmark's cells for CPU tests: the real
configuration and traffic files shrunk to a toy size, laid out as the
harness finds them (``BENCHMARK.json`` plus ``configs/``, ``traffic/``,
``capacity/``, ``metrics/`` and ``program_spans/`` under one
directory)."""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CORPUS = dict(n_docs=3000, vocab=512, n_topics=8, doc_terms=16,
                   t_pad=32, query_terms=6, q_pad=12)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def make_tiny(root: str) -> str:
    """Write the tiny cells ``tiny.closed128`` and ``tiny.poisson80``
    under ``root``; returns ``root``."""
    for d in ("configs", "traffic", "capacity"):
        os.makedirs(os.path.join(root, d), exist_ok=True)
    for d in ("metrics", "program_spans"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(root, d),
                        dirs_exist_ok=True)
    c = _load("configs", "msmarco-splade-shard8.json")
    c["name"] = "tiny"
    c["corpus"].update(TINY_CORPUS)
    c["index"].update(m=12, n_seg=4, d_pad=384)
    c["clustering"]["chunk"] = 1024
    c["compare"]["requests"] = 64
    _dump(c, root, "configs", "tiny.json")
    t = _load("traffic", "closed128.json")
    t["frontend"]["max_batch"] = 8
    t["query_pool"] = 256
    _dump(dict(t, clients=16, warm_batches=[8]), root, "traffic",
          "closed128.json")
    # an open-loop mix, as the load generator and run.open_rate read one
    _dump(dict(t, loop="open", load=0.8, capacity="closed128_qps",
               warm_batches=[1, 2, 4, 8]), root, "traffic", "poisson80.json")
    _dump({"closed128_qps": 100.0}, root, "capacity", "tiny.json")
    b = _load("..", "BENCHMARK.json")
    b["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "configs/tiny.json", "why": "test"}]
    b["workloads"] = [
        {"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1,
         "why": "test"} for t in ("closed128", "poisson80")]
    b["end_to_end"] = [_metric(n, u, w, 0.25) for n, u, w in END_TO_END]
    b["per_layer"] = [dict(_metric(n, u, w, None), layer=layer, moves=moves)
                      for n, u, w, layer, moves in PER_LAYER]
    _dump(b, root, "BENCHMARK.json")
    return root


CLOSED = ["tiny.closed128"]
END_TO_END = [("qps", "queries/s", CLOSED), ("recall_at_10", "ratio", None),
              ("setup_s", "s", None)]
PER_LAYER = [(n, u, CLOSED, layer, "qps") for n, u, layer in (
    ("engine_host_ms_per_batch", "ms", "engine dispatch"),
    ("step_device_ms", "ms", "served step"),
    ("step_roofline", "%", "served step"),
    ("docs_admitted_per_query", "docs", "pruning"),
    ("clusters_scored_share", "ratio", "pruning"),
    ("device_idle_share", "ratio", "device"),
    ("step_bounds_ms", "ms", "served step: bounds"),
    ("step_plan_ms", "ms", "planner"),
    ("step_execute_ms", "ms", "executor"),
    ("step_merge_ms", "ms", "top-k merge"),
    ("waves_per_batch", "waves", "pruning: wave walk"),
    ("engine_self_ms_per_batch", "ms", "engine dispatch"),
    ("frontend_idle_ms_per_batch", "ms", "front-end"))]


def _metric(name, unit, workloads, bound):
    m = {"name": name, "unit": unit, "better": "lower",
         "source": "host_clock"}
    if bound is not None:
        m["bound"] = bound
    if workloads is not None:
        m["workloads"] = workloads
    return m


def _dump(obj, root, *parts):
    with open(os.path.join(root, *parts), "w") as f:
        json.dump(obj, f, indent=1)


def tiny_cell(root: str, name: str = "tiny.closed128"):
    from bench.spec import load_cell
    return load_cell(name, root, root)
