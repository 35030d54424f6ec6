"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of
its own (``configs/<config>.json``, ``traffic/<traffic>.json``), and
each metric is read by ``metrics/<name>.py``. The program's span
prefixes are the names of ``program_spans/<prefix>.txt``. Adding a
cell, a configuration, a mix, a metric or a span prefix adds files and
entries, and edits no file that is there.

A reader's ``read(rec)`` returns a number, or None when its record
holds nothing to read (the harness then leaves the metric out). The
record ``rec`` that ``run.run_cell`` builds holds:

- ``setup_s``, ``t0``, ``t_end``, ``window_s``: set-up seconds, the
  window's start and end (``time.perf_counter``) and its length.
- ``requests``: per request of the run, ``served``, ``in_window``,
  ``latency_ms``, ``lag_ms`` (open loop: how late it was submitted).
- ``calls``: per ``engine.search`` call that started in the window,
  ``start``, ``end``, ``wall_s``, ``rows``, ``served`` (requests it
  answered), ``distinct_terms``; the pruning counters ``docs``,
  ``docs_max``, ``clusters``, ``bounded``, ``waves`` (None where the
  ``TopK`` has no ``n_waves``); and ``counters``: every integer
  ``n_*`` field of the returned ``TopK`` as ``{"sum", "max",
  "first"}`` over the batch's rows (sum or max for a per-query
  counter, first for a batch-level one replicated per query, as
  ``TopK``'s docstring says of each). A counter added to ``TopK`` is
  there with no edit.
- ``geometry``: the index's ``m``, ``d_pad``, ``t_pad``, ``n_seg``,
  ``vocab`` and the search's ``k``.
- ``recall``: share of the exact top-min(10, k) found among the first
  min(10, k) answers; ``recall_at_k``: share of the exact top-k
  returned. Both are means over the compared requests.
- ``compiles_in_window``; ``peak``: the chip's peaks
  (``peaks.peak``), None off a TPU.
- ``trace`` (``--trace 1``, else None): ``trace_reduce.reduce`` of the
  capture: ``window_s``, ``busy_s``, ``devices``, ``modules`` ([host
  span, program, seconds, launches]), ``device_ops``, ``idle_gaps``.
- ``scopes`` (``--trace 1``, else None): ``scope_reduce.reduce`` of
  the capture and the step's compiled text: ``phases``
  (``launches``, ``device_s`` by ``asc.*`` scope, ``unscoped_s``),
  ``engine_self_ms`` (per batch), ``idle_by_program_span`` (seconds
  by innermost program span), ``spans`` (``{name: [count, host
  seconds]}`` of the program spans that start in the window) and
  ``has_program_spans``. A new ``asc.*`` scope is a new key of
  ``device_s``, and a span under a listed prefix a new key of
  ``spans``, with no edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple       # metric entries of BENCHMARK.json
    per_layer: tuple
    bench_dir: str = BENCH_DIR


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration and traffic files read from ``bench_dir``."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}, "
                        f"which BENCHMARK.json does not list")
    config = _json(os.path.join(bench_dir, "configs", f"{w['config']}.json"))
    traffic = _json(os.path.join(bench_dir, "traffic",
                                 f"{w['traffic']}.json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in spec.get("end_to_end", [])
                         if _applies(m, name)),
        per_layer=tuple(m for m in spec.get("per_layer", [])
                        if _applies(m, name)),
        bench_dir=bench_dir)


def program_span_prefixes(bench_dir: str = BENCH_DIR) -> tuple:
    """``<p>.`` for each ``program_spans/<p>.txt`` under ``bench_dir``:
    the prefixes of the program's own host spans, each file saying in a
    line which part of the program opens them."""
    names = os.listdir(os.path.join(bench_dir, "program_spans"))
    return tuple(sorted(f"{n[:-len('.txt')]}." for n in names
                        if n.endswith(".txt")))


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read(record) -> float | None`` function of
    ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"metric {name!r} has no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
