"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of
its own (``configs/<config>.json``, ``traffic/<traffic>.json``), and
each metric is read by ``metrics/<name>.py``. Adding a cell, a
configuration, a mix or a metric adds files and entries, and edits no
file that is there.
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
