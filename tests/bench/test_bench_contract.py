"""BENCHMARK.json against the benchmark's contract; files found by name,
new ones picked up with no edit; the command refuses the CPU."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from _bench_tiny import BENCH, REPO, make_tiny, tiny_cell
from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_shape(bench_json):
    b = bench_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert 1 <= len(b["command"]) <= 32 and all(map(_line, b["command"]))
    for word in b["command"]:
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in b["paths"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    cells = 2 + 14 * 24
    assert cells * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(b)) <= 64 * 1024


def test_configs_and_cells(bench_json):
    b = bench_json
    assert 1 <= len(b["configs"]) <= 24 and 1 <= len(b["workloads"]) <= 24
    used = {w["config"] for w in b["workloads"]}
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_metrics(bench_json):
    b = bench_json
    e2e, layer = b["end_to_end"], b["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(names) == len(set(names))
    cells = {w["name"] for w in b["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in e2e)
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in {x["name"] for x in e2e}
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        def has(m):
            return "workloads" not in m or cell in m["workloads"]
        got = {m["name"] for m in e2e if has(m)}
        assert "setup_s" in got and len(got) >= 2
        assert any(has(m) for m in layer)
        for m in layer:
            if has(m):        # what a layer metric moves is in the cell
                assert m["moves"] in got


def test_every_metric_has_a_reader_found_by_name(bench_json):
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.metric_reader("no_such_metric")


def test_every_cell_loads(bench_json):
    for w in bench_json["workloads"]:
        cell = spec.load_cell(w["name"], REPO)
        assert cell.config["name"] == w["config"]
        assert cell.chips == w["chips"]
        if cell.traffic["loop"] == "open":
            with open(os.path.join(BENCH, "capacity",
                                   f"{w['config']}.json")) as f:
                assert cell.traffic["capacity"] in json.load(f)


def test_new_files_are_picked_up_with_no_edit(tmp_path):
    """A configuration, a traffic mix and a metric added as files, with
    BENCHMARK.json entries, are found without touching any other file."""
    root = make_tiny(str(tmp_path))
    with open(os.path.join(root, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny2"
    cfg["index"]["m"] = 6
    with open(os.path.join(root, "configs", "tiny2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "traffic", "closed128.json")) as f:
        tr = json.load(f)
    tr["clients"] = 4
    with open(os.path.join(root, "traffic", "closed4.json"), "w") as f:
        json.dump(tr, f)
    with open(os.path.join(root, "metrics", "answered.py"), "w") as f:
        f.write("def read(rec):\n    return len(rec['requests'])\n")
    # readers of a new TopK counter and of a program span, by name
    with open(os.path.join(root, "metrics", "merged_rows.py"), "w") as f:
        f.write("def read(rec):\n    return sum(c['counters']['n_merged']"
                "['sum'] for c in rec['calls'])\n")
    with open(os.path.join(root, "metrics", "routes.py"), "w") as f:
        f.write("def read(rec):\n    return rec['scopes']['spans']"
                "['router.route'][0]\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny2", "source": "t", "reduced": [],
                         "file": "configs/tiny2.json", "why": "t"})
    b["workloads"].append({"name": "tiny2.closed4", "config": "tiny2",
                           "traffic": "closed4", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "answered", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator", "moves": "qps",
                           "workloads": ["tiny2.closed4"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    cell = spec.load_cell("tiny2.closed4", root, root)
    assert cell.config["index"]["m"] == 6 and cell.traffic["clients"] == 4
    assert "answered" in {m["name"] for m in cell.per_layer}
    assert spec.metric_reader("answered", root)({"requests": [1, 2]}) == 2
    calls = [{"counters": {"n_merged": {"sum": n, "max": n, "first": n}}}
             for n in (3, 4)]
    assert spec.metric_reader("merged_rows", root)({"calls": calls}) == 7
    scopes = {"spans": {"router.route": [5, 0.25]}}
    assert spec.metric_reader("routes", root)({"scopes": scopes}) == 5
    assert tiny_cell(root).traffic["clients"] == 16      # others unchanged


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "msmarco-splade-shard8.closed128", "--seed", str(2**31 + 9),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    out = _run(REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path, bench_json):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in bench_json["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "repro package" in out.stderr
