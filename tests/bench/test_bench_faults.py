"""A whole run on the CPU at a toy size, with the look for a chip
skipped: sound, it comes out correct; with the timed path broken
underneath, or with the bfloat16 control in the program's place, it
does not."""

from __future__ import annotations

import json

import numpy as np
import pytest

from _bench_tiny import make_tiny, tiny_cell
from bench import control, run
from repro.serving.engine import RetrievalEngine

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_tiny(str(tmp_path_factory.mktemp("tiny")))


def _run(root, seed=SEED):
    return run.run_cell(tiny_cell(root), seed, 1.0, False)


def _broken(monkeypatch, alter):
    """Patch the engine's search so that its answers are altered where
    they are produced."""
    inner = RetrievalEngine.search

    def search(self, queries, mu_eta=None, budget_frac=None):
        out = inner(self, queries, mu_eta=mu_eta, budget_frac=budget_frac)
        return out.__class__(**{**out.__dict__,
                                **alter(np.asarray(out.doc_ids),
                                        np.asarray(out.scores))})

    monkeypatch.setattr(RetrievalEngine, "search", search)


def test_sound_run_is_correct_and_the_control_is_not(root):
    result, rec, checks, sample = _run(root)
    assert result["correct"], checks
    assert list(result)[-1] == "checks"
    assert result["failed"] == 0 and result["attempted"] > 0
    assert checks["score_err"]["value"] < 1e-6
    limit = checks["score_err"]["limit"]
    ctl = control.control_numbers(sample, rec["geometry"]["k"])
    assert ctl["score_err"] > 10 * limit        # bfloat16 fails the limit


def test_altered_answer_is_not_correct(root, monkeypatch):
    def alter(ids, scores):
        ids = ids.copy()
        ids[:, 0] = (ids[:, 0] + 1) % 3000      # another doc, same score
        return {"doc_ids": ids}
    _broken(monkeypatch, alter)
    result, _, checks, _ = _run(root)
    assert not result["correct"]
    assert not checks["score_err"]["ok"]


def test_half_the_batch_left_out_is_not_correct(root, monkeypatch):
    def alter(ids, scores):
        half = ids.shape[0] // 2               # rows past half get row 0's
        ids, scores = ids.copy(), scores.copy()
        ids[half:], scores[half:] = ids[0], scores[0]
        return {"doc_ids": ids, "scores": scores}
    _broken(monkeypatch, alter)
    result, _, checks, _ = _run(root)
    assert not result["correct"]
    assert not (checks["score_err"]["ok"] and checks["prop3_ratio"]["ok"])


def test_open_loop_run_and_traced_result_line(root):
    cell = tiny_cell(root, "tiny.poisson80")
    result, rec, checks, _ = run.run_cell(cell, SEED + 1, 1.0, True)
    assert result["correct"], checks
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s", "memory_peak_bytes", "platform", "kind",
            "count"} <= set(result["device"])
    # every request was timed from when it was due, and answered
    reqs = rec["requests"]
    assert reqs and all(r["served"] and r["lag_ms"] is not None
                        and np.isfinite(r["latency_ms"]) for r in reqs)
    # the cell lists no per-layer metric, and off the chip the trace
    # holds no device
    assert result["metrics"] == {}
    assert rec["compiles_in_window"] == 0


def test_main_traced_run_in_a_fresh_checkout(root, monkeypatch, capsys):
    """The command's own traced path, with only the look for a chip and
    the compile cache stubbed: it needs no directory that a checkout
    lacks, and its last stdout line is the result."""
    monkeypatch.setattr(run, "use_checkout_cache", lambda: None)
    monkeypatch.setattr(run, "device_info", lambda chips: {"platform": "x"})
    cell = tiny_cell(root)
    monkeypatch.setattr("bench.spec.load_cell", lambda name, _root: cell)
    assert run.main(["--workload", "tiny.closed128", "--seed",
                     str(SEED + 7), "--seconds", "1", "--trace", "1"]) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] and list(result)[-1] == "checks"
    assert "breakdown" in result and "window_s" in result["device"]
    assert {"docs_admitted_per_query", "clusters_scored_share"} <= set(
        result["metrics"])
    # the wave counter reaches its reader; the idle time by program span
    # rides on the line (empty off the chip: the capture has no device)
    assert result["metrics"]["waves_per_batch"]["value"] >= 1
    assert isinstance(result["idle_gaps_by_program_span"], list)
    assert err.strip().splitlines()[-1].startswith("[bench] check ")


def test_control_alone_fails_the_limit(root):
    cell = tiny_cell(root)
    numbers = control.control_numbers(control.control_sample(cell, SEED + 5),
                                      cell.config["search"]["k"])
    assert numbers["score_err"] > cell.config["limits"]["score_err"]["max"]
