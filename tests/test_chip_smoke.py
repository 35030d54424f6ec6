"""chip_smoke.py's phases on the CPU at a tiny size.

The chip run itself needs a TPU; here the phase functions run through
the same control flow and oracle checks at a toy geometry (Pallas
kernels in interpret mode), the checks are shown to catch wrong
answers, and the script is shown to refuse a non-TPU platform and a
checkout without the package. The four-device phase runs in a child
process on four virtual CPU devices.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


cs = _load()

TINY = cs.Geometry(n_docs=480, m=8, vocab=256, n_seg=4, d_pad=96, t_pad=32,
                   q_pad=12, doc_terms=16, query_terms=6, n_requests=16,
                   max_batch=8)


def _cpu_env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"), **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


@pytest.fixture(scope="module")
def world():
    from repro.data.synthetic import make_queries
    spec, index, doc_topic = cs.index_phase(TINY)
    queries, _ = make_queries(spec, TINY.n_requests, doc_topic, seed=1)
    return spec, index, queries, cs.oracle_phase(index, queries, TINY)


def test_device_phase_refuses_cpu():
    with pytest.raises(cs.SmokeFailure, match="platform 'cpu'"):
        cs.device_phase()


def test_index_phase_builds_requested_geometry(world):
    _, index, _, _ = world
    assert (index.m, index.d_pad, index.t_pad, index.n_seg, index.vocab) \
        == (TINY.m, TINY.d_pad, TINY.t_pad, TINY.n_seg, TINY.vocab)
    assert int(index.cluster_ndocs.sum()) == TINY.n_docs


def test_serve_and_direct_phases_pass_their_oracle_checks(world, capsys):
    spec, index, queries, oracle = world
    out = cs.serve_phase(spec, index, queries, oracle, TINY)
    cs.direct_phase(index, queries, oracle, TINY)
    assert 0.0 < out["recall"] <= 1.0
    log = capsys.readouterr().out
    assert f"{TINY.n_requests}/{TINY.n_requests} served" in log
    assert "batch 1 (per_query" in log and "batch 8 (batched" in log


def test_kernel_phase_matches_references(world, capsys):
    _, index, queries, _ = world
    cs.kernel_phase(index, cs._rows(queries, 0, TINY.max_batch))
    log = capsys.readouterr().out
    assert "kernel segment_bound_gemm" in log
    for n in (8, TINY.d_pad):
        assert f"compact_front_pallas (8, {n})" in log
    assert "score_queue_kernel: refused" in log


def test_checks_catch_wrong_answers(world):
    _, index, queries, (o_ids, o_scores) = world
    cs.check_exact("same", o_ids, o_scores, o_ids, o_scores)
    truth = cs.true_scores(index, queries, o_ids)
    np.testing.assert_allclose(truth, o_scores, rtol=cs.RTOL)
    cs.check_true("same", o_ids, o_scores, truth)

    ids = o_ids.copy()
    ids[0, 0] = next(d for d in range(TINY.n_docs) if d not in o_ids[0])
    with pytest.raises(cs.SmokeFailure, match="beyond k-th ties"):
        cs.check_exact("swapped id", ids, o_scores, o_ids, o_scores)
    with pytest.raises(cs.SmokeFailure, match="brute force"):
        cs.check_true("swapped id", ids, o_scores,
                      cs.true_scores(index, queries, ids))
    scores = o_scores.copy()
    scores[1, 0] *= 1.001
    with pytest.raises(cs.SmokeFailure, match="scores"):
        cs.check_exact("scaled score", o_ids, scores, o_ids, o_scores)

    # a tie at the k-th score may swap which doc fills the last slot
    tied = o_scores.copy()
    tied[2, -1] = tied[2, -2]
    ids = o_ids.copy()
    ids[2, -1] = next(d for d in range(TINY.n_docs) if d not in o_ids[2])
    cs.check_exact("tie", ids, tied, o_ids, tied)
    assert cs.recall_at_k(o_ids, o_ids) == 1.0


def test_script_fails_off_the_chip_without_result_line():
    out = subprocess.run([sys.executable, SCRIPT], env=_cpu_env(),
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_script_fails_without_the_package(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = subprocess.run([sys.executable, str(lone)], env=_cpu_env(),
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_sharded_phase_on_four_virtual_devices():
    body = f"""
import importlib.util, sys
import jax
assert jax.device_count() == 4, jax.devices()
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
cs = importlib.util.module_from_spec(spec)
sys.modules["chip_smoke"] = cs
spec.loader.exec_module(cs)
from repro.data.synthetic import make_queries
geo = cs.Geometry(n_docs=480, m=8, vocab=256, n_seg=4, d_pad=96, t_pad=32,
                  q_pad=12, doc_terms=16, query_terms=6, max_batch=8)
spec_, index, doc_topic = cs.index_phase(geo)
queries, _ = make_queries(spec_, geo.max_batch, doc_topic, seed=1)
cs.sharded_phase(index, queries, geo)
"""
    env = _cpu_env(XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", body], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "== single-device retrieve" in out.stdout
