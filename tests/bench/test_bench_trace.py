"""The trace reduction and the device-side metric readers, on a small
synthetic trace in the reduction's plain form; the peak table."""

from __future__ import annotations

import pytest

import _bench_tiny  # noqa: F401
from bench import peaks, trace_reduce
from bench.spec import metric_reader

# times in ns: a 10 us window with two served steps and one other program
TRACE = {
    "/host:CPU": {"python": [
        ("bench.window", 1000, 10000),
        ("bench.dispatch", 1100, 2000),
        ("bench.submit", 4000, 100),
        ("bench.dispatch", 5000, 3000),
        ("PjitFunction(<lambda>)", 5000, 100),
    ]},
    "/device:TPU:0": {
        "XLA Ops": [("fusion.1", 1200, 800), ("while", 2000, 1000),
                    ("fusion.1", 5100, 2500), ("early", 100, 200)],
        "XLA Modules": [("jit__lambda", 1200, 1800),
                        ("jit__lambda", 5100, 2500),
                        ("jit_other", 9500, 300)],
    },
}


def test_reduce_busy_idle_programs_and_gaps():
    r = trace_reduce.reduce(TRACE)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(4.3e-6)      # [1200,3000] + [5100,7600]
    assert r["devices"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(3.3e-6)]
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx({
        "no bench span": 3.4e-6, "bench.submit": 2.1e-6,
        "bench.dispatch": 0.2e-6})
    mods = {(lbl, name): (t, n) for lbl, name, t, n in r["modules"]}
    assert mods[("bench.dispatch", "jit__lambda")] == (
        pytest.approx(4.3e-6), 2)
    assert mods[("no bench span", "jit_other")] == (pytest.approx(0.3e-6), 1)


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce({"/device:TPU:0": TRACE["/device:TPU:0"]})


def _rec(trace):
    return {
        "trace": trace,
        "peak": peaks.peak("TPU v5 lite"),
        "geometry": {"m": 80, "d_pad": 2560, "t_pad": 128, "n_seg": 8,
                     "vocab": 30522, "k": 10},
        "calls": [{"wall_s": 3e-6, "rows": 64, "docs": 1000, "docs_max": 40,
                   "clusters": 640, "bounded": 80, "distinct_terms": 500},
                  {"wall_s": 4e-6, "rows": 64, "docs": 3000, "docs_max": 90,
                   "clusters": 1280, "bounded": 80, "distinct_terms": 700}],
    }


def test_device_metric_readers():
    rec = _rec(trace_reduce.reduce(TRACE))
    read = {n: metric_reader(n)(rec) for n in (
        "step_device_ms", "device_idle_share", "engine_host_ms_per_batch",
        "step_roofline", "docs_admitted_per_query", "clusters_scored_share")}
    assert read["step_device_ms"] == pytest.approx(2.15e-3)
    assert read["device_idle_share"] == pytest.approx(0.57)
    assert read["engine_host_ms_per_batch"] == pytest.approx(3.5e-3 - 2.15e-3)
    assert read["docs_admitted_per_query"] == pytest.approx(4000 / 128)
    assert read["clusters_scored_share"] == pytest.approx(1920 / (80 * 128))
    least = sum(d * 128 * 3 + t * 80 * 9 + 64 * 30523 * 4
                for d, t in ((40, 500), (90, 700))) / 819e9
    assert read["step_roofline"] == pytest.approx(
        100 * least / (2 * 2.15e-6))


def test_device_readers_read_nothing_without_a_trace():
    rec = _rec(None)
    for n in ("step_device_ms", "device_idle_share",
              "engine_host_ms_per_batch", "step_roofline"):
        assert metric_reader(n)(rec) is None


def test_qps_counts_the_work_inside_the_window():
    calls = [{"start": 0.0, "end": 1.0, "served": 64},
             {"start": 1.0, "end": 2.0, "served": 64},
             {"start": 2.0, "end": 3.0, "served": 64}]
    rec = {"t0": 0.0, "t_end": 2.5, "window_s": 2.5, "calls": calls}
    assert metric_reader("qps")(rec) == pytest.approx(160 / 2.5)


def test_op_names_are_shortened():
    name = ("%fusion.2 = pred[1310720]{0:T(1024)(128)(4,1)S(1)} fusion("
            "pred[64,8,8]{0,2,1:T(8,128)} %get-tuple-element.144), "
            "kind=kCustom, calls=%fused_computation.2")
    assert trace_reduce.short_op(name) == "%fusion.2 fusion pred[1310720]"
    loop = ("%while.87 = (s32[]{:T(128)}, pred[64]{0:T(512)}) while("
            "(s32[]{:T(128)}, pred[64]{0:T(512)}) %tuple.129), body=%b")
    assert trace_reduce.short_op(loop) == "%while.87 while tuple"
    assert trace_reduce.short_op("plain") == "plain"


def test_peak_table():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("TPU v9 imaginary")


def test_a_launch_cut_by_the_window_counts_by_its_share_inside():
    """The capture runs on until the last batch has replied: the step
    launched before the window's close and running past it counts by
    the part inside, so its time per launch stays the launches'."""
    host = TRACE["/host:CPU"]["python"] + [("bench.dispatch", 10000, 2000)]
    dev = TRACE["/device:TPU:0"]
    trace = {"/host:CPU": {"python": host}, "/device:TPU:0": {
        "XLA Ops": dev["XLA Ops"] + [("fusion.1", 10100, 2150)],
        "XLA Modules": dev["XLA Modules"] + [("jit__lambda", 10100, 2150)]}}
    r = trace_reduce.reduce(trace)
    mods = {(lbl, name): (t, n) for lbl, name, t, n in r["modules"]}
    # 900 of its 2150 ns lie inside the window, which ends at 11000
    assert mods[("bench.dispatch", "jit__lambda")] == (
        pytest.approx(5.2e-6), pytest.approx(2 + 900 / 2150))
    assert metric_reader("step_device_ms")(_rec(r)) == pytest.approx(
        5.2e-3 / (2 + 900 / 2150))
    assert r["window_s"] - r["busy_s"] == pytest.approx(10e-6 - 5.2e-6)
