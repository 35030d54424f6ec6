"""The reduction of the step's named phases, the engine's own time and
the idle time by program span, and the readers of the seven metrics
that use them, on a small synthetic trace in ``trace_reduce``'s plain
form with the step's compiled text beside it."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from _bench_tiny import make_tiny
from bench import run, scope_reduce, spec, trace_reduce
from bench.spec import metric_reader
from repro.core.types import QueryBatch, TopK

# the step's compiled text, as RetrievalEngine.step_text gives it: the
# phases ride in the op_name metadata; the compiler's copy.7 has none
# (and no caller with a phase), add_convert.8 has none but fuses scoped
# instructions, and copy-start.9 inherits the executor's conditional
STEP_TEXT = '''HloModule jit_step

%fused_computation.5 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %gather.1 = f32[8]{0} sqrt(f32[8]{0} %p), metadata={op_name="jit(step)/while/body/asc.execute/cond/branch_1_fun/gather"}
}

%fused_computation.8 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %convert.2 = f32[8]{0} sqrt(f32[8]{0} %p.1), metadata={op_name="jit(step)/while/body/asc.execute/cond/branch_1_fun/convert"}
}

%branch_1 (b: f32[8]) -> (f32[8]) {
  %b = f32[8]{0} parameter(0)
  %copy-start.9 = (f32[8]{0}, f32[8]{0}) copy-start(f32[8]{0} %b)
  %add_convert.8 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop, calls=%fused_computation.8
  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %add_convert.8), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/while/body/asc.execute/cond/branch_1_fun/gather"}
  ROOT %tuple.1 = (f32[8]{0}) tuple(f32[8]{0} %fusion.5)
}

%body (s: (s32[], f32[8])) -> (s32[], f32[8]) {
  %s = (s32[], f32[8]{0}) parameter(0)
  %fusion.3 = s32[8]{0} fusion(), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/while/body/asc.plan/argsort"}
  %cond.4 = (f32[8]{0}) conditional(s32[] %i, f32[8]{0} %x, f32[8]{0} %x), branch_computations={%branch_1, %branch_1}, metadata={op_name="jit(step)/while/body/asc.execute/cond"}
  %fusion.6 = f32[8]{0} fusion(), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/while/body/asc.merge/top_k"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(s32[] %i, f32[8]{0} %x)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(step)/asc.bounds/dot"}
  %while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%body, body=%body, metadata={op_name="jit(step)/while"}
  ROOT %copy.7 = f32[8]{0} copy(f32[8]{0} %fusion.1)
}
'''


def _op(ident, opcode, shape="f32[8]{0}"):
    """An XLA Ops event name: the instruction's text, no metadata."""
    return f"%{ident} = {shape} {opcode}({shape} %p)"


def _step_ops(t, bounds, plan, execute, merge, copy):
    """One launch of the step from ``t``: a bounds op outside the loop,
    a while loop holding a plan op, a conditional holding executor ops
    (one phase from its own op_name, one from the computation it fuses,
    one from the conditional), a merge op, then an unscoped copy."""
    a = t + bounds
    b = a + plan
    c = b + execute
    d = c + merge
    third = execute // 3
    return [
        (_op("fusion.1", "fusion"), t, bounds),
        (_op("while.2", "while", shape="(s32[], f32[8]{0})"), a, d - a),
        (_op("fusion.3", "fusion"), a, plan),
        (_op("cond.4", "conditional", shape="(f32[8]{0})"), b, execute),
        (_op("copy-start.9", "copy-start", shape="(f32[8]{0}, f32[8]{0})"),
         b, third),
        (_op("add_convert.8", "fusion"), b + third, third),
        (_op("fusion.5", "fusion"), b + 2 * third, execute - 2 * third),
        (_op("fusion.6", "fusion"), c, merge),
        (_op("copy.7", "copy"), d, copy),
    ]


# times in ns: a 10 us window, two batches, each one step launch inside
# bench.dispatch, and another program outside it
TRACE = {
    "/host:CPU": {"python": [
        ("bench.window", 1000, 10000),
        ("frontend.pump", 1000, 50),
        ("frontend.dispatch", 1050, 4000),
        ("frontend.stack", 1060, 100),
        ("bench.dispatch", 1150, 3850),
        ("engine.search", 1160, 3820),
        ("engine.prepare", 1165, 20),
        ("engine.launch", 1190, 50),
        ("engine.wait", 1240, 3700),
        ("engine.account", 4945, 30),
        ("frontend.reply", 5060, 200),
        ("frontend.pump", 5300, 20),
        ("frontend.dispatch", 5400, 4000),
        ("frontend.stack", 5410, 90),
        ("bench.dispatch", 5500, 3850),
        ("engine.search", 5510, 3830),
        ("engine.wait", 5600, 3700),
        ("frontend.reply", 9420, 180),
    ]},
    "/device:TPU:0": {
        "XLA Ops": (_step_ops(1250, 50, 200, 3000, 300, 100)
                    + _step_ops(5610, 40, 100, 3300, 100, 100)
                    + [(_op("fusion.1", "fusion"), 9700, 200)]),
        "XLA Modules": [("jit_step", 1250, 3650),
                        ("jit_step", 5610, 3640),
                        ("jit_other", 9700, 200)],
    },
}

NEW_METRICS = ("step_bounds_ms", "step_plan_ms", "step_execute_ms",
               "step_merge_ms", "waves_per_batch",
               "engine_self_ms_per_batch", "frontend_idle_ms_per_batch")


def _rec(trace, waves=(6, 8), step_text=STEP_TEXT):
    return {
        "trace": trace_reduce.reduce(trace) if trace else None,
        "scopes": scope_reduce.reduce(trace, step_text) if trace else None,
        "calls": [{"rows": 64, "waves": w} for w in waves],
    }


def test_op_phases_from_the_compiled_text():
    ph = scope_reduce.op_phases(STEP_TEXT)
    assert ph["fusion.1"] == "asc.bounds"
    assert ph["fusion.3"] == "asc.plan"
    assert ph["fusion.6"] == "asc.merge"
    assert ph["fusion.5"] == ph["cond.4"] == "asc.execute"
    # no op_name: the fused computation's phase, else the caller's
    assert ph["add_convert.8"] == "asc.execute"
    assert ph["copy-start.9"] == "asc.execute"
    assert ph["copy.7"] is None and ph["while.2"] is None


def test_step_phases_leave_containers_out():
    ph = scope_reduce.step_phases(TRACE, STEP_TEXT)
    assert ph["launches"] == 2
    # ns -> s; the other program's ops are not the step's
    assert ph["device_s"] == pytest.approx({
        "asc.bounds": 90e-9, "asc.plan": 300e-9, "asc.execute": 6300e-9,
        "asc.merge": 400e-9})
    assert ph["unscoped_s"] == pytest.approx(200e-9)


def test_phase_readers_divide_by_the_launches():
    rec = _rec(TRACE)
    read = {n: metric_reader(n)(rec) for n in NEW_METRICS}
    assert read["step_bounds_ms"] == pytest.approx(45e-6)
    assert read["step_plan_ms"] == pytest.approx(150e-6)
    assert read["step_execute_ms"] == pytest.approx(3150e-6)
    assert read["step_merge_ms"] == pytest.approx(200e-6)
    # the phases and the unscoped copy tile the step's device time
    step = metric_reader("step_device_ms")(rec)
    phases = sum(read[f"step_{p}_ms"] for p in
                 ("bounds", "plan", "execute", "merge"))
    assert phases + 100e-6 == pytest.approx(step)
    assert read["waves_per_batch"] == 7.0
    # (3820 - 3700 + 3830 - 3700) / 2 ns
    assert read["engine_self_ms_per_batch"] == pytest.approx(125e-6)
    # pump 70 + dispatch 160 + stack 190 + reply 380 ns over 2 batches
    assert read["frontend_idle_ms_per_batch"] == pytest.approx(400e-6)


def test_idle_goes_to_the_innermost_program_span():
    idle = scope_reduce.idle_by_span(TRACE)
    assert idle == pytest.approx({
        "frontend.pump": 70e-9, "frontend.dispatch": 160e-9,
        "frontend.stack": 190e-9, "frontend.reply": 380e-9,
        "engine.search": 150e-9, "engine.prepare": 20e-9,
        "engine.launch": 50e-9, "engine.wait": 110e-9,
        "engine.account": 30e-9, "no program span": 1350e-9})
    # all of the window's idle time, as trace_reduce counts it
    t = trace_reduce.reduce(TRACE)
    assert sum(idle.values()) == pytest.approx(t["window_s"] - t["busy_s"])
    # with the benchmark's spans too, bench.dispatch is innermost where
    # it starts after the front-end's span and outlasts the engine's
    with_bench = scope_reduce.idle_by_span(
        TRACE, ("frontend.", "engine.", "bench."), "no span")
    assert with_bench["bench.dispatch"] == pytest.approx(50e-9)
    assert with_bench["no span"] == pytest.approx(1350e-9)


def test_new_readers_read_nothing_untraced():
    rec = _rec(None, waves=(None, None))
    for n in NEW_METRICS:
        assert metric_reader(n)(rec) is None, n


def test_a_program_without_spans_or_scopes_reads_nothing():
    """The parent program: no ``asc.*`` scope in its compiled text, no
    program span, no wave counter. The readers return None and do not
    raise."""
    bare = {"/host:CPU": {"python": [
        e for e in TRACE["/host:CPU"]["python"] if e[0].startswith("bench.")]},
        "/device:TPU:0": TRACE["/device:TPU:0"]}
    for text in (None, STEP_TEXT.replace("asc.", "")):
        rec = {"trace": trace_reduce.reduce(bare),
               "scopes": scope_reduce.reduce(bare, text),
               "calls": [{"rows": 64}, {"rows": 64}]}
        for n in NEW_METRICS:
            assert metric_reader(n)(rec) is None, n
        assert rec["scopes"]["idle_by_program_span"] == pytest.approx(
            {"no program span": 2510e-9})


def test_program_span_prefixes_are_the_files_of_program_spans():
    assert spec.program_span_prefixes() == ("engine.", "frontend.")


def test_spans_in_the_window_by_name():
    spans = scope_reduce.reduce(TRACE, STEP_TEXT)["spans"]
    assert set(spans) == {n for n, _, _ in TRACE["/host:CPU"]["python"]
                          if not n.startswith("bench.")}
    assert spans["frontend.dispatch"] == [2, pytest.approx(8000e-9)]
    # the second dispatch runs past the window's close at 11000
    assert spans["frontend.reply"] == [2, pytest.approx(380e-9)]
    assert spans["engine.prepare"] == [1, pytest.approx(20e-9)]


def test_a_span_under_a_new_prefix_reaches_the_readers(tmp_path):
    """A prefix declared by one new file of ``program_spans/``: its spans
    are in ``spans`` and take their idle time in
    ``idle_by_program_span``, with no other edit."""
    root = make_tiny(str(tmp_path))
    (tmp_path / "program_spans" / "router.txt").write_text(
        "a router in front of the engines: router.route\n")
    prefixes = spec.program_span_prefixes(root)
    assert prefixes == ("engine.", "frontend.", "router.")
    trace = {**TRACE, "/host:CPU": {"python": TRACE["/host:CPU"]["python"]
                                    + [("router.route", 9500, 300)]}}
    scopes = scope_reduce.reduce(trace, STEP_TEXT, prefixes)
    assert scopes["spans"]["router.route"] == [1, pytest.approx(300e-9)]
    # the idle 9500-9700 before the other program: the route span starts
    # inside the last reply (9420-9600) and is the innermost there
    assert scopes["idle_by_program_span"]["router.route"] == pytest.approx(
        200e-9)
    assert scopes["idle_by_program_span"]["frontend.reply"] == pytest.approx(
        280e-9)
    without = scope_reduce.reduce(trace, STEP_TEXT)
    assert "router.route" not in without["spans"]
    assert "router.route" not in without["idle_by_program_span"]


def test_the_seven_readers_read_a_record_built_as_the_harness_builds_it():
    """``calls`` from ``run.call_counters`` on returned ``TopK``s, and
    ``trace`` / ``scopes`` as ``run.run_cell`` reduces a capture: each
    of the seven readers gives a number."""
    def out(waves):
        n = np.full(64, 1, np.int32)
        return TopK(doc_ids=np.zeros((64, 10), np.int32),
                    scores=np.zeros((64, 10), np.float32),
                    **{f.name: n * (waves if f.name == "n_waves" else 1)
                       for f in dataclasses.fields(TopK)
                       if f.name.startswith("n_")})
    q = QueryBatch(tids=np.zeros((64, 4), np.int32),
                   tw=np.ones((64, 4), np.float32),
                   mask=np.ones((64, 4), bool), vocab=8)
    rec = {"calls": [run.call_counters((1.0, 2.0, q, out(w)))
                     for w in (6, 8)],
           "trace": trace_reduce.reduce(TRACE),
           "scopes": scope_reduce.reduce(TRACE, STEP_TEXT,
                                         spec.program_span_prefixes())}
    read = {n: metric_reader(n)(rec) for n in NEW_METRICS}
    assert all(v is not None for v in read.values()), read
    assert read["waves_per_batch"] == 7.0
    assert read == pytest.approx(
        {n: metric_reader(n)(_rec(TRACE)) for n in NEW_METRICS})


def test_a_new_counter_span_and_scope_reach_readers_added_as_files(tmp_path):
    """A configuration's new ``TopK`` counter, its span under a new
    prefix and its new ``asc.*`` scope each reach a reader that is added
    as a new file; the harness's code is not edited."""
    root = make_tiny(str(tmp_path))
    (tmp_path / "program_spans" / "router.txt").write_text("router.route\n")
    readers = {
        "merge_rows_per_batch": "return sum(c['counters']['n_merge_rows']"
                                "['first'] for c in rec['calls'])",
        "routes_per_window": "return rec['scopes']['spans']"
                             "['router.route'][0]",
        "step_rerank_ms": "from bench.scope_reduce import phase_ms\n"
                          "    return phase_ms(rec, 'asc.rerank')",
    }
    for name, body in readers.items():
        (tmp_path / "metrics" / f"{name}.py").write_text(
            f"def read(rec):\n    {body}\n")

    @dataclasses.dataclass(frozen=True)
    class Wider(TopK):
        n_merge_rows: np.ndarray = None

    n = np.ones(2, np.int32)
    out = Wider(doc_ids=np.zeros((2, 10), np.int32),
                scores=np.zeros((2, 10), np.float32),
                n_merge_rows=np.array([1000, 1000], np.int32),
                **{f.name: n for f in dataclasses.fields(TopK)
                   if f.name.startswith("n_")})
    q = QueryBatch(tids=np.zeros((2, 4), np.int32),
                   tw=np.ones((2, 4), np.float32),
                   mask=np.ones((2, 4), bool), vocab=8)
    trace = {**TRACE, "/host:CPU": {"python": TRACE["/host:CPU"]["python"]
                                    + [("router.route", 9500, 300)]}}
    rec = {"calls": [run.call_counters((1.0, 2.0, q, out))] * 2,
           "trace": trace_reduce.reduce(trace),
           "scopes": scope_reduce.reduce(
               trace, STEP_TEXT.replace("asc.merge", "asc.rerank"),
               spec.program_span_prefixes(root))}
    read = {n: metric_reader(n, root)(rec) for n in readers}
    assert read["merge_rows_per_batch"] == 2000
    assert read["routes_per_window"] == 1
    assert read["step_rerank_ms"] == pytest.approx(200e-6)
