"""Reduction from a profiler trace to the served step's device time by
named phase, the engine's own host time per batch, and the device's
idle time by the innermost program span.

It works on the plain form of ``trace_reduce.load``, so it is tested on
a small synthetic trace, and on the compiled text of the step
(``RetrievalEngine.step_text``):

- The step's phases are the ``asc.*`` named scopes of
  ``repro.core.search`` (``PHASE_SCOPES``). A TPU capture names each
  operation by its HLO instruction (``%fusion.2 = pred[...] fusion(...)``)
  and carries no ``op_name``, so an operation's phase is read from the
  step's compiled text, where each instruction's ``op_name`` holds its
  scope path (``op_phases``).
- Only leaf operations count: a ``while``, ``conditional`` or ``call``
  holds other operations, and its time is theirs.
- The step is the program that ``bench.readout.step_program`` picks:
  the one with the most device time among those launched inside
  ``bench.dispatch``. Its operations are those that start inside one of
  its launches in the window.
- Program spans are the host spans (``repro.obs.trace.host_span``)
  named under one of the program's span prefixes: ``<p>.`` for each
  file ``program_spans/<p>.txt`` of the benchmark
  (``spec.program_span_prefixes``), so a new prefix is a new file.
  Each stretch of device idle time in the window belongs to the
  innermost program span around it: the latest-starting one.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import trace_reduce
from bench.readout import DISPATCH_SPAN, step_program
from bench.spec import program_span_prefixes

PHASE_PREFIX = "asc."
NO_PROGRAM_SPAN = "no program span"
CONTAINERS = ("while", "conditional", "call")
SEARCH_SPAN, WAIT_SPAN = "engine.search", "engine.wait"


def op_phases(step_text: str) -> dict:
    """Instruction name -> phase (the last ``asc.*`` component of its
    ``op_name``, or None) for every instruction of a compiled module's
    text. An instruction the compiler made without a phase takes the
    phase most common in the computation it fuses, else the phase of
    the instruction that calls its computation."""
    entry, comp = None, None
    members, own, fused, calls = defaultdict(list), {}, {}, {}
    for line in step_text.splitlines():
        m = re.match(r"(ENTRY )?%([\w.\-]+) .*\{\s*$", line)
        if m:
            comp = m.group(2)
            entry = comp if m.group(1) else entry
            continue
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = ", line)
        if not m or comp is None:
            continue
        ident = m.group(1)
        members[comp].append(ident)
        op_name = re.search(r'op_name="([^"]*)"', line)
        parts = [p for p in (op_name.group(1) if op_name else "").split("/")
                 if p.startswith(PHASE_PREFIX)]
        own[ident] = parts[-1] if parts else None
        fused[ident] = re.findall(r"calls=%([\w.\-]+)", line)
        calls[ident] = re.findall(
            r"(?:calls|body|condition|to_apply|true_computation|"
            r"false_computation)=%([\w.\-]+)", line)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
            calls[ident] += [c.strip().lstrip("%") for c in group.split(",")]
    for ident in own:
        inner = [own[i] for c in fused[ident] for i in members[c]
                 if own[i] is not None]
        if own[ident] is None and inner:
            own[ident] = max(set(inner), key=inner.count)
    phases: dict = {}

    def visit(comp, inherited, seen):
        for ident in members[comp]:
            phases[ident] = own[ident] or inherited
            for c in calls[ident]:
                if c not in seen:
                    visit(c, phases[ident], seen | {c})

    if entry is not None:
        visit(entry, None, {entry})
    return phases


def ident_of(name: str) -> str:
    """The instruction name in an operation event's name."""
    m = re.match(r"%([\w.\-]+) = ", name)
    return m.group(1) if m else name


def opcode_of(name: str) -> str:
    """The HLO opcode in an operation event's name."""
    parts = trace_reduce.short_op(name).split(" ")
    return parts[1] if len(parts) == 3 else ""


def _device_planes(trace: dict) -> dict:
    return {p: lines for p, lines in trace.items()
            if p.startswith(trace_reduce.DEVICE_PREFIX)}


def _host_spans(trace: dict, prefixes) -> list:
    """Sorted (start, end, name) of the host events whose names start
    with one of ``prefixes``, the window span left out."""
    return sorted((s, s + d, name)
                  for p, lines in trace.items()
                  if not p.startswith(trace_reduce.DEVICE_PREFIX)
                  for events in lines.values()
                  for name, s, d in events
                  if name.startswith(prefixes)
                  and name != trace_reduce.WINDOW_SPAN)


def step_phases(trace: dict, step_text: str | None) -> dict:
    """Device seconds of the step's leaf operations by phase, and of
    those with no phase, averaged over the device planes; the step's
    launches per plane. No phase is read without ``step_text``."""
    lo, hi = trace_reduce.window(trace)
    bench_spans = _host_spans(trace, (trace_reduce.HOST_LABEL_PREFIX,))
    planes = _device_planes(trace)
    launches, per_module = {}, defaultdict(float)
    for p, lines in planes.items():
        mods = sorted(trace_reduce._clip(
            lines.get(trace_reduce.MODULES_LINE, []), lo, hi),
            key=lambda e: e[1])
        labels = trace_reduce._labels(bench_spans, [a for _, a, _ in mods])
        launches[p] = [(name, a, b) for (name, a, b), label
                       in zip(mods, labels) if label == DISPATCH_SPAN]
        for name, a, b in launches[p]:
            per_module[name] += b - a
    phase_of = op_phases(step_text) if step_text else {}
    step = max(per_module, key=per_module.get) if per_module else None
    device_s, unscoped, n_launch = defaultdict(float), 0.0, 0
    for p, lines in planes.items():
        spans = [(a, b) for name, a, b in launches[p] if name == step]
        n_launch += len(spans)
        starts = [a for a, _ in spans]
        for name, s, d in lines.get(trace_reduce.OPS_LINE, []):
            i = bisect.bisect_right(starts, s) - 1
            a, b = max(s, lo), min(s + d, hi)
            if i < 0 or s >= spans[i][1] or b <= a:
                continue
            if opcode_of(name) in CONTAINERS:
                continue
            phase = phase_of.get(ident_of(name))
            if phase is None:
                unscoped += (b - a) / 1e9
            else:
                device_s[phase] += (b - a) / 1e9
    n_dev = max(len(planes), 1)
    return {"launches": n_launch // n_dev,
            "device_s": {k: v / n_dev for k, v in sorted(device_s.items())},
            "unscoped_s": unscoped / n_dev}


def idle_gaps(trace: dict) -> list:
    """Per device plane, the window's idle (start, end) intervals, as
    ``trace_reduce.reduce`` finds them."""
    lo, hi = trace_reduce.window(trace)
    out = []
    for lines in _device_planes(trace).values():
        ops = (lines.get(trace_reduce.OPS_LINE)
               or lines.get(trace_reduce.MODULES_LINE, []))
        merged = trace_reduce._union(
            (a, b) for _, a, b in trace_reduce._clip(ops, lo, hi))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        out.append([(a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a])
    return out


def attribute(gaps, spans, rest: str) -> dict:
    """Seconds of the sorted, disjoint ``gaps`` by the innermost of the
    ``spans`` ((start, end, name), sorted) over each stretch of them:
    the latest-starting span that covers it, else ``rest``."""
    out: dict = defaultdict(float)
    active, i = [], 0
    for a, b in gaps:
        while i < len(spans) and spans[i][0] < b:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        cuts = sorted({a, b} | {x for sp in active for x in sp[:2]
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            over = [sp for sp in active if sp[0] <= x and sp[1] >= y]
            name = max(over, key=lambda sp: (sp[0], -sp[1]))[2] \
                if over else rest
            out[name] += (y - x) / 1e9
    return dict(out)


def idle_by_span(trace: dict, prefixes: tuple | None = None,
                 rest: str = NO_PROGRAM_SPAN) -> dict:
    """The window's device idle seconds by innermost host span among
    those named with ``prefixes`` (the program's, by default), averaged
    over the device planes."""
    spans = _host_spans(trace, prefixes or program_span_prefixes())
    per_plane = [attribute(g, spans, rest) for g in idle_gaps(trace)]
    total: dict = defaultdict(float)
    for d in per_plane:
        for k, v in d.items():
            total[k] += v / max(len(per_plane), 1)
    return dict(sorted(total.items(), key=lambda kv: -kv[1]))


def engine_self_ms(trace: dict) -> list:
    """For each ``engine.search`` span that starts in the window: its
    length less the ``engine.wait`` spans inside it, in ms."""
    lo, hi = trace_reduce.window(trace)
    spans = _host_spans(trace, (SEARCH_SPAN, WAIT_SPAN))
    waits = [(s, e) for s, e, name in spans if name == WAIT_SPAN]
    out = []
    for s, e, name in spans:
        if name == SEARCH_SPAN and lo <= s < hi:
            inner = sum(b - a for a, b in waits if s <= a and b <= e)
            out.append((e - s - inner) / 1e6)
    return out


def span_totals(trace: dict, spans: list) -> dict:
    """``{name: [count, host seconds]}`` of the ``spans`` ((start, end,
    name)) that start in the window, each one's seconds clipped to the
    window's close."""
    lo, hi = trace_reduce.window(trace)
    out: dict = {}
    for s, e, name in spans:
        if lo <= s < hi:
            n, sec = out.get(name, (0, 0.0))
            out[name] = [n + 1, sec + (min(e, hi) - s) / 1e9]
    return dict(sorted(out.items()))


def reduce(trace: dict, step_text: str | None = None,
           prefixes: tuple | None = None) -> dict:
    """The step's phases, the engine's own time per batch, the idle time
    by innermost program span and each program span's count and time in
    the window; ``has_program_spans`` says whether the program opened
    any span in the trace. ``prefixes`` name the program's spans (the
    program's, by default)."""
    prefixes = prefixes or program_span_prefixes()
    spans = _host_spans(trace, prefixes)
    return {
        "phases": step_phases(trace, step_text),
        "engine_self_ms": engine_self_ms(trace),
        "idle_by_program_span": idle_by_span(trace, prefixes),
        "spans": span_totals(trace, spans),
        "has_program_spans": bool(spans),
    }


def phase_ms(rec: dict, phase: str) -> float | None:
    """Device ms per step launch of the step's leaf operations under
    ``phase``, dividing by ``step_program``'s launch count; None
    untraced, or when no operation of the step has a phase."""
    scopes, step = rec.get("scopes"), step_program(rec)
    if not scopes or step is None or not scopes["phases"]["device_s"]:
        return None
    return scopes["phases"]["device_s"].get(phase, 0.0) / step[1] * 1e3
