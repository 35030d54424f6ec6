"""Reduction from a profiler trace to busy and idle time, per-program
device time, the top device operations and the idle gaps by what the
host was doing.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain form, ``{plane name: {line name: [(event name, start_ns,
duration_ns), ...]}}``; ``reduce`` works on that form only, so it is
tested on a small synthetic trace. Device planes are those named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line gives the operations
and the ``XLA Modules`` line the programs. The measured window is the
host span named ``WINDOW_SPAN``; host spans whose names start with
``bench.`` label the idle gaps, and each program launch is filed under
the host span it started in (so the served step is the program that
runs inside ``bench.dispatch``, whatever its name).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LABEL_PREFIX = "bench."
TOP = 10


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in plain form."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out: dict = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in line.events)
        out[plane.name] = lines
    return out


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def window(trace: dict) -> tuple[int, int]:
    """(start_ns, end_ns) of the host's window span."""
    for lines in trace.values():
        for events in lines.values():
            for name, s, d in events:
                if name == WINDOW_SPAN:
                    return s, s + d
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def reduce(trace: dict) -> dict:
    """Window length, device busy time (averaged over the device planes),
    time and launches per (host span, program), the top operations and
    the idle time by host span. A launch that the window's edge cuts
    counts by the share of it inside the window."""
    lo, hi = window(trace)
    devices = {p: lines for p, lines in trace.items()
               if p.startswith(DEVICE_PREFIX)}
    host_spans = [(s, s + d, name)
                  for p, lines in trace.items()
                  if not p.startswith(DEVICE_PREFIX)
                  for events in lines.values()
                  for name, s, d in events
                  if name.startswith(HOST_LABEL_PREFIX)
                  and name != WINDOW_SPAN]
    busy_ns, ops, modules, gaps = [], defaultdict(float), {}, defaultdict(float)
    for lines in devices.values():
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE, [])
        clipped = list(_clip(op_events, lo, hi))
        merged = _union((a, b) for _, a, b in clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        for name, a, b in clipped:
            ops[short_op(name)] += (b - a) / 1e9
        # a launch that runs past the window's edge counts by the share
        # of it inside, as its time does
        mods = sorted(((name, max(s, lo), min(s + d, hi), d)
                       for name, s, d in lines.get(MODULES_LINE, [])
                       if min(s + d, hi) > max(s, lo)), key=lambda e: e[1])
        for (name, a, b, d), label in zip(mods, _labels(
                host_spans, [a for _, a, _, _ in mods])):
            t, n = modules.get((label, name), (0.0, 0))
            modules[(label, name)] = (t + (b - a) / 1e9, n + (b - a) / d)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for (a, b), label in zip(idle, _labels(host_spans,
                                               [(a + b) // 2
                                                for a, b in idle])):
            gaps[label] += (b - a) / 1e9
    n_dev = max(len(devices), 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "devices": len(devices),
        "modules": [[label, name, t / n_dev, n / n_dev]
                    for (label, name), (t, n) in sorted(modules.items())],
        "device_ops": _top({k: v / n_dev for k, v in ops.items()}),
        "idle_gaps": _top({k: v / n_dev for k, v in gaps.items()}),
    }


def _labels(spans, instants) -> list:
    """For each of the ascending ``instants``, the name of the
    latest-starting host span around it (one sweep)."""
    spans = sorted(spans)
    out, active, i = [], [], 0
    for t in instants:
        while i < len(spans) and spans[i][0] <= t:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > t]
        out.append(max(active)[2] if active else "no bench span")
    return out


def short_op(name: str) -> str:
    """``%fusion.2 fusion pred[1310720]`` for an HLO instruction's text
    (identifier, opcode, result shape without layouts); other names as
    they are."""
    text = re.sub(r"\{[^{}]*\}", "", name)
    m = re.match(r"(%[\w.\-]+) = (.*)$", text, re.S)
    if not m:
        return text[:100]
    ident, rhs = m.groups()
    if rhs.startswith("("):                    # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "tuple", rhs[i + 1:].lstrip()
    else:
        shape, _, rest = rhs.partition(" ")
    opcode = re.match(r"[\w\-]*", rest).group(0)
    return f"{ident} {opcode} {shape}"[:100]


def _top(totals: dict) -> list:
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]]
