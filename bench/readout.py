"""Arithmetic that the metric readers share."""

from __future__ import annotations

DISPATCH_SPAN = "bench.dispatch"


def step_program(rec: dict):
    """(device seconds, launches) of the served step: the program with
    the most device time among those launched inside ``engine.search``
    (the ``bench.dispatch`` span) in the traced window, a launch that
    runs past the window's close counted by its share inside; None
    untraced."""
    t = rec.get("trace")
    if not t:
        return None
    inside = [(sec, n) for label, _, sec, n in t["modules"]
              if label == DISPATCH_SPAN]
    if not inside:
        return None
    return max(inside)
