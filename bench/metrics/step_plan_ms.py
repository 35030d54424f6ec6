"""Device time per step launch of the served step's leaf operations
under the ``asc.plan`` scope: the planner (the visitation order, and
each wave's admission and work queues)."""
from bench.scope_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "asc.plan")
