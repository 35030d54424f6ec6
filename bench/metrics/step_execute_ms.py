"""Device time per step launch of the served step's leaf operations
under the ``asc.execute`` scope: the executor (each wave's scoring of
its admitted tiles)."""
from bench.scope_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "asc.execute")
