"""Device time per step launch of the served step's leaf operations
under the ``asc.merge`` scope: the top-k merge (each wave's candidate
filter, group top-k and 2k merge, the counters and the early-exit
test)."""
from bench.scope_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "asc.merge")
