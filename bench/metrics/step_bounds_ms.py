"""Device time per step launch of the served step's leaf operations
under the ``asc.bounds`` scope: the bounds pass (query maps, the bound
GEMM or gather, the method's statistics; on the two-level walk, the
superblock and member pricing)."""
from bench.scope_reduce import phase_ms


def read(rec):
    return phase_ms(rec, "asc.bounds")
