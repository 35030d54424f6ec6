"""Waves the served step walked per batch (``TopK.n_waves``), over the
window's batches."""


def read(rec):
    waves = [c["waves"] for c in rec["calls"] if c.get("waves") is not None]
    return sum(waves) / len(waves) if waves else None
