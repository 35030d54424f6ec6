"""The engine's own host time per batch: the ``engine.search`` span less
its ``engine.wait`` child (the wait for the step), over the window's
batches."""


def read(rec):
    scopes = rec.get("scopes")
    ms = scopes["engine_self_ms"] if scopes else None
    return sum(ms) / len(ms) if ms else None
