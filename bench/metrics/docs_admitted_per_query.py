"""Documents the pruning admitted for scoring, per query (the funnel's
``docs_scored`` over the window's batches)."""


def read(rec):
    calls = rec["calls"]
    rows = sum(c["rows"] for c in calls)
    return sum(c["docs"] for c in calls) / rows if rows else None
