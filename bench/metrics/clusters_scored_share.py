"""Share of (cluster, query) pairs that the pruning scored: clusters
scored over m x queries, over the window's batches."""


def read(rec):
    calls = rec["calls"]
    rows = sum(c["rows"] for c in calls)
    if not rows:
        return None
    return sum(c["clusters"] for c in calls) / (rec["geometry"]["m"] * rows)
