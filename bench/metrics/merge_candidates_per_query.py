"""Scored docs that beat their query's theta and entered the top-k merge,
per query (``TopK.n_merge_candidates``, a per-query counter, summed over
the window's batches); None where no call carries the counter."""


def read(rec):
    calls = [c for c in rec["calls"]
             if "n_merge_candidates" in c["counters"]]
    rows = sum(c["rows"] for c in calls)
    if not rows:
        return None
    return sum(c["counters"]["n_merge_candidates"]["sum"]
               for c in calls) / rows
