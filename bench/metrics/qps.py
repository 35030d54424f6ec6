"""Queries served per second of the window: the work of every batch the
engine served in the window, over the window's seconds. A batch counts
its requests in proportion to the part of its ``engine.search`` call
inside the window, so the batch running when the window closes counts
for what it did, and one step more or less in a window does not jump
the rate by a whole batch."""


def read(rec):
    t0, t1 = rec["t0"], rec["t_end"]
    work = sum(c["served"] * (min(c["end"], t1) - max(c["start"], t0))
               / (c["end"] - c["start"])
               for c in rec["calls"] if c["end"] > c["start"])
    return work / rec["window_s"] if work else None
