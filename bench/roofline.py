"""Least HBM bytes of one served step, from the algorithm's admitted
work (not from the implementation that runs it).

A batch must at least read, once each: the forward rows of the
documents its queries admit (t_pad term ids of 2 bytes and t_pad
weights of 1 byte), the bound-table entries of its distinct query terms
in every cluster it bounds (n_seg segment rows plus the collapsed row,
1 byte each), and its dense query maps ((V + 1) float32 per query).
The batch shares its reads, so a document admitted by several queries
is read once: the batch's admitted documents are counted as the most
that any one of its queries admits, a lower bound of their union (the
counters hold no more), so the share it gives is never overstated.
"""

from __future__ import annotations


def step_bytes(*, n_q: int, admitted_docs: int, distinct_terms: int,
               bounded_clusters: int, t_pad: int, n_seg: int,
               vocab: int) -> int:
    return (admitted_docs * t_pad * 3
            + distinct_terms * bounded_clusters * (n_seg + 1)
            + n_q * (vocab + 1) * 4)
