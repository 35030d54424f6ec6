"""Share of the HBM roofline the served step reaches: the least time
the chip's HBM bandwidth allows for the window's admitted work
(bench/roofline.py) over the step program's device time."""
from bench.readout import step_program
from bench.roofline import step_bytes


def read(rec):
    step, calls, peak = step_program(rec), rec["calls"], rec["peak"]
    if step is None or not calls or peak is None:
        return None
    g = rec["geometry"]
    total = sum(step_bytes(n_q=c["rows"], admitted_docs=c["docs_max"],
                           distinct_terms=c["distinct_terms"],
                           bounded_clusters=c["bounded"], t_pad=g["t_pad"],
                           n_seg=g["n_seg"], vocab=g["vocab"])
                for c in calls)
    # device time of as many launches as the window made calls
    device_s = step[0] / step[1] * len(calls)
    return 100.0 * total / peak["hbm_bytes_per_s"] / device_s
