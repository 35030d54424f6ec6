"""The control of the correctness comparison, on the chip at a cell's
own size.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

The control is the benchmark's exhaustive reference put in the
program's place and computed in bfloat16, the precision below the
configuration's float32. For each seed it generates the cell's corpus
and query pool, draws as many queries as a run compares (in the seeded
order a run draws them), answers them with the bfloat16 reference, and
puts those answers through the comparison a run makes against the
float32 reference: they have to fail it. One JSON line per seed on
stdout. The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_numbers(sample: dict, k: int) -> dict:
    """The comparison's numbers for the bfloat16 reference's answers to
    the sampled queries (``sample`` as ``run.run_cell`` returns it)."""
    from bench.reference import compare
    ref = sample["reference"]
    q = sample["queries"]
    ids, scores = ref.topk(*q, k, precision="bf16")
    pair = ref.pair_scores(*q, ids)
    return compare(ids, scores, *sample["ref"], pair)


def control_sample(cell, seed: int) -> dict:
    """The cell's data from ``seed`` and a run's worth of its queries,
    with their float32 reference answers."""
    import numpy as np

    from bench import gen
    from bench.reference import Reference
    config, traffic = cell.config, cell.traffic
    st = gen.Stats(**config["corpus"])
    tab = gen.tables(st, seed)
    tids, tw, mask, _ = gen.make_docs(st, seed, tab)
    q_tids, q_tw, q_mask, _ = gen.make_queries(
        st, traffic["query_pool"], traffic["topic_zipf"], seed, tab)
    order = gen.host_rng(seed, 4).permutation(q_tids.shape[0])
    n = config["compare"]["requests"]
    qi = order[np.arange(n) % order.size]
    q = (q_tids[qi], q_tw[qi], q_mask[qi])
    ref = Reference(tids, tw, mask, st.vocab)
    return {"queries": q, "reference": ref,
            "ref": ref.topk(*q, config["search"]["k"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    from bench.spec import load_cell
    cell = load_cell(args.workload, ROOT)
    run.use_checkout_cache()
    if run.device_info(cell.chips) is None:
        return 3
    limits = cell.config["limits"]
    for seed in args.seeds:
        numbers = control_numbers(control_sample(cell, seed),
                                  cell.config["search"]["k"])
        failed = [n for n, (bound, lim) in
                  ((n, *limits[n].items()) for n in numbers if n in limits)
                  if not (numbers[n] <= lim if bound == "max"
                          else numbers[n] >= lim)]
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": numbers, "fails": failed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
