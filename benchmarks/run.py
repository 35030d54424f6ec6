"""Benchmark driver: one module per paper table/figure + the roofline
report. ``python -m benchmarks.run [names...]`` — each module prints its
CSV table and asserts the paper's qualitative claims (a failed claim is a
regression, not a soft warning).

Every run also updates ``BENCH_retrieval.json`` (machine-readable perf
trajectory): per-suite status, wall-clock, and whatever metrics dict the
suite's ``run()`` returns. Partial runs merge into the existing file so
the trajectory accumulates instead of resetting.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

BENCH_JSON = os.environ.get("REPRO_BENCH_JSON", "BENCH_retrieval.json")

SUITES = [
    ("fig2_bound_tightness", "Fig 2: cluster bound tightness vs m"),
    ("fig3_fig6_recall_latency", "Fig 3/6: recall-latency over mu, m, n"),
    ("table2_clustering", "Table 2: clustering representations"),
    ("table3_segmentation", "Table 3: segmentation methods"),
    ("table4_baselines", "Table 4: ASC vs MaxScore/Anytime/Anytime*"),
    ("table5_models", "Table 5: weight regimes"),
    ("table6_zeroshot", "Table 6: zero-shot collections"),
    ("table7_budget", "Table 7: budgets + static pruning"),
    ("lifecycle_churn", "Lifecycle: churn vs full rebuild"),
    ("serve_throughput", "Serving: batched vs per-query engine qps"),
    ("roofline", "Roofline from dry-run artifacts"),
]


def _emit_json(entries: dict) -> None:
    """Merge this run's suite entries into the trajectory file."""
    doc = {"suites": {}}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                doc = json.load(f)
        except (json.JSONDecodeError, OSError):
            doc = {"suites": {}}
    doc.setdefault("suites", {}).update(entries)
    doc["updated"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(BENCH_JSON, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"[bench] wrote {BENCH_JSON} ({len(entries)} suite(s) updated)")


def main() -> int:
    names = sys.argv[1:] or [s for s, _ in SUITES]
    known = {s for s, _ in SUITES}
    unknown = [n for n in names if n not in known]
    if unknown:
        print(f"[bench] unknown suite(s): {', '.join(unknown)}; "
              f"known: {', '.join(sorted(known))}", file=sys.stderr)
        return 2
    from repro.utils import init_compile_cache
    init_compile_cache()
    failed = []
    entries: dict = {}
    t_all = time.perf_counter()
    for name, desc in SUITES:
        if name not in names:
            continue
        print(f"\n{'=' * 70}\n[bench] {name}: {desc}\n{'=' * 70}",
              flush=True)
        t0 = time.perf_counter()
        entry = {"ok": False, "seconds": None, "desc": desc}
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            metrics = mod.run()
            entry["ok"] = True
            if isinstance(metrics, dict):
                entry["metrics"] = metrics
            print(f"[bench] {name} OK in "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"[bench] {name} FAILED", flush=True)
        entry["seconds"] = round(time.perf_counter() - t0, 2)
        entries[name] = entry
    _emit_json(entries)
    print(f"\n[bench] total {time.perf_counter() - t_all:.1f}s; "
          f"{'FAILED: ' + ', '.join(failed) if failed else 'all OK'}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
