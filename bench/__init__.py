"""On-chip benchmark of the served retrieval path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything that belongs to one
deployment, traffic mix or metric is a file of its own, found by name:
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py``.
"""
