"""Chip benchmark of core maintenance: one cell per run, on a TPU.

``python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>``; the cells, metrics and bounds are in the
repository's ``BENCHMARK.json``.
"""
