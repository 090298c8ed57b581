"""On-chip benchmark of the MST system: cells, traffic, references, metrics.

Run one cell with ``python bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout; the cells are
listed in ``BENCHMARK.json``.
"""
