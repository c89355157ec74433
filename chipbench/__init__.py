"""The benchmark of cells: the yardstick every later change is measured
by (``BENCHMARK.json`` at the repository root names the cells; ``PERF.md``
says why each exists).  Run one cell once with ``python3 -m
chipbench.run``."""
