"""On-chip benchmark of the malleable-scheduling simulator.

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` at the repository root
declares the cells, and everything a cell needs is found by name under
``bench/configs``, ``bench/traffic``, ``bench/metrics`` and
``bench/limits``.
"""
