"""Benchmark of the Opera simulator: traffic, references, trace reduction.

Run one cell once with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``; `BENCHMARK.json` at the repository root
names the cells, configurations and metrics.
"""
