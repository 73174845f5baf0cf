"""On-chip benchmark of the DS2 train-and-serve paths (see PERF.md).

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1`
runs one cell of `BENCHMARK.json` once and prints one JSON result line.
"""
