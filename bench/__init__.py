"""On-chip benchmark of the lane engine and the analytics service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``; everything a cell needs is found by
name under this directory (see ``bench/run.py``).
"""
