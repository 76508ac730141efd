"""On-chip benchmark of FedGroup rounds (see ``BENCHMARK.json`` and
``PERF.md`` at the root of the checkout). Run a cell with

    python3 bench/run.py --workload <cell> --seed 1 --seconds 10 \
        --trace 0
"""
