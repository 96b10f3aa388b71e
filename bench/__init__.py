"""The on-chip benchmark: one command that runs one cell of BENCHMARK.json.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
