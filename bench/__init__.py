"""The benchmark: one cell of BENCHMARK.json per run (see bench/run.py)."""
