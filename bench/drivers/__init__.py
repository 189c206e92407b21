"""Found by name from BENCHMARK.json (see bench/run.py)."""
