"""The benchmark: one command that runs one cell of BENCHMARK.json."""
