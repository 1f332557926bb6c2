"""The on-chip benchmark: cells of BENCHMARK.json run through the served path."""
