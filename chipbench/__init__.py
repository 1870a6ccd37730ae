"""The chip benchmark: cells, metrics and bounds listed in BENCHMARK.json."""
