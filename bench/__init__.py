"""On-chip benchmark of the RST graph service (see BENCHMARK.json, PERF.md)."""
