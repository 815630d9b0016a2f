"""On-chip benchmark of the AQP server: see BENCHMARK.json and PERF.md."""
