"""The chip benchmark of fugue_tpu (see BENCHMARK.json and PERF.md)."""
