"""Chip benchmark of the serving stack; see BENCHMARK.json and PERF.md."""
