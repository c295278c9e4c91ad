"""Chip benchmark of the FPISA train step (see BENCHMARK.json and PERF.md)."""
