"""Chip benchmark of SOLAR-fed surrogate training (see BENCHMARK.json and PERF.md)."""
