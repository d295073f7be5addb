"""Chip benchmark of exact motif-transition mining (see BENCHMARK.json)."""
