"""Chip benchmark for the serving stack (see BENCHMARK.json at the repo root)."""
