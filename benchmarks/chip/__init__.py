"""Chip benchmark: the harness, its data and its yardstick (see PERF.md)."""
