"""Chip benchmark of the DiP serving engine and trainer (see PERF.md)."""
