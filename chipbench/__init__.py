"""Chip benchmark of the GLM train, score and live paths (see PERF.md)."""
