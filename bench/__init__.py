"""Chip benchmark of the serving system: data-driven cells, traffic and metrics."""
