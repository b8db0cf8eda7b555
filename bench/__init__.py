"""Chip benchmark of the graph engine: one cell per run (see ``run.py``)."""
