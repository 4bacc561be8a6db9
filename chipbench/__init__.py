"""Chip benchmark of the serving path (see run.py)."""
