"""Chip benchmark of the served sparse path (see run.py and harness.py)."""
