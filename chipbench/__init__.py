"""Chip benchmark of the IMC design-space explorer (see run.py)."""
