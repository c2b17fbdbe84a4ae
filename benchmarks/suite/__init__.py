"""Chip benchmark of SISSO campaigns: see ``run.py``."""
