"""Chip benchmark of DBG-ordered graph analytics; see ``run.py``."""
