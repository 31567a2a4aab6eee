"""Chip benchmark of the served detection path (see README.md)."""
