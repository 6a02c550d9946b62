"""Chip benchmark of the coded-optimization system (see README.md)."""
