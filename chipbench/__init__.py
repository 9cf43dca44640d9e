"""Chip benchmark of the geostatistics system: see run.py."""
