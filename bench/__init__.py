"""Chip benchmark for SPDC: one command runs one cell once (see README.md)."""
