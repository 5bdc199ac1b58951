"""Chip benchmark of the LUT low-bit serving path (see PERF.md)."""
