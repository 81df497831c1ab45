"""Chip benchmark of the RTL emulator: one harness, cells defined by data."""
