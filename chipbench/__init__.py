"""Chip benchmark: seeded traffic, the program's timed path, the plain
reference that decides ``correct``, and the reduction to metrics."""
