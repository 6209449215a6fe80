"""Partition steps the executor ran per op in the window."""
from bench.readers import steps_per_op as read  # noqa: F401
