"""Seconds per iteration: the window's time over its ops."""
from bench.readers import per_op as read  # noqa: F401
