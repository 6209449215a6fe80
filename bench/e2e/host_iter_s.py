"""Seconds per iteration with X streamed from host RAM: the window's
time over its ops.

A metric of its own, so that the spread of the streamed cells does not
widen the bound of the resident cells' ``iter_s``."""
from bench.readers import per_op as read  # noqa: F401
