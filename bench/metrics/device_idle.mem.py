"""Share of the traced window in which the device ran nothing."""
from bench.readers import device_idle as read  # noqa: F401
