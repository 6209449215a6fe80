"""What the stager sustained in the window: the bytes it read from host
RAM over the seconds its staging calls took (``stage_bytes_read`` over
``stage_seconds``, both program counters; the seconds include the host's
layout of each block inside ``device_put``), in GB/s."""


def read(run):
    seconds = run.counters.get("stage_seconds")
    nbytes = run.counters.get("stage_bytes_read")
    if not seconds or nbytes is None:
        return None
    return nbytes / seconds / 1e9
