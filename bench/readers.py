"""What the per-layer and end-to-end readers share.

A reader is ``read(run) -> float | None``; ``run`` is ``run.Run``.  A
reader that finds nothing to read returns None, and the metric is left out
of the result line; a share of a roofline is never made up as 0.
"""
from __future__ import annotations

from bench import work


def per_op(run) -> float:
    """Seconds per op over the whole window: its time over its ops."""
    return run.window_s / len(run.op_seconds)


def counter_share(run, part: str, whole: str):
    """``part`` over ``whole`` of the window's counters, in percent."""
    num, den = run.counters.get(part), run.counters.get(whole)
    if num is None or not den:
        return None
    return 100.0 * num / den


def steps_per_op(run):
    steps = run.counters.get("partition_steps")
    return None if not steps else steps / len(run.op_seconds)


def device_idle(run):
    """Share of the traced window in which no operation ran on the device."""
    tr = run.trace
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def roofline(run, kernel: str, flops: float, nbytes: float):
    """The least time the chip needs for the traced ops' work, at its
    peaks, over the time the kernel's events took, in percent."""
    tr = run.trace
    seconds = tr and tr["kernel_seconds"].get(kernel)
    if not seconds:
        return None
    ops = tr["ops"]
    least = work.least_seconds(flops * ops, nbytes * ops, run.device_kind)
    return 100.0 * least / seconds
