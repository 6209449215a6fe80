"""Putting a generated matrix on the tier a cell names.

    hbm   device memory (``fm.conv_R2FM``): the kernel and the per-iteration
          dispatch do all the work; storage is bypassed.
    host  host RAM (``fm.conv_R2FM(host=True)``, no copy): staged to the
          chip partition by partition by the prefetcher once per pass.
"""
from __future__ import annotations

import sys
import time

import numpy as np

_T0 = time.perf_counter()


def log(*parts) -> None:
    print(f"[bench] +{time.perf_counter() - _T0:.3f}s", *parts,
          f"(host rss {host_rss()})", file=sys.stderr, flush=True)


def host_rss() -> str:
    """This process's resident host memory now and at its peak."""
    fields = {}
    with open("/proc/self/status") as f:
        for line in f:
            key, _, value = line.partition(":")
            fields[key] = value.strip()
    return f"{fields.get('VmRSS', '?')}, peak {fields.get('VmHWM', '?')}"


def mem_total_bytes() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("no MemTotal in /proc/meminfo")


def to_device(arr, block_rows: int = 1 << 17, in_flight: int = 4):
    """A tall (n, p) ``arr`` on the device, copied in blocks of rows.

    The device keeps a matrix this narrow column-major, as its transpose
    (p, n).  One ``device_put`` of the whole matrix lays it out on a single
    host thread (41.9 s for 8 GiB on a v5e's host); so each block goes over
    already transposed, a few at once, is written in place into a (p, n)
    buffer, and the buffer's transpose, which costs nothing, is the
    matrix.  Blocks stay under the mmap threshold, as
    ``bench/gen/_blocks.BLOCK_ROWS`` explains."""
    import collections

    import jax
    import jax.numpy as jnp

    write = jax.jit(lambda buf, blk, lo: jax.lax.dynamic_update_slice(
        buf, blk, (0, lo)), donate_argnums=0)
    buf = jnp.zeros(arr.shape[::-1], arr.dtype)
    pending = collections.deque()
    for lo in range(0, arr.shape[0], block_rows):
        blk = np.ascontiguousarray(arr[lo:lo + block_rows].T)
        pending.append((lo, jax.device_put(blk)))
        while len(pending) >= in_flight or (
                pending and lo + block_rows >= arr.shape[0]):
            start, blk = pending.popleft()
            buf = write(buf, blk, start)
    return jax.jit(lambda a: a.T, donate_argnums=0)(buf).block_until_ready()


def place(tier: str, arrays: dict) -> dict:
    """The run's matrices as FlashR matrices on ``tier``."""
    from repro.core import fm
    log(f"tier {tier}; host MemTotal {mem_total_bytes()} bytes")
    if tier == "hbm":
        return {k: fm.conv_R2FM(to_device(v)) for k, v in arrays.items()}
    if tier == "host":
        return {k: fm.conv_R2FM(v, host=True) for k, v in arrays.items()}
    raise ValueError(f"unknown tier {tier!r}: hbm or host")
