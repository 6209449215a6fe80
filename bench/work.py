"""The work each kernel's algorithm needs, as functions of shape.

Operations count the multiply-adds the mathematics asks for (two
operations each), not the passes an implementation spends on them; bytes
count what must cross HBM at least once: each operand read once and each
per-row output written once.  ``least_seconds`` is the roofline: the time
the chip needs at its peaks, bound by whichever of the two is slower.
"""
from __future__ import annotations

import json
import pathlib

F32 = 4
I32 = 4


def kmeans_assign(n: int, p: int, k: int) -> tuple[float, float]:
    """One Lloyd step over n rows of p columns against k centers:
    distances (the n x p x k cross term) and the per-cluster sums
    (one-hot times X); reads X, writes the n labels."""
    flops = 2.0 * n * p * k + 2.0 * n * p * k
    nbytes = float(n) * p * F32 + float(n) * I32
    return flops, nbytes


def peaks(device_kind: str) -> dict:
    """The table's entry for ``device_kind``; an unknown kind is an error."""
    with open(pathlib.Path(__file__).with_name("peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table['devices'])}")
    return table["devices"][device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    pk = peaks(device_kind)
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
