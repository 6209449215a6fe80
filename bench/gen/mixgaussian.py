"""MixGaussian: rows of a Gaussian mixture, the FlashR paper's dense data.

The law of ``chip_smoke.mixgaussian``: ``components`` components with unit
noise, whose means are drawn once from N(0, ``mean_sd``²), so that they lie
about ``mean_sd`` standard deviations apart in each column.  Each row picks
its component uniformly.
"""
from __future__ import annotations

import numpy as np

from bench.gen import _blocks


def generate(config: dict, seed: int, rows: int) -> dict:
    """``{"X": float32 (rows, cols)}`` for ``seed``."""
    p = int(config["cols"])
    k = int(config["components"])
    means = (_blocks.rng(seed, 0).standard_normal((k, p))
             * float(config["mean_sd"])).astype(np.float32)
    X = np.empty((rows, p), np.float32)

    def block(b, lo, hi):
        g = _blocks.rng(seed, 1, b)
        labels = g.integers(0, k, hi - lo)
        g.standard_normal(out=X[lo:hi], dtype=np.float32)
        X[lo:hi] += np.take(means, labels, axis=0)

    # Blocks of 2^17 rows: the component means gathered for a block take
    # 16 MiB, under the mmap threshold (see _blocks.BLOCK_ROWS).
    _blocks.fill(rows, block, 1 << 17)
    return {"X": X}
