"""The control of ``ops/kmeans_step``: the reference's Lloyd step put in
the program's place, in float32 with its products in bfloat16
(``bench/control/_bf16.py``): one precision step below the configuration's
float32.

It runs on the device over X in row blocks staged from the host, from the
input centers of a sampled op of the program, and returns that op's
record, so ``ref/kmeans_step.compare`` reads it as it reads the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.control._bf16 import dot

#: Rows staged per block, under the mmap threshold (bench/gen/_blocks.py).
BLOCK_ROWS = 1 << 17


@functools.partial(jax.jit, static_argnames="k")
def _block(x, c, k):
    c2 = (c * c).sum(1)
    d = (x * x).sum(1, keepdims=True) - 2.0 * dot(x, c.T) + c2
    lab = jnp.argmin(d, axis=1)
    onehot = jax.nn.one_hot(lab, k, dtype=jnp.float32)
    return (dot(onehot.T, x), onehot.sum(0), d.min(1).sum(),
            lab.astype(jnp.int32))


def step(data: dict, config: dict, record: dict):
    """(record, labels) of one Lloyd step from ``record['centers_in']``."""
    X = data["X"]
    k = int(config["k"])
    c_in = np.asarray(record["centers_in"], np.float32)
    c = jnp.asarray(c_in)
    sums = counts = wss = None
    labels = []
    for lo in range(0, X.shape[0], BLOCK_ROWS):
        s, n, w, lab = _block(jnp.asarray(X[lo:lo + BLOCK_ROWS]), c, k)
        sums = s if sums is None else sums + s
        counts = n if counts is None else counts + n
        wss = w if wss is None else wss + w
        labels.append(np.asarray(lab))
    sums, counts = np.asarray(sums), np.asarray(counts)
    centers = np.where(counts[:, None] > 0,
                       sums / np.maximum(counts[:, None], 1.0),
                       c_in).astype(np.float32)
    return ({"centers_in": c_in, "centers": centers, "wss": float(wss)},
            np.concatenate(labels))
