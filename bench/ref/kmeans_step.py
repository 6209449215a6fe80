"""Plain float64 reference of one Lloyd step, and the numbers compared.

For each sampled op the reference recomputes, from the same input centers
and over the same X, the per-cluster sums and counts, the objective and the
new centers (empty clusters keep their center), in float64 and row blocks,
importing nothing of the program.  The numbers compared, for each sampled
op:

    centers_err  max |centers - ref| / max |ref centers|
    wss_err      |wss - ref| / ref
    label_gap    the widest gap, over rows, by which the reference's
                 squared distance to the label the program chose exceeds
                 the reference's least one (0 where they agree)
"""
from __future__ import annotations

import numpy as np

from bench.gen import _blocks

#: Rows per reference block: each float64 temporary stays under glibc's
#: largest mmap threshold, as ``bench/gen/_blocks.BLOCK_ROWS`` explains.
BLOCK_ROWS = 1 << 16


def lloyd_step(X: np.ndarray, centers: np.ndarray,
               labels: np.ndarray) -> dict:
    """One Lloyd step from ``centers`` over ``X`` in float64, and the
    widest gap of the program's ``labels``."""
    c = np.asarray(centers, np.float64)
    k = c.shape[0]
    c2 = (c * c).sum(1)

    def block(b, lo, hi):
        x = X[lo:hi].astype(np.float64)
        d = (x * x).sum(1, keepdims=True) - 2.0 * (x @ c.T) + c2
        lab = d.argmin(1)
        rows = np.arange(hi - lo)
        best = d[rows, lab]
        onehot = np.zeros((hi - lo, k))
        onehot[rows, lab] = 1.0
        got = labels[lo:hi].astype(np.int64)
        if got.min() < 0 or got.max() >= k:
            return onehot.T @ x, onehot.sum(0), best.sum(), np.inf
        gap = float((d[rows, got] - best).max())
        return onehot.T @ x, onehot.sum(0), best.sum(), gap

    parts = _blocks.map_blocks(X.shape[0], block, BLOCK_ROWS)
    sums = sum(p[0] for p in parts)
    counts = sum(p[1] for p in parts)
    wss = float(sum(p[2] for p in parts))
    new = np.where(counts[:, None] > 0,
                   sums / np.maximum(counts[:, None], 1.0), c)
    return {"sums": sums, "counts": counts, "wss": wss, "centers": new,
            "label_gap": max(p[3] for p in parts)}


def _labels_np(labels):
    """The program's labels as a flat host array (they may be on the
    device, in a matrix handle)."""
    if hasattr(labels, "m"):
        from repro.core import fm
        labels = fm.as_np(labels)
    return np.asarray(labels).reshape(-1)


def compare(data: dict, config: dict, samples: list) -> list:
    """Each number's reading for each sampled op: ``samples`` is a
    list of (record, labels) from ``ops/kmeans_step.Op.step``."""
    X = data["X"]
    keys = ("centers_err", "wss_err", "label_gap")
    readings = []
    for rec, labels in samples:
        labels = _labels_np(labels)
        got = np.asarray(rec["centers"], np.float64)
        if labels.shape != (X.shape[0],) or got.shape != (int(config["k"]),
                                                         X.shape[1]):
            readings.append({key: np.inf for key in keys})
            continue
        ref = lloyd_step(X, rec["centers_in"], labels)
        vals = {
            "centers_err": float(np.abs(got - ref["centers"]).max()
                                 / np.abs(ref["centers"]).max()),
            "wss_err": abs(rec["wss"] - ref["wss"]) / ref["wss"],
            "label_gap": ref["label_gap"],
        }
        readings.append({k: v if np.isfinite(v) else np.inf
                         for k, v in vals.items()})
    return readings
