"""Plain float64 numpy references of the algorithm suite's semantics.

Each function recomputes what an engine algorithm computes, independently
of the engine, in float64 on the host.  ``X`` is anything with ``shape``
and row slicing (an ndarray, an ``np.memmap``, a lazily densified one-hot
design), read in row chunks so a matrix of 2^24 rows never exists whole in
float64.  The tests and ``chip_smoke.py`` compare against these.
"""
from __future__ import annotations

import numpy as np

#: Rows per host chunk: 2^20 rows x 32 columns of float64 is 256 MiB.
CHUNK_ROWS = 1 << 20


def _chunks(X, chunk_rows: int = CHUNK_ROWS):
    n = X.shape[0]
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        yield lo, hi, np.asarray(X[lo:hi], np.float64)


def column_summary(X) -> dict:
    """Column min, max, sum, L1 norm, sum of squares and non-zero count."""
    p = X.shape[1]
    out = {"min": np.full(p, np.inf), "max": np.full(p, -np.inf),
           "sum": np.zeros(p), "l1": np.zeros(p), "sumsq": np.zeros(p),
           "nnz": np.zeros(p, np.int64)}
    for _, _, x in _chunks(X):
        out["min"] = np.minimum(out["min"], x.min(0))
        out["max"] = np.maximum(out["max"], x.max(0))
        out["sum"] += x.sum(0)
        out["l1"] += np.abs(x).sum(0)
        out["sumsq"] += (x * x).sum(0)
        out["nnz"] += (x != 0).sum(0)
    return out


def gram(X) -> np.ndarray:
    """XᵀX."""
    p = X.shape[1]
    g = np.zeros((p, p))
    for _, _, x in _chunks(X):
        g += x.T @ x
    return g


def lloyd(X, centers, *, max_iter: int, tol: float = 1e-6):
    """Lloyd's k-means from the given centers with `algorithms.kmeans`'s
    rules: empty clusters keep their center, and the loop stops once the
    objective falls by at most ``tol`` relative.  Returns (centers, wss,
    iterations)."""
    c = np.asarray(centers, np.float64)
    k = c.shape[0]
    prev = np.inf
    wss = np.inf
    it = 0
    for it in range(1, max_iter + 1):
        sums = np.zeros_like(c)
        counts = np.zeros(k)
        wss = 0.0
        c2 = (c * c).sum(1)
        for _, _, x in _chunks(X):
            d = (x * x).sum(1, keepdims=True) - 2.0 * x @ c.T + c2
            lab = d.argmin(1)
            rows = np.arange(len(lab))
            wss += float(d[rows, lab].sum())
            onehot = np.zeros((len(lab), k))
            onehot[rows, lab] = 1.0
            counts += onehot.sum(0)
            sums += onehot.T @ x
        c = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts[:, None], 1.0), c)
        if np.isfinite(prev) and prev - wss <= tol * max(prev, 1.0):
            break
        prev = wss
    return c, wss, it


def irls_logistic(X, y, *, max_iter: int = 25, tol: float = 1e-8,
                  ridge: float = 0.0, w_eps: float = 1e-6) -> np.ndarray:
    """Logistic regression by IRLS with `algorithms.glm`'s weight floor,
    ridge term and stopping rule (the log-likelihood at the coefficients
    entering an iteration, compared with the previous iteration's)."""
    p = X.shape[1]
    y = np.asarray(y, np.float64).reshape(-1)
    beta = np.zeros(p)
    prev = -np.inf
    for _ in range(max_iter):
        a = ridge * np.eye(p)
        b = np.zeros(p)
        ll = 0.0
        for lo, hi, x in _chunks(X):
            eta = x @ beta
            mu = 1.0 / (1.0 + np.exp(-eta))
            w = mu * (1 - mu) + w_eps
            z = eta + (y[lo:hi] - mu) / w
            a += x.T @ (x * w[:, None])
            b += x.T @ (w * z)
            ll += float(np.sum(y[lo:hi] * eta - np.logaddexp(0.0, eta)))
        beta = np.linalg.solve(a, b)
        if np.isfinite(prev) and abs(ll - prev) <= tol * (abs(prev) + 1.0):
            break
        prev = ll
    return beta
