"""The generators and the float64 references, at tiny sizes on the CPU."""
import json

import numpy as np
import pytest

from bench import spec
from bench.gen import _blocks, mixgaussian
from bench.ref import kmeans_step

SEED = 2**31 + 2**30 + 12345        # larger than 32 signed bits hold


def _config(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_generator_is_deterministic_per_seed(monkeypatch):
    cfg = _config("mixgauss32-k10")
    monkeypatch.setattr(_blocks, "BLOCK_ROWS", 1 << 10)
    a = mixgaussian.generate(cfg, SEED, 5000)
    b = mixgaussian.generate(cfg, SEED, 5000)
    c = mixgaussian.generate(cfg, SEED + 1, 5000)
    assert a["X"].dtype == np.float32 and a["X"].shape == (5000, cfg["cols"])
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["X"], c["X"])


def test_generation_does_not_depend_on_the_threads(monkeypatch):
    cfg = _config("mixgauss32-k10")
    monkeypatch.setattr(_blocks, "BLOCK_ROWS", 1 << 9)
    many = mixgaussian.generate(cfg, 7, 3000)["X"]
    real = _blocks.map_blocks
    monkeypatch.setattr(_blocks, "map_blocks",
                        lambda n, fn, br=None: real(n, fn, br, workers=1))
    np.testing.assert_array_equal(many, mixgaussian.generate(cfg, 7, 3000)["X"])


def _plain_lloyd(X, c):
    d = ((X[:, None, :].astype(np.float64) - c[None]) ** 2).sum(-1)
    lab = d.argmin(1)
    k = c.shape[0]
    counts = np.bincount(lab, minlength=k).astype(np.float64)
    sums = np.stack([X[lab == j].astype(np.float64).sum(0) for j in range(k)])
    new = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None],
                   c)
    return new, counts, d.min(1).sum(), lab, d


def test_lloyd_reference_matches_plain_numpy(monkeypatch):
    monkeypatch.setattr(_blocks, "BLOCK_ROWS", 1 << 8)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((2000, 5)).astype(np.float32)
    c = X[:4].astype(np.float64) + 0.1
    new, counts, wss, lab, d = _plain_lloyd(X, c)
    got = kmeans_step.lloyd_step(X, c, lab)
    np.testing.assert_allclose(got["centers"], new, rtol=1e-12)
    np.testing.assert_array_equal(got["counts"], counts)
    assert got["wss"] == pytest.approx(wss, rel=1e-12)
    assert got["label_gap"] == 0.0
    other = lab.copy()
    other[5] = (lab[5] + 1) % 4
    gap = kmeans_step.lloyd_step(X, c, other)["label_gap"]
    assert gap == pytest.approx(d[5, other[5]] - d[5, lab[5]], rel=1e-9)
