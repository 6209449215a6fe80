"""One Lloyd iteration through the library's own step.

``repro.algorithms.kmeans.kmeans_iteration(X, centers)``: one fused pass
over X (distances, argmin, per-cluster sums and counts, objective) and the
new centers on the host, as ``fm.kmeans`` runs each iteration.  The window
repeats it with the centers carried from op to op, starting from the
library's seeded k-means++ centers.
"""
from __future__ import annotations

from repro.algorithms.kmeans import _init_centers, kmeans_iteration


class Op:
    def __init__(self, mats: dict, config: dict, seed: int):
        self.X = mats["X"]
        self.centers = _init_centers(self.X, int(config["k"]), seed)

    def step(self):
        """Run one iteration; returns (record, labels): the small outputs
        and the per-row labels, which are kept only for sampled ops."""
        c_in = self.centers
        centers, _, wss, labels = kmeans_iteration(self.X, c_in)
        self.centers = centers
        return ({"centers_in": c_in, "centers": centers, "wss": float(wss)},
                labels)

    def close(self):
        self.X = None
