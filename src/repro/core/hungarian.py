"""Offline combinatorial solvers used by gyro-permutation.

- `linear_sum_assignment`: Hungarian assignment. Uses scipy's C
  implementation when available, with a pure-numpy Jonker-Volgenant
  (shortest augmenting path) fallback so the core has no hard scipy
  dependency.
- `balanced_kmeans`: K-means with exact equal-size clusters, solved by
  turning the assignment step into a Hungarian problem over
  (points x cluster-slots) — the clustering used by the OCP phase [4].

Everything here is offline preprocessing (numpy, not jax).
"""
from __future__ import annotations

import numpy as np

try:  # pragma: no cover - exercised implicitly
    from scipy.optimize import linear_sum_assignment as _scipy_lsa
except Exception:  # pragma: no cover
    _scipy_lsa = None


def _lsa_numpy(cost: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Jonker-Volgenant shortest-augmenting-path LAP. cost: (n, n)."""
    cost = np.asarray(cost, dtype=np.float64)
    n = cost.shape[0]
    u = np.zeros(n + 1)
    v = np.zeros(n + 1)
    p = np.full(n + 1, 0, dtype=np.int64)   # p[j] = row matched to column j
    way = np.zeros(n + 1, dtype=np.int64)
    # 1-indexed classic implementation
    for i in range(1, n + 1):
        p[0] = i
        j0 = 0
        minv = np.full(n + 1, np.inf)
        used = np.zeros(n + 1, dtype=bool)
        while True:
            used[j0] = True
            i0 = p[j0]
            delta = np.inf
            j1 = -1
            for j in range(1, n + 1):
                if not used[j]:
                    cur = cost[i0 - 1, j - 1] - u[i0] - v[j]
                    if cur < minv[j]:
                        minv[j] = cur
                        way[j] = j0
                    if minv[j] < delta:
                        delta = minv[j]
                        j1 = j
            for j in range(n + 1):
                if used[j]:
                    u[p[j]] += delta
                    v[j] -= delta
                else:
                    minv[j] -= delta
            j0 = j1
            if p[j0] == 0:
                break
        while True:
            j1 = way[j0]
            p[j0] = p[j1]
            j0 = j1
            if j0 == 0:
                break
    col_of_row = np.zeros(n, dtype=np.int64)
    for j in range(1, n + 1):
        if p[j] > 0:
            col_of_row[p[j] - 1] = j - 1
    rows = np.arange(n)
    return rows, col_of_row


def linear_sum_assignment(cost: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Minimum-cost perfect matching on a square cost matrix."""
    cost = np.asarray(cost)
    if cost.ndim != 2 or cost.shape[0] != cost.shape[1]:
        raise ValueError(f"square cost matrix required, got {cost.shape}")
    if _scipy_lsa is not None:
        r, c = _scipy_lsa(cost)
        return np.asarray(r), np.asarray(c)
    return _lsa_numpy(cost)


def balanced_kmeans(
    points: np.ndarray,
    n_clusters: int,
    rng: np.random.Generator,
    n_iters: int = 8,
) -> np.ndarray:
    """Equal-size K-means. points: (P, d) with P % n_clusters == 0.

    Returns labels (P,) with exactly P / n_clusters points per cluster.
    The balanced assignment step replicates each centroid `capacity` times
    and solves a Hungarian matching of points to centroid slots.
    """
    points = np.asarray(points, dtype=np.float64)
    n_pts = points.shape[0]
    if n_pts % n_clusters != 0:
        raise ValueError(f"{n_pts} points not divisible by {n_clusters} clusters")
    cap = n_pts // n_clusters
    if n_clusters == 1:
        return np.zeros(n_pts, dtype=np.int64)

    # k-means++ style init
    centroids = points[rng.choice(n_pts, size=n_clusters, replace=False)]
    labels = np.zeros(n_pts, dtype=np.int64)
    sq = (points ** 2).sum(-1)
    for _ in range(n_iters):
        # squared distances (P, C)
        d2 = (sq[:, None] - 2.0 * points @ centroids.T
              + (centroids ** 2).sum(-1)[None, :])
        slot_cost = np.repeat(d2, cap, axis=1)  # (P, C*cap)
        _, cols = linear_sum_assignment(slot_cost)
        new_labels = cols // cap
        if np.array_equal(new_labels, labels):
            labels = new_labels
            break
        labels = new_labels
        for c in range(n_clusters):
            centroids[c] = points[labels == c].mean(axis=0)
    return labels
