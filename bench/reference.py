"""Plain host reference for the benchmark's answers: SciPy and NumPy.

Shares no code with the system under test.  BFS depths come from SciPy's
``csgraph`` (unit-weight shortest paths), PageRank from a float64 NumPy
power iteration with the same damping, dangling redistribution and
iteration count as the traffic asks for.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra


class Reference:
    """Answers for one symmetric CSR graph held on the host."""

    def __init__(self, offsets: np.ndarray, cols: np.ndarray):
        self.n = offsets.size - 1
        self.pattern = sp.csr_matrix(
            (np.ones(cols.size, np.float64), cols, offsets),
            shape=(self.n, self.n))
        self.out_degree = np.diff(offsets)

    def bfs_depths(self, root: int) -> np.ndarray:
        """Hop count from ``root`` to every vertex, -1 where unreached."""
        hops = dijkstra(self.pattern, indices=int(root), unweighted=True)
        return np.where(np.isfinite(hops), hops, -1).astype(np.int64)

    def pagerank(self, iters: int, damping: float) -> np.ndarray:
        """Float64 power iteration from the uniform vector.

        Dangling vertices (no out-edges) spread their rank uniformly.
        """
        n = self.n
        deg = self.out_degree.astype(np.float64)
        at = self.pattern.T.tocsr()
        pr = np.full(n, 1.0 / n)
        for _ in range(iters):
            share = np.where(deg > 0, pr / np.maximum(deg, 1.0), 0.0)
            dangling = pr[deg == 0].sum()
            pr = (1.0 - damping) / n + damping * (at @ share + dangling / n)
        return pr


def depth_mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Number of vertices whose BFS depth differs from the reference."""
    return int(np.count_nonzero(np.asarray(got, np.int64) != want))


def max_relative_error(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want| / |want|`` over all vertices (inf on NaN)."""
    got = np.asarray(got, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want) / np.abs(want), initial=0.0))
