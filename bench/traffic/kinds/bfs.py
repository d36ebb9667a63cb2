"""Traffic kind ``bfs``: Graph500 kernel 2.

A mix names this kind with ``"kind": "bfs"`` and gives ``num_roots``,
``root_seed``, ``min_degree`` and ``limits.depth_mismatches``.  The roots
are drawn from ``root_seed`` among the vertices of degree at least
``min_degree``; the run's seed orders them, and the cycle is one call per
root.  The window runs whole ``bfs(graph, root, plan=plan)`` calls back to
back, each ending in ``block_until_ready``.  A call's work is the undirected
edges of the component it traversed, and its levels its largest depth plus
one.

Warm-up runs one call from an isolated vertex where the graph has one (a
single level: the same program, as the root is a traced argument), and
otherwise from one more root drawn with the others.
"""
from __future__ import annotations

import numpy as np

from bench.reference import depth_mismatches


def traversed_edges(depth: np.ndarray, degrees: np.ndarray) -> int:
    """Undirected edges of the component a BFS reached (Graph500's count).

    Every edge of a symmetric graph with one end reached has both ends
    reached, so the component's edges are half its vertices' degrees.
    """
    reached = np.asarray(depth) >= 0
    return int(np.asarray(degrees, np.int64)[reached].sum()) // 2


class Traffic:
    def __init__(self, mix: dict, seed: int, degrees: np.ndarray):
        self.mix, self.degrees = mix, degrees
        rng = np.random.default_rng([abs(int(seed)), int(seed < 0)])
        pool = np.flatnonzero(degrees >= int(mix["min_degree"]))
        picked = np.random.default_rng(int(mix["root_seed"])).choice(
            pool, int(mix["num_roots"]) + 1, replace=False)
        self.roots = [int(r) for r in rng.permutation(picked[:-1])]
        isolated = np.flatnonzero(degrees == 0)
        self.warm_root = int(isolated[0] if isolated.size else picked[-1])
        self.cycle = len(self.roots)
        self.answers = []

    def _run(self, graph, plan, root: int):
        from repro.sparse import bfs
        return bfs(graph, root, plan=plan)

    def warm_up(self, graph, plan) -> None:
        self._run(graph, plan, self.warm_root).block_until_ready()

    def call(self, graph, plan) -> None:
        root = self.roots[len(self.answers) % self.cycle]
        depth = self._run(graph, plan, root)
        depth.block_until_ready()
        self.answers.append(depth)

    def collect(self) -> dict:
        """Answers to the host, and the window's work counted from them."""
        self.host = [np.asarray(d) for d in self.answers]
        self.answers = None
        return {"calls": len(self.host),
                "edges": sum(traversed_edges(d, self.degrees)
                             for d in self.host),
                "levels": sum(int(d.max()) + 1 for d in self.host)}

    def check(self, ref) -> tuple[dict, int]:
        """Compare every answer of the window with the reference.

        Returns ``{name: (value, limit)}`` of the numbers compared, and how
        many answers were wrong.
        """
        limit = self.mix["limits"]["depth_mismatches"]
        want = {r: ref.bfs_depths(r) for r in self.roots}
        worst, failed = 0, 0
        for i, depth in enumerate(self.host):
            bad = depth_mismatches(depth, want[self.roots[i % self.cycle]])
            worst = max(worst, bad)
            failed += bad > limit
        return {"depth_mismatches": (worst, limit)}, failed
