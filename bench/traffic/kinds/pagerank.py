"""Traffic kind ``pagerank``: the Graphalytics PageRank.

A mix names this kind with ``"kind": "pagerank"`` and gives ``damping``,
``num_iters`` and ``limits.rank_rel_err``.  The window runs whole
``pagerank(graph, plan=plan, damping=..., num_iters=...)`` calls back to
back, each ending in ``block_until_ready``; the cycle is one call, and a
call's work is its iterations.
"""
from __future__ import annotations

import numpy as np

from bench.reference import max_relative_error


class Traffic:
    cycle = 1

    def __init__(self, mix: dict, seed: int, degrees: np.ndarray):
        self.mix = mix
        self.answers = []

    def _run(self, graph, plan):
        from repro.sparse import pagerank
        return pagerank(graph, plan=plan, damping=float(self.mix["damping"]),
                        num_iters=int(self.mix["num_iters"]))

    def warm_up(self, graph, plan) -> None:
        self._run(graph, plan).block_until_ready()

    def call(self, graph, plan) -> None:
        ranks = self._run(graph, plan)
        ranks.block_until_ready()
        self.answers.append(ranks)

    def collect(self) -> dict:
        self.host = [np.asarray(r) for r in self.answers]
        self.answers = None
        return {"calls": len(self.host),
                "iterations": len(self.host) * int(self.mix["num_iters"])}

    def check(self, ref) -> tuple[dict, int]:
        """Compare every answer of the window with the reference."""
        limit = self.mix["limits"]["rank_rel_err"]
        want = ref.pagerank(int(self.mix["num_iters"]),
                            float(self.mix["damping"]))
        worst, failed = 0.0, 0
        for ranks in self.host:
            err = max_relative_error(ranks, want)
            worst = max(worst, err)
            failed += not err <= limit
        return {"rank_rel_err": (worst, limit)}, failed
