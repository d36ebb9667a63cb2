"""Shared fixture of the benchmark's CPU tests: a small checkout."""
from __future__ import annotations

import json
import pathlib
import shutil

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


#: A traffic kind that no committed mix uses, added as a file alone: the
#: size of the component of the vertex of largest degree.
REACH_KIND = '''
import numpy as np


class Traffic:
    cycle = 1

    def __init__(self, mix, seed, degrees):
        self.mix, self.root = mix, int(np.argmax(degrees))
        self.answers = []

    def warm_up(self, graph, plan):
        self.call(graph, plan)
        self.answers.clear()

    def call(self, graph, plan):
        from repro.sparse import bfs
        reached = (bfs(graph, self.root, plan=plan) >= 0).sum()
        reached.block_until_ready()
        self.answers.append(reached)

    def collect(self):
        self.host = [int(a) for a in self.answers]
        return {"calls": len(self.host)}

    def check(self, ref):
        want = int((ref.bfs_depths(self.root) >= 0).sum())
        bad = sum(a != want for a in self.host)
        return {"reached_mismatches": (bad, self.mix["limit"])}, bad
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the benchmark cut to scale 8, plus one new mix
    added as a file (``bfs-4roots``) and one new kind with its mix
    (``reach``).  Plans are scored with an autotune cache of its own."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg.pop("undirected_edges", None)
        cfg["scale"] = 8
        cfg["plan"]["num_blocks"] = 8
        path.write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/bfs.json").read_text())
    mix["num_roots"] = 4
    (root / "bench/traffic/bfs-4roots.json").write_text(json.dumps(mix))
    (root / "bench/traffic/kinds/reach.py").write_text(REACH_KIND)
    (root / "bench/traffic/reach.json").write_text(
        json.dumps({"kind": "reach", "limit": 0}))
    for traffic in ("bfs-4roots", "reach"):
        spec["workloads"].append({"name": f"kron-s20.{traffic}",
                                  "config": "kron-s20", "traffic": traffic,
                                  "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "kron-s20.bfs" in m.get("workloads", ()):
            m["workloads"].append("kron-s20.bfs-4roots")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    peaks = json.loads((root / "bench/peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench/peaks.json").write_text(json.dumps(peaks))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_AUTOTUNE_CACHE", str(root / "autotune.json"))
        yield root
    jax.clear_caches()
