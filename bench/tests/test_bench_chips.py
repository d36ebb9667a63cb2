"""A cell on four chips, run through ``run_cell`` on four host devices.

The ``root`` fixture's checkout (``conftest.py``) gains, for each of the
cells below, a twin that asks for ``"chips": 4``.  A child process with four
forced CPU devices runs every cell and its twin, recording the plan each
kind was handed and every answer it collected; this process compares them.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = ["kron-s20.bfs", "urand-s20.bfs", "kron-s20.pagerank"]
CHIPS = 4


def _twin(cell: str) -> str:
    return f"{cell}-{CHIPS}chips"


def _add_twins(root: pathlib.Path) -> None:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    for cell in CELLS:
        spec["workloads"].append({**cells[cell], "name": _twin(cell),
                                  "chips": CHIPS})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if cell in m.get("workloads", ()):
                m["workloads"].append(_twin(cell))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def _child(root: str, out: str) -> None:
    """Runs in the child: every cell and its twin, through ``run_cell``."""
    import jax
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    assert len(jax.devices()) == CHIPS, jax.devices()
    seen = {}
    load_kind = harness.load_kind

    def spying(root, kind):
        base = load_kind(root, kind)

        class Spy(base):
            def warm_up(self, graph, plan):
                seen["plan"] = (type(plan).__name__,
                                getattr(plan, "num_shards", 1))
                super().warm_up(graph, plan)

            def collect(self):
                work = super().collect()
                seen["answers"] = [np.asarray(a) for a in self.host]
                return work
        return Spy

    harness.load_kind = spying
    report, answers = {}, {}
    for cell in CELLS + [_twin(c) for c in CELLS]:
        chips = CHIPS if cell.endswith("chips") else 1
        result = harness.run_cell(
            pathlib.Path(root), cell, seed=2 ** 31 + 7, seconds=0.05,
            trace=False, t_start=time.perf_counter(),
            device={"platform": "cpu", "kind": "cpu", "count": chips})
        report[cell] = {"result": result, "plan": seen["plan"]}
        for i, a in enumerate(seen["answers"]):
            answers[f"{cell}/{i}"] = a
    np.savez(out + ".npz", **answers)
    pathlib.Path(out + ".json").write_text(json.dumps(report))


@pytest.fixture(scope="module")
def runs(root, tmp_path_factory):
    _add_twins(root)
    out = str(tmp_path_factory.mktemp("chips") / "runs")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={CHIPS}"}
    proc = subprocess.run(
        [sys.executable, __file__, str(root), out], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return (json.loads(pathlib.Path(out + ".json").read_text()),
            np.load(out + ".npz"))


@pytest.mark.parametrize("cell", CELLS)
def test_four_chip_cell_is_correct_on_a_sharded_plan(runs, cell):
    report, _ = runs
    one, four = report[cell], report[_twin(cell)]
    assert one["plan"] == ["AdvancePlan", 1]
    assert four["plan"] == ["ShardedAdvancePlan", CHIPS]
    for run in (one, four):
        result = run["result"]
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] >= 1
        assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(four["result"]["metrics"]) == set(one["result"]["metrics"])
    assert four["result"]["device"]["count"] == CHIPS
    assert len(four["result"]["device"]["memory_peak_bytes_per_chip"]) \
        == CHIPS


def _pairs(runs, cell: str):
    """The answers of the window's calls that both the cell and its twin
    made (a call's input follows from its index alone)."""
    _, answers = runs
    calls = min(sum(k.startswith(f"{c}/") for k in answers.files)
                for c in (cell, _twin(cell)))
    assert calls >= 1
    return [(answers[f"{cell}/{i}"], answers[f"{_twin(cell)}/{i}"])
            for i in range(calls)]


@pytest.mark.parametrize("cell", [c for c in CELLS if c.endswith(".bfs")])
def test_four_chip_bfs_depths_are_one_chips_bits(runs, cell):
    for one, four in _pairs(runs, cell):
        np.testing.assert_array_equal(four, one)


def test_four_chip_pagerank_ranks_match_one_chip(runs):
    """The program's sharded PageRank sums the dangling rank per shard and
    then over shards, and its inspector may pick another schedule for a
    shard than for the whole graph: float32 sums in another order, so the
    ranks agree to rounding, not bit for bit."""
    for one, four in _pairs(runs, "kron-s20.pagerank"):
        assert four.dtype == one.dtype == np.float32
        np.testing.assert_allclose(four, one, rtol=1e-6, atol=0)


if __name__ == "__main__":
    _child(*sys.argv[1:3])
