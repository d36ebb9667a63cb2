"""The controls, at a size a test run holds: the check rejects each one."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import control  # noqa: E402


@pytest.mark.parametrize("cell", ["kron-s20.bfs", "urand-s20.bfs",
                                  "kron-s20.pagerank", "urand-s20.pagerank"])
def test_control_fails_the_check(root, cell):
    for line in control.readings(root, cell, [3, 2 ** 31 + 5]):
        (name, c), = line["compared"].items()
        assert c["value"] > c["limit"], (cell, line)
        assert line["failed"] >= 1


def test_float32_control_passes_the_pagerank_check(root):
    import jax.numpy as jnp
    from bench import graphs, harness
    from bench.reference import Reference, max_relative_error

    cell = harness.find_cell(root, "kron-s20.pagerank")
    offsets, cols, _ = graphs.generate(cell.config, 1)
    want = Reference(offsets, cols).pagerank(20, 0.85)
    got = control.pagerank_control(offsets, cols, 20, 0.85, jnp.float32)
    limit = cell.mix["limits"]["rank_rel_err"]
    assert max_relative_error(got, want) < limit
