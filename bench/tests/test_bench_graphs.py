"""Generators, reference and work counts of the benchmark, on the CPU."""
from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import graphs  # noqa: E402
from bench.harness import load_kind, load_reader, window_done  # noqa: E402
from bench.reference import (Reference, depth_mismatches,  # noqa: E402
                             max_relative_error)


def _cfg(generator: str, scale: int) -> dict:
    return {"generator": generator, "scale": scale, "edge_factor": 16,
            "initiator": [0.57, 0.19, 0.19, 0.05]}


def _host(cfg: dict, seed: int):
    offsets, cols, salt = graphs.generate(cfg, seed)
    return offsets, cols, graphs.edge_weights(offsets, cols, salt)


@pytest.mark.parametrize("generator", graphs.GENERATORS)
def test_graph_is_symmetric_simple_and_sorted(generator):
    offsets, cols, weights = _host(_cfg(generator, 9), 3)
    n = offsets.size - 1
    assert n == 512 and offsets[0] == 0 and offsets[-1] == cols.size
    rows = np.repeat(np.arange(n), np.diff(offsets))
    assert not np.any(rows == cols), "self-loop"
    key = rows.astype(np.int64) * n + cols
    assert np.all(np.diff(key) > 0), "duplicate edge or unsorted row"
    back = cols.astype(np.int64) * n + rows
    order = np.argsort(back)
    np.testing.assert_array_equal(back[order], key)   # every edge reversed
    np.testing.assert_array_equal(weights[order], weights)  # one weight
    assert np.all((weights >= 0) & (weights < 1))


@pytest.mark.parametrize("generator", graphs.GENERATORS)
def test_same_seed_same_graph_other_seed_other_graph(generator):
    cfg = _cfg(generator, 8)
    a, b, c = _host(cfg, 2 ** 31 + 11), _host(cfg, 2 ** 31 + 11), \
        _host(cfg, 12)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert a[1].size != c[1].size or not np.array_equal(a[1], c[1])


def test_kronecker_is_skewed_urand_is_not():
    kron = np.diff(_host(_cfg("kronecker", 10), 5)[0])
    urand = np.diff(_host(_cfg("urand", 10), 5)[0])
    assert kron.max() > 5 * urand.max()
    assert urand.max() < 4 * urand.mean()


def test_stated_edge_count_is_checked():
    cfg = _cfg("urand", 8)
    edges = _host(cfg, 4)[1].size
    graphs.generate({**cfg, "undirected_edges": edges // 2}, 4)
    with pytest.raises(ValueError, match="distinct edges"):
        graphs.generate({**cfg, "undirected_edges": edges // 2 + 1}, 4)


def _numpy_weights(offsets, cols, salt):
    """The weights' hash in NumPy's uint32 arithmetic, edge by edge."""
    def mix(x):
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x7FEB352D)
        x = x ^ (x >> np.uint32(15))
        x = x * np.uint32(0x846CA68B)
        return x ^ (x >> np.uint32(16))
    rows = np.repeat(np.arange(offsets.size - 1), np.diff(offsets))
    lo = np.minimum(rows, cols).astype(np.uint32)
    hi = np.maximum(rows, cols).astype(np.uint32)
    h = mix(lo ^ mix(hi ^ np.uint32(salt)))
    return (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


@pytest.mark.parametrize("generator", graphs.GENERATORS)
def test_blocked_weights_are_the_whole_arrays_bits(generator, monkeypatch):
    offsets, cols, salt = graphs.generate(_cfg(generator, 9), 2 ** 31 + 3)
    e = cols.size
    assert e < graphs.WEIGHT_BLOCK
    whole = graphs.edge_weights(offsets, cols, salt)        # one block
    monkeypatch.setattr(graphs, "WEIGHT_BLOCK", 1000)
    assert e % 1000 and e > 3 * 1000
    blocked = graphs.edge_weights(offsets, cols, salt)
    assert isinstance(blocked, np.ndarray) and blocked.dtype == np.float32
    np.testing.assert_array_equal(blocked, whole)
    np.testing.assert_array_equal(whole, _numpy_weights(offsets, cols, salt))
    on_device = graphs.edge_weights(jnp.asarray(offsets), jnp.asarray(cols),
                                    salt)
    assert isinstance(on_device, jax.Array)
    np.testing.assert_array_equal(np.asarray(on_device), whole)


def _path_plus_triangle():
    """Vertices 0-1-2-3 a path, 4-5-6 a triangle, 7 isolated."""
    edges = [(0, 1), (1, 2), (2, 3), (4, 5), (5, 6), (4, 6)]
    n = 8
    rows = [a for a, b in edges] + [b for a, b in edges]
    cols = [b for a, b in edges] + [a for a, b in edges]
    order = np.lexsort((cols, rows))
    rows, cols = np.asarray(rows)[order], np.asarray(cols)[order]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=offsets[1:])
    return offsets, cols


def test_reference_bfs_and_teps_edge_count_by_hand():
    offsets, cols = _path_plus_triangle()
    ref = Reference(offsets, cols)
    depth = ref.bfs_depths(1)
    np.testing.assert_array_equal(depth, [1, 0, 1, 2, -1, -1, -1, -1])
    degrees = np.diff(offsets)
    bfs = load_kind(ROOT, "bfs")
    for root, edges, levels in ((1, 3, 3), (6, 3, 2), (7, 0, 1)):
        traffic = bfs({"num_roots": 1, "root_seed": 0, "min_degree": 1},
                      0, degrees)
        traffic.answers = [ref.bfs_depths(root)] * 2
        assert traffic.collect() == {"calls": 2, "edges": 2 * edges,
                                     "levels": 2 * levels}
    assert depth_mismatches(depth, ref.bfs_depths(0)) == 4


def test_reference_pagerank_by_hand():
    offsets, cols = _path_plus_triangle()
    pr = Reference(offsets, cols).pagerank(1, 0.85)
    n, d = 8, 0.85
    # one step from 1/8: vertex 1 has neighbours 0 (deg 1) and 2 (deg 2);
    # vertex 7 is dangling and spreads its 1/8 over all
    want1 = (1 - d) / n + d * (1 / 8 / 1 + 1 / 8 / 2 + 1 / 8 / n)
    assert pr[1] == pytest.approx(want1, rel=1e-12)
    assert pr.sum() == pytest.approx(1.0, rel=1e-12)
    assert max_relative_error(pr * (1 + 1e-3), pr) == pytest.approx(1e-3)
    assert max_relative_error(pr * np.nan, pr) == float("inf")


def test_pagerank_bytes_by_hand():
    reader = load_reader(ROOT, "hbm_share.pr")
    assert reader.pagerank_pull_bytes(1000, 20000) == 8 * 20000 + 16 * 1000


@pytest.mark.parametrize("pace,seconds,cycles", [
    (10.0, 25, 2),     # two cycles end at 20 s, a third would at 30 s
    (12.5, 25, 2),     # ends exactly at the asked length
    (30.0, 25, 1),     # one cycle, even though it overruns
    (7.2, 25, 3),
])
def test_window_ends_on_the_last_whole_cycle_within_the_length(
        pace, seconds, cycles):
    done = [window_done(pace * k, k, seconds) for k in range(1, 10)]
    assert done.index(True) + 1 == cycles


def test_bfs_roots_are_fixed_ordered_by_seed_and_warm_up_elsewhere():
    bfs = load_kind(ROOT, "bfs")
    mix = {"num_roots": 3, "root_seed": 1, "min_degree": 1}
    degrees = np.array([0, 3, 1, 0, 2, 5, 1, 1, 4, 2])
    a, b = bfs(mix, 2 ** 31 + 9, degrees), bfs(mix, 2 ** 31 + 9, degrees)
    others = [bfs(mix, s, degrees) for s in range(8)]
    assert a.roots == b.roots and a.cycle == 3
    assert all(sorted(o.roots) == sorted(a.roots) for o in others)
    assert len({tuple(o.roots) for o in others}) > 1
    assert all(degrees[r] >= 1 for r in a.roots)
    assert a.warm_root == 0             # the first isolated vertex
    dense = bfs(mix, 4, degrees + 1)    # none isolated: one more root
    assert dense.warm_root not in dense.roots
