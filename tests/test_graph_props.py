"""Property tests for the graph advance subsystem (requires hypothesis).

For arbitrary random digraphs — including isolated vertices, self-loops and
zero-degree tails, which the generator produces naturally — the balanced
advance and the traversals built on it must satisfy the structural laws of
frontier computation:

* **exact-once edge coverage** — a full-frontier sum-advance of unit edge
  values returns every vertex's in-degree, bit for bit, on both execution
  paths (any dropped or duplicated edge atom shows up as a count mismatch);
* **monotone frontier convergence** — BFS frontiers are disjoint level
  sets; labels only ever move from unreached (-1) to a final depth, and the
  loop terminates in at most |V| iterations;
* **SSSP triangle inequality** — for every edge (u, v, w) with reached u:
  ``dist[v] <= dist[u] + w``, and every finite ``dist[v]`` is realised by
  at least one in-edge (tightness at v's predecessor) or v is the source;
* **direction equivalence** — the push-direction advance scatters the same
  candidate multiset the pull direction reduces, so a direction-optimizing
  BFS (measured-density push/pull switching, any threshold) visits the same
  vertex set at the same depths as a pull-only BFS, and full-frontier push
  counts in-degrees exactly once.
"""
import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")  # optional dev dep: skip, don't error
from hypothesis import given, settings, strategies as st

from repro.core import Schedule
from repro.sparse import (CSR, Graph, advance, advance_push, bfs,
                          build_advance, delta_stepping, sssp)
from _conformance import (assert_bitwise_equal, np_bfs, np_delta_stepping,
                          np_sssp)

SCHEDULES = [Schedule.CHUNKED, Schedule.ADAPTIVE, Schedule.MERGE_PATH,
             Schedule.NONZERO_SPLIT, Schedule.THREAD_MAPPED,
             Schedule.GROUP_MAPPED]


def random_digraph(V: int, density: float, seed: int) -> np.ndarray:
    """Dense weight matrix; integer weights; self-loops kept at ~10%."""
    rng = np.random.default_rng(seed)
    w = (rng.random((V, V)) < density) * rng.integers(1, 6, (V, V))
    keep_loops = rng.random(V) < 0.1
    diag = np.diag(np.diag(w) * keep_loops)
    np.fill_diagonal(w, 0)
    return (w + diag).astype(np.float32)


graph_params = st.tuples(st.integers(min_value=1, max_value=18),
                         st.floats(min_value=0.0, max_value=0.5),
                         st.integers(min_value=0, max_value=2**31 - 1))


class TestExactOnceEdgeCoverage:
    @pytest.mark.parametrize("schedule", SCHEDULES)
    @given(params=graph_params,
           num_blocks=st.integers(min_value=1, max_value=6))
    @settings(max_examples=6, deadline=None)
    def test_full_frontier_unit_advance_counts_in_degrees(
            self, schedule, params, num_blocks):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        in_deg = (w > 0).sum(axis=0).astype(np.float32)
        frontier = jnp.ones((V,), bool)
        for path in ("pure", "native"):
            plan = build_advance(g, schedule=schedule,
                                 num_blocks=num_blocks, path=path)
            got = advance(plan, frontier,
                          lambda e: jnp.ones(e.shape, jnp.float32),
                          combiner="sum")
            assert_bitwise_equal(got, in_deg,
                                 f"edges dropped/duplicated: {schedule}/{path}")


class TestMonotoneFrontierConvergence:
    @given(params=graph_params)
    @settings(max_examples=8, deadline=None)
    def test_bfs_levels_partition_reachable_set(self, params):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        depth = np.asarray(bfs(g, 0, schedule="chunked_lpt", num_blocks=3))
        want, _ = np_bfs(w, 0)
        np.testing.assert_array_equal(depth, want)
        # monotone convergence: running with a tighter iteration budget
        # yields a prefix of the final labelling (labels never regress)
        for cap in range(int(depth.max()) + 1):
            partial = np.asarray(bfs(g, 0, schedule="chunked_lpt",
                                     num_blocks=3, max_iters=cap))
            settled = partial >= 0
            np.testing.assert_array_equal(partial[settled], depth[settled])
            assert np.all(partial[depth == -1] == -1)

    @given(params=graph_params)
    @settings(max_examples=8, deadline=None)
    def test_bfs_parent_edges_step_one_level(self, params):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        depth, parent = bfs(g, 0, schedule="adaptive", num_blocks=3,
                            return_parents=True)
        depth, parent = np.asarray(depth), np.asarray(parent)
        for v in range(V):
            if parent[v] >= 0:
                assert w[parent[v], v] > 0, "parent must be an in-neighbour"
                assert depth[v] == depth[parent[v]] + 1


class TestDirectionEquivalence:
    @given(params=graph_params,
           threshold=st.floats(min_value=0.0, max_value=1.0))
    @settings(max_examples=8, deadline=None)
    def test_direction_optimizing_bfs_matches_pull_only(self, params,
                                                        threshold):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        plan = build_advance(g, schedule="merge_path", num_blocks=3,
                             direction_threshold=threshold)
        pull = np.asarray(bfs(g, 0, plan=plan, direction="pull"))
        auto = np.asarray(bfs(g, 0, plan=plan, direction="auto"))
        push = np.asarray(bfs(g, 0, plan=plan, direction="push"))
        want, _ = np_bfs(w, 0)
        np.testing.assert_array_equal(pull, want)
        np.testing.assert_array_equal(auto, want)
        np.testing.assert_array_equal(push, want)
        # identical visited sets by construction of the equality above
        assert set(np.flatnonzero(auto >= 0)) == set(np.flatnonzero(
            pull >= 0))

    @pytest.mark.parametrize("schedule", SCHEDULES)
    @given(params=graph_params,
           num_blocks=st.integers(min_value=1, max_value=6))
    @settings(max_examples=6, deadline=None)
    def test_push_full_frontier_unit_advance_counts_in_degrees(
            self, schedule, params, num_blocks):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        in_deg = (w > 0).sum(axis=0).astype(np.float32)
        frontier = jnp.ones((V,), bool)
        for path in ("pure", "native"):
            plan = build_advance(g, schedule=schedule,
                                 num_blocks=num_blocks, path=path)
            got = advance_push(plan, frontier,
                               lambda e: jnp.ones(e.shape, jnp.float32),
                               combiner="sum")
            assert_bitwise_equal(got, in_deg,
                                 f"push dropped/duplicated edges: "
                                 f"{schedule}/{path}")


class TestDeltaSteppingEquivalence:
    """Delta-stepping == frontier Bellman-Ford, bitwise, for *arbitrary*
    bucket widths on random weighted digraphs (the bucketed traversal runs
    every relaxation to quiescence, so the f32 fixed point is the same no
    matter how distances were binned)."""

    @given(params=graph_params,
           delta=st.floats(min_value=0.05, max_value=24.0))
    @settings(max_examples=8, deadline=None)
    def test_delta_matches_bellman_ford_bitwise(self, params, delta):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        plan = build_advance(g, schedule="chunked_lpt", num_blocks=3,
                             delta=delta, compact=True)
        bf = np.asarray(sssp(g, 0, plan=plan, direction="pull"))
        for direction in ("pull", "push", "auto"):
            ds = np.asarray(delta_stepping(g, 0, plan=plan,
                                           direction=direction))
            assert_bitwise_equal(ds, bf, f"direction={direction}, "
                                         f"delta={delta}")
        assert_bitwise_equal(np_delta_stepping(w, 0, delta), bf,
                             f"np oracle, delta={delta}")

    @pytest.mark.parametrize("schedule", SCHEDULES)
    @given(params=graph_params)
    @settings(max_examples=4, deadline=None)
    def test_delta_default_width_matches_across_schedules(self, schedule,
                                                          params):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        bf = np.asarray(sssp(g, 0, schedule=schedule, num_blocks=3))
        ds = np.asarray(delta_stepping(g, 0, schedule=schedule,
                                       num_blocks=3))
        assert_bitwise_equal(ds, bf, str(schedule))


class TestCompactLadderEquivalence:
    """The gather-compacted push reduce runs the smallest rung of its
    capacity ladder that holds the frontier's active edges; whatever the
    capacity, the frontier and the combiner, it returns the masked
    reduce's bits."""

    @pytest.mark.parametrize("path", ["pure", "native"])
    @given(params=st.tuples(st.integers(min_value=40, max_value=72),
                            st.floats(min_value=0.2, max_value=0.6),
                            st.integers(min_value=0, max_value=2**31 - 1)),
           frac=st.floats(min_value=0.0, max_value=1.0),
           cap_frac=st.floats(min_value=0.0, max_value=1.1))
    @settings(max_examples=4, deadline=None)
    def test_any_capacity_matches_masked(self, path, params, frac,
                                         cap_frac):
        from repro.core import execute_scatter_reduce, make_partition
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        spec = g.csr.workspec()
        part = make_partition(spec, Schedule.CHUNKED, 5)
        rng = np.random.default_rng(seed)
        vals = jnp.asarray(rng.integers(-8, 9, spec.num_atoms)
                           .astype(np.float32))
        mask = jnp.asarray(rng.random(spec.num_atoms) < frac)
        capacity = max(int(cap_frac * spec.num_atoms), 1)
        for combiner in ("sum", "min", "max"):
            got, want = (execute_scatter_reduce(
                spec, part, vals, g.csr.col_indices, V, path=path,
                combiner=combiner, atom_mask=mask, compact_capacity=cap)
                for cap in (capacity, None))
            assert_bitwise_equal(got, want,
                                 f"{path}/{combiner}/capacity {capacity}")


class TestSsspTriangleInequality:
    @given(params=graph_params)
    @settings(max_examples=8, deadline=None)
    def test_relaxed_distances_are_stable(self, params):
        V, density, seed = params
        w = random_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        dist = np.asarray(sssp(g, 0, schedule="chunked_rr", num_blocks=3))
        np.testing.assert_allclose(dist, np_sssp(w, 0), rtol=1e-6)
        us, vs = np.nonzero(w)
        for u, v in zip(us, vs):
            if np.isfinite(dist[u]):
                assert dist[v] <= dist[u] + w[u, v] + 1e-6
        # tightness: every finite distance is witnessed by an in-edge
        for v in range(V):
            if v != 0 and np.isfinite(dist[v]):
                preds = np.nonzero(w[:, v])[0]
                assert any(np.isclose(dist[p] + w[p, v], dist[v], rtol=1e-6)
                           for p in preds)


def _skewed_digraph(V: int, density: float, seed: int) -> np.ndarray:
    """A random digraph with a planted in-hub at vertex 0 — the skew
    degree-aware boundary schedules exist for."""
    w = random_digraph(V, density, seed)
    if V > 1:
        rng = np.random.default_rng(seed + 1)
        w[1:, 0] = rng.integers(1, 6, V - 1).astype(np.float32)
    return w


class TestShardedHaloExactOnce:
    """Sharding is a pure decomposition of the edge set: for arbitrary
    random skewed digraphs and *every* boundary schedule, the per-shard
    local CSR views must cover every edge exactly once (any halo
    duplication or drop shows up as a mask-count mismatch), and the
    halo-exchanging sharded traversals must land on the same fixed point
    as the unsharded drivers, bit for bit."""

    @given(params=graph_params)
    @settings(max_examples=4, deadline=None)
    def test_shard_views_partition_edge_set(self, params):
        import jax
        from repro.sparse import (SHARD_SCHEDULES, build_sharded_advance,
                                  sharded_bfs)
        V, density, seed = params
        w = _skewed_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        S = max(s for s in (1, 2, 4)
                if s <= len(jax.devices()) and s <= V)
        E = g.csr.nnz
        want, _ = np_bfs(w, 0)
        for boundary in SHARD_SCHEDULES:
            splan = build_sharded_advance(g, S, schedule="merge_path",
                                          path="pure", num_blocks=3,
                                          shard_schedule=boundary)
            # exact-once: the valid masks over both directions' padded
            # local views sum to the global edge count — no edge is owned
            # by two shards, none falls into the padding
            assert int(np.asarray(splan.arrays["pull_valid"]).sum()) == E
            assert int(np.asarray(splan.arrays["push_valid"]).sum()) == E
            assert int(np.asarray(splan.arrays["out_degrees"]).sum()) == E
            np.testing.assert_array_equal(np.asarray(sharded_bfs(splan, 0)),
                                          want)

    @given(params=graph_params)
    @settings(max_examples=2, deadline=None)
    def test_sharded_traversals_bitwise_any_boundary(self, params):
        import jax
        from repro.sparse import (SHARD_SCHEDULES, build_sharded_advance,
                                  sharded_delta_stepping, sharded_sssp)
        V, density, seed = params
        w = _skewed_digraph(V, density, seed)
        g = Graph(CSR.from_dense(w))
        want_s = sssp(g, 0, schedule="merge_path", path="pure", num_blocks=3)
        want_d = delta_stepping(g, 0, schedule="merge_path", path="pure",
                                num_blocks=3, compact=None)
        for boundary in SHARD_SCHEDULES:
            for S in (1, 2, 4):
                if S > len(jax.devices()) or S > V:
                    continue
                splan = build_sharded_advance(g, S, schedule="merge_path",
                                              path="pure", num_blocks=3,
                                              shard_schedule=boundary,
                                              delta="auto")
                assert_bitwise_equal(sharded_sssp(splan, 0), want_s,
                                     f"sssp {boundary} s{S}")
                assert_bitwise_equal(sharded_delta_stepping(splan, 0),
                                     want_d, f"delta {boundary} s{S}")
