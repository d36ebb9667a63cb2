"""Differential suite for the load-balanced graph operators (paper §5.3).

The acceptance bar of the graph subsystem: the frontier-masked ``advance``
must be **bit-identical** to a pure-NumPy oracle under every registered
schedule x execution path, and BFS / SSSP / PageRank built on it must match
scipy-free NumPy references on random and adversarial graphs (isolated
vertices, self-loops, disconnected components, zero-degree tails).  All
machinery comes from the shared conformance library (``_conformance.py``).

Note for CI: the tests with ``native`` in their name are the graph
native-path gate — the tier-1 workflow collects them by keyword and fails
if they disappear.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import jax

from repro.core import (ExecutionPath, Plan, Schedule,
                        blocked_compact_value_windows, compact_active_atoms,
                        compact_rung_index, compact_rungs,
                        estimate_compact_capacity,
                        estimate_direction_threshold, execute_scatter_reduce,
                        make_partition, modeled_advance_cost,
                        native_compact_value_windows, partition_build_count,
                        score_plans, select_plan, supports_native_execution)
from repro.sparse import (CSR, Graph, advance, advance_frontier,
                          advance_push, advance_relax_min, bfs, bfs_multi,
                          build_advance, delta_stepping, estimate_delta,
                          frontier_filter, pagerank, sssp)
from _conformance import (
    PATHS, SCHEDULES, adversarial_graphs, assert_bitwise_equal,
    check_advance_direction_equivalence, np_advance, np_advance_push,
    np_bfs, np_delta_stepping, np_pagerank, np_sssp, powerlaw_graph_dense,
)

GRAPHS = {"powerlaw": powerlaw_graph_dense(40, avg_degree=5.0, seed=2),
          **adversarial_graphs(seed=3)}

#: A skewed graph large enough for a ladder of compaction rungs: 8,463
#: edges, and BFS levels from vertex 0 with 1, 9, 19, 58, 286, 499, 2,658
#: and 4,933 active out-edges.
LADDER_W = powerlaw_graph_dense(1500, avg_degree=6.0, seed=4)


def graph_of(w) -> Graph:
    return Graph(CSR.from_dense(np.asarray(w, np.float32)))


def frontier_of(V, seed, frac=0.4):
    rng = np.random.default_rng(seed)
    f = rng.random(V) < frac
    f[0] = True           # never empty
    return f


class TestAdvanceConformance:
    """advance == NumPy oracle, bit for bit, across the whole matrix."""

    @pytest.mark.parametrize("schedule", SCHEDULES)
    @pytest.mark.parametrize("path", PATHS, ids=str)
    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_relax_min_matrix(self, name, schedule, path):
        w = GRAPHS[name]
        g = graph_of(w)
        plan = build_advance(g, schedule=schedule, num_blocks=4, path=path)
        assert plan.path == ExecutionPath(path)
        V = g.num_vertices
        rng = np.random.default_rng(7)
        pot = rng.integers(0, 16, V).astype(np.float32)
        frontier = frontier_of(V, seed=8)
        got = advance_relax_min(plan, jnp.asarray(pot), jnp.asarray(frontier))
        pull_off = np.asarray(plan.spec.tile_offsets)
        src = np.asarray(plan.src)
        edge_vals = pot[src] + np.asarray(plan.weight)
        want = np_advance(pull_off, src, edge_vals, frontier, "min")
        assert_bitwise_equal(got, want, f"{name}/{schedule}/{path}")

    @pytest.mark.parametrize("combiner", ["sum", "max"])
    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_sum_and_or_combiners_native_and_pure(self, name, combiner):
        w = GRAPHS[name]
        g = graph_of(w)
        V = g.num_vertices
        frontier = frontier_of(V, seed=9)
        rng = np.random.default_rng(10)
        vertex_vals = rng.integers(1, 9, V).astype(np.float32)
        results = []
        for schedule in SCHEDULES:
            for path in PATHS:
                plan = build_advance(g, schedule=schedule, num_blocks=3,
                                     path=path)
                src = plan.src
                jv = jnp.asarray(vertex_vals)
                got = advance(plan, jnp.asarray(frontier),
                              lambda e: jv[src[e]], combiner=combiner)
                results.append((f"{schedule}/{path}", got, plan))
        pull_off = np.asarray(results[0][2].spec.tile_offsets)
        srcs = np.asarray(results[0][2].src)
        want = np_advance(pull_off, srcs, vertex_vals[srcs], frontier,
                          combiner)
        for label, got, _ in results:
            assert_bitwise_equal(got, want, f"{name}/{label}/{combiner}")

    def test_empty_frontier_yields_identity(self):
        g = graph_of(GRAPHS["powerlaw"])
        V = g.num_vertices
        none = jnp.zeros((V,), bool)
        plan = build_advance(g, schedule="chunked_lpt", num_blocks=4)
        cand = advance_relax_min(plan, jnp.zeros((V,), jnp.float32), none)
        assert bool(jnp.isinf(cand).all())
        assert not bool(advance_frontier(plan, none).any())

    def test_frontier_filter_masks_visited(self):
        # path 0 -> 1 -> 2: filtering out visited vertex 1 empties the
        # next frontier of it, keeps 2 when advancing from {1}
        w = np.zeros((3, 3), np.float32)
        w[0, 1] = w[1, 2] = 1.0
        g = graph_of(w)
        plan = build_advance(g, schedule="merge_path", num_blocks=2)
        frontier = jnp.asarray([True, True, False])
        visited = jnp.asarray([True, True, False])
        nxt = frontier_filter(plan, frontier, keep=~visited)
        np.testing.assert_array_equal(np.asarray(nxt), [False, False, True])


class TestPushDirection:
    """Push advance == pull advance == NumPy oracles, bit for bit.

    These tests carry the ``push``/``direction`` keywords the CI direction
    gate collects by (``-k "push or direction"``); pytest exits 5 if the
    keyword stops matching anything, so silently losing this coverage
    fails the workflow.
    """

    @pytest.mark.parametrize("schedule", SCHEDULES)
    @pytest.mark.parametrize("path", PATHS, ids=str)
    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_push_relax_min_matrix(self, name, schedule, path):
        w = GRAPHS[name]
        g = graph_of(w)
        plan = build_advance(g, schedule=schedule, num_blocks=4, path=path)
        assert plan.push_path == ExecutionPath(path)
        V = g.num_vertices
        rng = np.random.default_rng(7)
        pot = rng.integers(0, 16, V).astype(np.float32)
        frontier = frontier_of(V, seed=8)
        got = advance_relax_min(plan, jnp.asarray(pot), jnp.asarray(frontier),
                                direction="push")
        psrc = np.asarray(plan.push_src)
        edge_vals = pot[psrc] + np.asarray(plan.push_weight)
        want = np_advance_push(np.asarray(plan.push_spec.tile_offsets),
                               np.asarray(plan.dst), edge_vals, frontier,
                               "min", V)
        assert_bitwise_equal(got, want, f"{name}/{schedule}/{path}")
        pull = advance_relax_min(plan, jnp.asarray(pot),
                                 jnp.asarray(frontier), direction="pull")
        assert_bitwise_equal(got, pull,
                             f"{name}/{schedule}/{path}: directions diverged")

    @pytest.mark.parametrize("combiner", ["sum", "min", "max"])
    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_push_equals_pull_full_matrix(self, name, combiner):
        # the one-call direction-equivalence matrix: every schedule x path
        check_advance_direction_equivalence(GRAPHS[name], combiner=combiner,
                                            seed=11)

    def test_push_empty_frontier_yields_identity(self):
        g = graph_of(GRAPHS["powerlaw"])
        V = g.num_vertices
        none = jnp.zeros((V,), bool)
        plan = build_advance(g, schedule="chunked_lpt", num_blocks=4)
        cand = advance_relax_min(plan, jnp.zeros((V,), jnp.float32), none,
                                 direction="push")
        assert bool(jnp.isinf(cand).all())
        assert not bool(advance_frontier(plan, none,
                                         direction="push").any())

    def test_push_full_frontier_counts_in_degrees(self):
        # exact-once edge coverage through the scatter path
        w = GRAPHS["zero_degree_tail"]
        g = graph_of(w)
        in_deg = (np.asarray(w) > 0).sum(axis=0).astype(np.float32)
        for schedule, path in (("chunked_rr", "native"),
                               ("merge_path", "pure")):
            plan = build_advance(g, schedule=schedule, num_blocks=3,
                                 path=path)
            got = advance_push(plan, jnp.ones((g.num_vertices,), bool),
                               lambda e: jnp.ones(e.shape, jnp.float32),
                               combiner="sum")
            assert_bitwise_equal(got, in_deg, f"{schedule}/{path}")

    def test_plan_pair_is_one_inspector_product(self):
        g = graph_of(GRAPHS["powerlaw"])
        before = partition_build_count()
        plan = build_advance(g, schedule="merge_path", num_blocks=4)
        assert partition_build_count() - before == 2  # one per direction
        assert plan.push_spec.num_atoms == plan.spec.num_atoms == g.num_edges
        assert float(plan.frontier_edge_fraction(
            jnp.ones((g.num_vertices,), bool))) == pytest.approx(1.0)


class TestDirectionOptimizingTraversals:
    """Measured-density direction switching never changes results."""

    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_direction_auto_bfs_matches_pull_only(self, name):
        w = GRAPHS[name]
        g = graph_of(w)
        plan = build_advance(g, schedule="merge_path", num_blocks=4)
        want_depth, want_parent = np_bfs(w, 0)
        for direction in ("auto", "push", "pull"):
            depth, parent = bfs(g, 0, plan=plan, direction=direction,
                                return_parents=True)
            np.testing.assert_array_equal(np.asarray(depth), want_depth,
                                          f"{name}/{direction}")
            np.testing.assert_array_equal(np.asarray(parent), want_parent,
                                          f"{name}/{direction}")

    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_direction_auto_sssp_matches_pull_only(self, name):
        w = GRAPHS[name]
        g = graph_of(w)
        plan = build_advance(g, schedule="chunked_lpt", num_blocks=4)
        pull = sssp(g, 0, plan=plan, direction="pull")
        auto = sssp(g, 0, plan=plan, direction="auto")
        assert_bitwise_equal(auto, pull, name)

    def test_direction_counts_report_the_switch(self):
        # the power-law graph's BFS starts sparse (push) and densifies
        # (pull) — with a mid-range threshold both counters must move
        g = graph_of(GRAPHS["powerlaw"])
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             direction_threshold=0.3)
        depth, counts = bfs(g, 0, plan=plan, direction="auto",
                            return_direction_counts=True)
        counts = np.asarray(counts)
        assert counts.sum() > 0
        assert counts[0] > 0, "push never ran"
        assert counts[1] > 0, "pull never ran"
        # forcing the threshold to the extremes pins the direction
        for thr, idx in ((0.0, 0), (1.0, 1)):
            p = build_advance(g, schedule="merge_path", num_blocks=4,
                              direction_threshold=thr)
            _, c = bfs(g, 0, plan=p, direction="auto",
                       return_direction_counts=True)
            assert np.asarray(c)[idx] == 0, (thr, np.asarray(c))

    def test_direction_threshold_is_a_density(self):
        g = graph_of(GRAPHS["powerlaw"])
        plan = build_advance(g, schedule="auto", num_blocks=8)
        assert 0.0 <= plan.direction_threshold <= 1.0
        thr = estimate_direction_threshold(
            plan.spec, plan.push_spec, 8,
            pull_schedule=plan.schedule, push_schedule=plan.push_schedule)
        assert thr == pytest.approx(plan.direction_threshold, abs=1e-6)

    def test_direction_cost_model_crosses_over(self):
        # push must be modeled cheaper at zero density and costlier than
        # pull at full density on an overhead-free pull schedule — the
        # crossover is what direction optimization exists for
        g = graph_of(powerlaw_graph_dense(120, avg_degree=8.0, seed=4))
        pull_spec = g.csr.transpose().workspec()
        push_spec = g.csr.workspec()
        lo_push = modeled_advance_cost(push_spec, "merge_path", 8,
                                       direction="push", density=0.0)
        lo_pull = modeled_advance_cost(pull_spec, "merge_path", 8,
                                       direction="pull", density=0.0)
        hi_push = modeled_advance_cost(push_spec, "merge_path", 8,
                                       direction="push", density=1.0)
        hi_pull = modeled_advance_cost(pull_spec, "merge_path", 8,
                                       direction="pull", density=1.0)
        assert lo_push < lo_pull
        assert hi_push > hi_pull
        with pytest.raises(ValueError):
            modeled_advance_cost(pull_spec, "merge_path", 8,
                                 direction="sideways")

    def test_direction_multi_source_bfs_shares_the_plan_pair(self):
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        plan = build_advance(g, schedule="adaptive", num_blocks=4)
        sources = [0, 3, 9]
        before = partition_build_count()
        batched = np.asarray(bfs_multi(g, sources, plan=plan))
        assert partition_build_count() == before  # no re-inspection
        for i, s in enumerate(sources):
            want, _ = np_bfs(w, s)
            np.testing.assert_array_equal(batched[i], want, f"source {s}")


class TestTraversalsVsReferences:
    """BFS/SSSP/PageRank drivers vs scipy-free NumPy references."""

    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_bfs_depth_and_parents(self, name):
        w = GRAPHS[name]
        g = graph_of(w)
        depth, parent = bfs(g, 0, schedule="merge_path", num_blocks=4,
                            return_parents=True)
        want_depth, want_parent = np_bfs(w, 0)
        np.testing.assert_array_equal(np.asarray(depth), want_depth, name)
        np.testing.assert_array_equal(np.asarray(parent), want_parent, name)

    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_sssp_distances(self, name):
        w = GRAPHS[name]
        g = graph_of(w)
        dist = np.asarray(sssp(g, 0, schedule="chunked_lpt", num_blocks=4))
        np.testing.assert_allclose(dist, np_sssp(w, 0), rtol=1e-6,
                                   err_msg=name)

    @pytest.mark.parametrize("name", ["powerlaw", "disconnected",
                                      "star_hub"])
    def test_pagerank(self, name):
        w = GRAPHS[name]
        g = graph_of(w)
        pr = np.asarray(pagerank(g, num_iters=40, schedule="adaptive",
                                 num_blocks=4))
        np.testing.assert_allclose(pr, np_pagerank(w, num_iters=40),
                                   rtol=1e-4, atol=1e-7, err_msg=name)
        np.testing.assert_allclose(pr.sum(), 1.0, rtol=1e-4)

    def test_bfs_native_schedule_sweep_bit_identical(self):
        # the graph native-path gate: every schedule on the native kernel
        # must reproduce the pure path's BFS labels exactly
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        want, _ = np_bfs(w, 0)
        for schedule in SCHEDULES:
            for path in PATHS:
                depth = bfs(g, 0, schedule=schedule, num_blocks=4, path=path)
                np.testing.assert_array_equal(
                    np.asarray(depth), want, f"{schedule}/{path}")

    def test_sssp_native_matches_pure_bitwise(self):
        w = GRAPHS["zero_degree_tail"]
        g = graph_of(w)
        native = sssp(g, 0, schedule="chunked_rr", num_blocks=4,
                      path="native")
        pure = sssp(g, 0, schedule="chunked_rr", num_blocks=4, path="pure")
        assert_bitwise_equal(native, pure)


class TestAdvanceAutotune:
    """schedule="auto" selects a plan for advance workloads (acceptance)."""

    def test_auto_plan_is_advance_argmin(self):
        g = graph_of(powerlaw_graph_dense(120, avg_degree=8.0, skew=1.5,
                                          seed=4))
        spec = g.csr.transpose().workspec()
        plan = select_plan(spec, 16, cache=None, workload="advance")
        scores = score_plans(spec, 16, workload="advance")
        assert scores[plan] == min(scores.values())

    def test_build_advance_auto_runs_and_matches(self):
        w = powerlaw_graph_dense(60, avg_degree=6.0, seed=5)
        g = graph_of(w)
        plan = build_advance(g, schedule="auto", num_blocks=8)
        assert plan.schedule in set(SCHEDULES)
        assert supports_native_execution(plan.part)
        depth = bfs(g, 0, plan=plan)
        want, _ = np_bfs(w, 0)
        np.testing.assert_array_equal(np.asarray(depth), want)

    def test_advance_workload_changes_cost_ordering_inputs(self):
        # the advance family scores atoms heavier than the reduce family;
        # per-block overheads are unscaled, so relative scores must differ
        g = graph_of(powerlaw_graph_dense(80, avg_degree=6.0, seed=6))
        spec = g.csr.transpose().workspec()
        reduce_scores = score_plans(spec, 8, workload="reduce")
        advance_scores = score_plans(spec, 8, workload="advance")
        assert any(advance_scores[p] > reduce_scores[p]
                   for p in reduce_scores)

    def test_advance_cache_namespace_is_disjoint(self, tmp_path):
        from repro.core import AutotuneCache
        cache = AutotuneCache(tmp_path / "cache.json")
        g = graph_of(powerlaw_graph_dense(50, avg_degree=5.0, seed=7))
        spec = g.csr.transpose().workspec()
        select_plan(spec, 8, cache=cache, workload="reduce")
        select_plan(spec, 8, cache=cache, workload="advance")
        keys = set(cache._mem)
        assert any(k.endswith("|plan") for k in keys)
        assert any(k.endswith("|plan.advance") for k in keys)

    def test_push_workload_family_selects_and_namespaces(self, tmp_path):
        from repro.core import AutotuneCache
        cache = AutotuneCache(tmp_path / "cache.json")
        g = graph_of(powerlaw_graph_dense(120, avg_degree=8.0, skew=1.5,
                                          seed=4))
        push_spec = g.csr.workspec()
        plan = select_plan(push_spec, 16, cache=cache,
                           workload="advance_push")
        scores = score_plans(push_spec, 16, workload="advance_push")
        assert scores[plan] == min(scores.values())
        assert any(k.endswith("|plan.advance_push") for k in cache._mem)
        # the push family charges active atoms heavier than the pull family
        adv = score_plans(push_spec, 16, workload="advance")
        assert any(scores[p] > adv[p] for p in adv)

    def test_build_advance_auto_selects_push_plan_jointly(self):
        g = graph_of(powerlaw_graph_dense(60, avg_degree=6.0, seed=5))
        plan = build_advance(g, schedule="auto", num_blocks=8)
        assert plan.push_schedule in set(SCHEDULES)
        assert supports_native_execution(plan.push_part)
        # direction equivalence survives independently chosen schedules
        depth_auto = bfs(g, 0, plan=plan, direction="auto")
        depth_pull = bfs(g, 0, plan=plan, direction="pull")
        np.testing.assert_array_equal(np.asarray(depth_auto),
                                      np.asarray(depth_pull))

    def test_unknown_workload_rejected(self):
        g = graph_of(GRAPHS["self_loops"])
        spec = g.csr.transpose().workspec()
        with pytest.raises(ValueError):
            select_plan(spec, 4, cache=None, workload="scan")


class TestDeltaStepping:
    """Delta-stepping SSSP == frontier Bellman-Ford, bit for bit.

    These tests carry the ``delta`` keyword the CI bucketed-traversal gate
    collects (``-k "delta or compact"``); pytest exits 5 if the keyword
    stops matching anything, so silently losing this coverage fails the
    workflow.
    """

    @pytest.mark.parametrize("name", sorted(GRAPHS))
    def test_delta_matches_bellman_ford_full_matrix(self, name):
        # the acceptance matrix: all 6 schedules x both execution paths x
        # both directions, one BF reference per graph (BF itself is
        # schedule/path-invariant — asserted by the PR-3/4 suites)
        w = GRAPHS[name]
        g = graph_of(w)
        want = np.asarray(sssp(g, 0, schedule="merge_path", num_blocks=4))
        for schedule in SCHEDULES:
            for path in PATHS:
                plan = build_advance(g, schedule=schedule, num_blocks=4,
                                     path=path, delta="auto", compact=True)
                for direction in ("pull", "push"):
                    got = delta_stepping(g, 0, plan=plan,
                                         direction=direction)
                    assert_bitwise_equal(
                        got, want, f"{name}/{schedule}/{path}/{direction}")

    @pytest.mark.parametrize("delta", [0.5, 1.0, 3.0, 64.0])
    def test_delta_width_never_changes_bits(self, delta):
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        plan = build_advance(g, schedule="chunked_lpt", num_blocks=4)
        want = np.asarray(sssp(g, 0, plan=plan))
        got = np.asarray(delta_stepping(g, 0, plan=plan, delta=delta))
        assert_bitwise_equal(got, want, f"delta={delta}")
        assert_bitwise_equal(got, np_delta_stepping(w, 0, delta),
                             f"np oracle, delta={delta}")

    @pytest.mark.parametrize("name", ["powerlaw", "star_hub",
                                      "zero_degree_tail"])
    def test_delta_numpy_oracle_bitwise(self, name):
        w = GRAPHS[name]
        g = graph_of(w)
        got = np.asarray(delta_stepping(g, 0, schedule="merge_path",
                                        num_blocks=4))
        assert_bitwise_equal(got, np_delta_stepping(w, 0), name)
        np.testing.assert_allclose(np.asarray(got), np_sssp(w, 0),
                                   rtol=1e-6, err_msg=name)

    def test_delta_exhausted_cap_still_converges(self):
        # a deliberately starved outer cap must not truncate: the
        # Bellman-Ford backstop finishes the leftover relaxations, so
        # bit-identity holds unconditionally (a bad cap costs rounds,
        # never bits)
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             delta=0.5)      # many buckets
        want = np.asarray(sssp(g, 0, plan=plan))
        for cap in (0, 1, 2):
            got = np.asarray(delta_stepping(g, 0, plan=plan,
                                            max_iters=cap))
            assert_bitwise_equal(got, want, f"max_iters={cap}")

    def test_sssp_algorithm_param_routes_to_delta(self):
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        bf = sssp(g, 0, schedule="merge_path", num_blocks=4)
        ds = sssp(g, 0, schedule="merge_path", num_blocks=4,
                  algorithm="delta", delta=2.0)
        assert_bitwise_equal(ds, bf)
        with pytest.raises(ValueError):
            sssp(g, 0, algorithm="dijkstra")

    def test_delta_split_partitions_the_edge_set(self):
        g = graph_of(GRAPHS["powerlaw"])
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             delta="auto")
        assert plan.delta is not None and plan.delta > 0
        E = g.num_edges
        light = np.asarray(plan.light_mask)
        push_light = np.asarray(plan.push_light_mask)
        assert light.shape == (E,) and push_light.shape == (E,)
        # same multiset of weights on both sides: the split is per-edge,
        # order differs per direction
        assert light.sum() == push_light.sum()
        assert np.all(np.asarray(plan.push_weight)[push_light] <= plan.delta)
        assert np.all(np.asarray(plan.push_weight)[~push_light] > plan.delta)
        # the measured light density term sums the push-side split
        assert int(np.asarray(plan.light_out_degrees).sum()) == \
            int(push_light.sum())

    def test_delta_default_width_is_the_mean_weight(self):
        g = graph_of(GRAPHS["powerlaw"])
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             delta="auto")
        w = np.asarray(plan.push_weight)
        assert plan.delta == pytest.approx(
            max(np.float32(w.mean()), w.min()))
        assert estimate_delta(w) == plan.delta
        assert estimate_delta(np.zeros((0,), np.float32)) == 1.0

    def test_delta_requires_positive_width(self):
        g = graph_of(GRAPHS["self_loops"])
        plan = build_advance(g, schedule="merge_path", num_blocks=2)
        with pytest.raises(ValueError):
            plan.with_delta(0.0)
        with pytest.raises(ValueError):
            plan.with_delta(-1.0)

    def test_delta_edges_selector_needs_a_split(self):
        g = graph_of(GRAPHS["self_loops"])
        plan = build_advance(g, schedule="merge_path", num_blocks=2)
        pot = jnp.zeros((g.num_vertices,), jnp.float32)
        frontier = jnp.ones((g.num_vertices,), bool)
        with pytest.raises(ValueError):
            advance_relax_min(plan, pot, frontier, edges="light")
        with pytest.raises(ValueError):
            advance_relax_min(plan, pot, frontier, edges="sideways")

    def test_delta_light_heavy_advances_cover_exactly_once(self):
        # light + heavy unit sum-advances == the full advance: the split is
        # a partition of the edge set, no edge dropped or double-counted
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        plan = build_advance(g, schedule="chunked_lpt", num_blocks=4,
                             delta="auto")
        frontier = jnp.ones((g.num_vertices,), bool)
        unit = lambda e: jnp.ones(e.shape, jnp.float32)
        in_deg = (np.asarray(w) > 0).sum(axis=0).astype(np.float32)
        for direction, adv in (("pull", advance), ("push", advance_push)):
            light = adv(plan, frontier, unit, combiner="sum",
                        edge_mask=plan.edge_set_mask("light", direction))
            heavy = adv(plan, frontier, unit, combiner="sum",
                        edge_mask=plan.edge_set_mask("heavy", direction))
            assert_bitwise_equal(np.asarray(light) + np.asarray(heavy),
                                 in_deg, direction)

    def test_delta_direction_counts_report_the_switch(self):
        g = graph_of(GRAPHS["powerlaw"])
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             delta="auto", direction_threshold=0.3)
        dist, counts = delta_stepping(g, 0, plan=plan, direction="auto",
                                      return_direction_counts=True)
        counts = np.asarray(counts)
        assert counts.sum() > 0
        # pinning the threshold pins every bucket phase's direction
        for thr, idx in ((0.0, 0), (1.0, 1)):
            p = build_advance(g, schedule="merge_path", num_blocks=4,
                              delta="auto", direction_threshold=thr)
            _, c = delta_stepping(g, 0, plan=p, direction="auto",
                                  return_direction_counts=True)
            assert np.asarray(c)[idx] == 0, (thr, np.asarray(c))

    def test_delta_autotune_family_selects_and_namespaces(self, tmp_path):
        from repro.core import AutotuneCache
        cache = AutotuneCache(tmp_path / "cache.json")
        g = graph_of(powerlaw_graph_dense(120, avg_degree=8.0, skew=1.5,
                                          seed=4))
        spec = g.csr.transpose().workspec()
        plan = select_plan(spec, 16, cache=cache, workload="advance_delta")
        scores = score_plans(spec, 16, workload="advance_delta")
        assert scores[plan] == min(scores.values())
        assert any(k.endswith("|plan.advance_delta") for k in cache._mem)
        # bucketed advances charge atoms heavier than the plain family
        adv = score_plans(spec, 16, workload="advance")
        assert any(scores[p] > adv[p] for p in adv)
        push_spec = g.csr.workspec()
        select_plan(push_spec, 16, cache=cache,
                    workload="advance_delta_push")
        assert any(k.endswith("|plan.advance_delta_push")
                   for k in cache._mem)

    def test_delta_auto_schedule_builds_and_matches(self):
        w = powerlaw_graph_dense(60, avg_degree=6.0, seed=5)
        g = graph_of(w)
        dist = np.asarray(sssp(g, 0, schedule="auto", num_blocks=8,
                               algorithm="delta"))
        np.testing.assert_allclose(dist, np_sssp(w, 0), rtol=1e-6)


class TestCompactWindows:
    """Gather-compacted push windows == masked full windows, bit for bit.

    The ``compact`` keyword half of the CI bucketed-traversal gate
    (``-k "delta or compact"``).
    """

    @pytest.mark.parametrize("schedule", SCHEDULES)
    @pytest.mark.parametrize("path", PATHS, ids=str)
    def test_compact_scatter_reduce_matches_masked(self, schedule, path):
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        V = g.num_vertices
        spec = g.csr.workspec()
        part = make_partition(spec, schedule, 4)
        rng = np.random.default_rng(21)
        vals = jnp.asarray(rng.integers(-8, 9, spec.num_atoms)
                           .astype(np.float32))
        atom_fn = lambda e: vals[e]
        mask = jnp.asarray(rng.random(spec.num_atoms) < 0.3)
        for combiner in ("sum", "min", "max"):
            want = execute_scatter_reduce(
                spec, part, atom_fn, g.csr.col_indices, V, path=path,
                combiner=combiner, atom_mask=mask)
            for capacity in (spec.num_atoms, int(mask.sum()) + 3):
                got = execute_scatter_reduce(
                    spec, part, atom_fn, g.csr.col_indices, V, path=path,
                    combiner=combiner, atom_mask=mask,
                    compact_capacity=capacity)
                assert_bitwise_equal(
                    got, want, f"{schedule}/{path}/{combiner}/{capacity}")

    def test_compact_overflow_falls_back_to_masked(self):
        # a capacity smaller than the active count must not drop atoms —
        # the executor's lax.cond falls back to masked full windows
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        spec = g.csr.workspec()
        part = make_partition(spec, Schedule.CHUNKED, 4)
        vals = jnp.ones((spec.num_atoms,), jnp.float32)
        mask = jnp.ones((spec.num_atoms,), bool)      # everything active
        for path in PATHS:
            got = execute_scatter_reduce(
                spec, part, lambda e: vals[e], g.csr.col_indices,
                g.num_vertices, path=path, combiner="sum", atom_mask=mask,
                compact_capacity=4)
            in_deg = (np.asarray(w) > 0).sum(axis=0).astype(np.float32)
            assert_bitwise_equal(got, in_deg, str(path))

    def test_compact_windows_native_equals_pure(self):
        w = GRAPHS["zero_degree_tail"]
        g = graph_of(w)
        spec = g.csr.workspec()
        part = make_partition(spec, Schedule.CHUNKED, 3,
                              chunk_policy="round_robin")
        rng = np.random.default_rng(5)
        vals = jnp.asarray(rng.integers(-8, 9, spec.num_atoms)
                           .astype(np.float32))
        mask = jnp.asarray(rng.random(spec.num_atoms) < 0.5)
        idx, count = compact_active_atoms(mask, spec.num_atoms)
        assert int(count) == int(np.asarray(mask).sum())
        pure = blocked_compact_value_windows(spec, part, lambda e: vals[e],
                                             idx)
        native = native_compact_value_windows(spec, part, lambda e: vals[e],
                                              idx)
        assert pure.shape == native.shape
        assert_bitwise_equal(pure.reshape(-1), native.reshape(-1))

    # rungs (4096, 2048, 1024): every rung's edge, zero, and the fallback
    LADDER_COUNTS = ((0, 2), (1, 2), (1024, 2), (1025, 1), (2048, 1),
                     (2049, 0), (4096, 0), (4097, 3))

    @pytest.mark.parametrize("combiner", ["sum", "min", "max"])
    @pytest.mark.parametrize("path", PATHS, ids=str)
    def test_compact_rungs_match_masked(self, path, combiner):
        # each active count runs the smallest rung that holds it, and every
        # rung (and the fallback past the top) returns the masked bits;
        # integer values make the sums exact, so all three are bitwise
        g = graph_of(LADDER_W)
        spec = g.csr.workspec()
        part = make_partition(spec, Schedule.CHUNKED, 8)
        rungs = compact_rungs(4096)
        assert rungs == (4096, 2048, 1024)
        rng = np.random.default_rng(17)
        vals = jnp.asarray(rng.integers(-8, 9, spec.num_atoms)
                           .astype(np.float32))

        def run(mask, capacity):
            return execute_scatter_reduce(
                spec, part, vals, g.csr.col_indices, g.num_vertices,
                path=path, combiner=combiner, atom_mask=mask,
                compact_capacity=capacity)

        masked = jax.jit(lambda m: run(m, None))
        compact = jax.jit(lambda m: run(m, rungs[0]))
        order = rng.permutation(spec.num_atoms)
        for count, want_rung in self.LADDER_COUNTS:
            mask = np.zeros(spec.num_atoms, bool)
            mask[order[:count]] = True
            assert int(compact_rung_index(jnp.int32(count),
                                          jnp.asarray(rungs))) == want_rung
            assert_bitwise_equal(compact(jnp.asarray(mask)),
                                 masked(jnp.asarray(mask)),
                                 f"{path}/{combiner}/{count}")

    def test_compact_rungs_halve_down_to_one_window_tile(self):
        assert compact_rungs(1) == (1,)
        assert compact_rungs(1024) == (1024,)
        assert compact_rungs(1025) == (1025, 513)
        rungs = compact_rungs(14719535)          # kron-s20's capacity
        assert rungs[0] == 14719535 and rungs[-1] == 899
        assert all(b == -(-a // 2) for a, b in zip(rungs, rungs[1:]))
        assert all(r > 1024 for r in rungs[:-1])

    @pytest.mark.parametrize("algo,path", [("bfs", "pure"), ("bfs", "native"),
                                           ("sssp", "pure"),
                                           ("delta", "pure")])
    def test_compact_ladder_traversals_match_masked(self, algo, path):
        # push levels of a skewed graph land on three rungs of
        # (5400, 2700, 1350, 675); results equal the uncompacted plan's
        g = graph_of(LADDER_W)
        plans = {cap: build_advance(g, schedule="chunked", num_blocks=8,
                                    path=path, compact=cap,
                                    delta="auto" if algo == "delta" else None)
                 for cap in (5400, None)}
        run = {"bfs": lambda p: bfs(g, 0, plan=p, direction="push"),
               "sssp": lambda p: sssp(g, 0, plan=p, direction="push"),
               "delta": lambda p: delta_stepping(g, 0, plan=p,
                                                 direction="push")}[algo]
        got, want = run(plans[5400]), run(plans[None])
        assert_bitwise_equal(got, want, f"{algo}/{path}")
        if algo == "bfs":
            depth = np.asarray(got)
            np.testing.assert_array_equal(depth, np_bfs(LADDER_W, 0)[0])
            out_deg = (LADDER_W > 0).sum(axis=1)
            active = [int(out_deg[depth == d].sum())
                      for d in range(depth.max() + 1)]
            rungs = jnp.asarray(compact_rungs(5400))
            used = {int(compact_rung_index(jnp.int32(a), rungs))
                    for a in active}
            assert used == {0, 1, 3}, (active, used)

    def test_bfs_multi_traces_one_unbatched_rung_switch(self):
        g = graph_of(LADDER_W)
        plan = build_advance(g, schedule="chunked", num_blocks=8,
                             path="pure", compact=5400)
        sources = jnp.asarray([0, 7, 42])
        run = lambda s: bfs_multi(g, s, plan=plan, direction="push")

        def switches(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "cond":
                    yield eqn
                for param in eqn.params.values():
                    for sub in (param if isinstance(param, (list, tuple))
                                else [param]):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from switches(sub)

        rung_switches = [e for e in switches(jax.make_jaxpr(run)(sources)
                                             .jaxpr)
                         if len(e.params["branches"])
                         == len(compact_rungs(5400))]
        assert len(rung_switches) == 1
        assert rung_switches[0].invars[0].aval.shape == ()
        got = np.asarray(run(sources))
        for lane, s in enumerate(np.asarray(sources)):
            np.testing.assert_array_equal(
                got[lane], np.asarray(bfs(g, int(s), plan=plan,
                                          direction="push")))

    def test_compact_advance_push_rides_the_plan(self):
        # a plan built with compact= must keep push advances bit-identical
        # to an uncompacted plan on sparse AND saturating frontiers
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        V = g.num_vertices
        plain = build_advance(g, schedule="merge_path", num_blocks=4)
        compact = build_advance(g, schedule="merge_path", num_blocks=4,
                                compact=0.25)
        assert compact.compact_capacity == int(np.ceil(g.num_edges * 0.25))
        rng = np.random.default_rng(9)
        pot = jnp.asarray(rng.integers(0, 16, V).astype(np.float32))
        for frac in (0.1, 0.9):
            frontier = jnp.asarray(rng.random(V) < frac)
            want = advance_relax_min(plain, pot, frontier, direction="push")
            got = advance_relax_min(compact, pot, frontier,
                                    direction="push")
            assert_bitwise_equal(got, want, f"frontier {frac}")

    def test_compact_rejects_degenerate_requests(self):
        g = graph_of(GRAPHS["self_loops"])
        for bad in (0, -5):
            with pytest.raises(ValueError, match="compact capacity"):
                build_advance(g, schedule="merge_path", num_blocks=2,
                              compact=bad)
        with pytest.raises(ValueError, match="compact fraction"):
            build_advance(g, schedule="merge_path", num_blocks=2,
                          compact=1.5)
        # None/False both mean disabled, not capacity-1
        for off in (None, False):
            plan = build_advance(g, schedule="merge_path", num_blocks=2,
                                 compact=off)
            assert plan.compact_capacity is None

    def test_compact_capacity_estimate_tracks_threshold(self):
        assert estimate_compact_capacity(1000, 0.25) == \
            int(np.ceil(1000 * 0.25 * 1.25))
        assert estimate_compact_capacity(1000, 0.0) == 32      # floor
        assert estimate_compact_capacity(1000, 1.0) == 1000    # clamp to E
        assert estimate_compact_capacity(0, 0.5) == 1
        g = graph_of(GRAPHS["powerlaw"])
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             compact=True)
        assert plan.compact_capacity == estimate_compact_capacity(
            g.num_edges, plan.direction_threshold)

    def test_compact_cost_model_flattens_skew(self):
        # a hub-skewed push view: the compacted even split must be modeled
        # cheaper than masked thread-mapped windows (which pay the hub),
        # and the mode must reject pull (nothing to compact)
        g = graph_of(GRAPHS["star_hub"])
        push_spec = g.csr.workspec()
        masked = modeled_advance_cost(push_spec, "thread_mapped", 4,
                                      direction="push", density=0.3)
        compacted = modeled_advance_cost(push_spec, "thread_mapped", 4,
                                         direction="push", density=0.3,
                                         window_mode="compact")
        assert compacted < masked
        with pytest.raises(ValueError):
            modeled_advance_cost(push_spec, "thread_mapped", 4,
                                 direction="pull", window_mode="compact")
        with pytest.raises(ValueError):
            modeled_advance_cost(push_spec, "thread_mapped", 4,
                                 direction="push", window_mode="wide")

    def test_compact_delta_stepping_end_to_end(self):
        # the tentpole composition: bucketed traversal + compacted windows
        w = GRAPHS["powerlaw"]
        g = graph_of(w)
        want = np.asarray(sssp(g, 0, schedule="merge_path", num_blocks=4))
        for compact in (True, 0.5, 16, None):
            got = np.asarray(delta_stepping(g, 0, schedule="merge_path",
                                            num_blocks=4, compact=compact,
                                            direction="push"))
            assert_bitwise_equal(got, want, f"compact={compact}")


class TestSourceValidation:
    """Out-of-range sources raise at build time instead of clamping."""

    @pytest.mark.parametrize("source", [-1, 40, 1000])
    def test_bad_source_raises(self, source):
        g = graph_of(GRAPHS["powerlaw"])     # V = 40
        plan = build_advance(g, schedule="merge_path", num_blocks=4)
        for fn in (lambda: bfs(g, source, plan=plan),
                   lambda: sssp(g, source, plan=plan),
                   lambda: delta_stepping(g, source, plan=plan),
                   lambda: sssp(g, source, plan=plan, algorithm="delta")):
            with pytest.raises(ValueError, match="out of range"):
                fn()

    def test_bfs_multi_bad_batch_entry_raises(self):
        g = graph_of(GRAPHS["powerlaw"])     # V = 40
        plan = build_advance(g, schedule="merge_path", num_blocks=4)
        for sources in ([0, -1, 3], [0, 40], [-1], [0, 1, 1000]):
            with pytest.raises(ValueError, match="out of range"):
                bfs_multi(g, sources, plan=plan)
        # the all-valid batch still runs
        assert np.asarray(bfs_multi(g, [0, 39], plan=plan)).shape == (2, 40)

    def test_boundary_sources_are_valid(self):
        w = GRAPHS["self_loops"]             # V = 8
        g = graph_of(w)
        plan = build_advance(g, schedule="merge_path", num_blocks=2)
        for source in (0, 7):
            want, _ = np_bfs(w, source)
            np.testing.assert_array_equal(
                np.asarray(bfs(g, source, plan=plan)), want)


class TestEmptyGraphs:
    """V == 0 and E == 0 graphs must not crash (satellite of PR 5)."""

    @pytest.mark.parametrize("schedule", SCHEDULES)
    @pytest.mark.parametrize("path", PATHS, ids=str)
    def test_edgeless_graph_traversals(self, schedule, path):
        V = 7
        g = graph_of(np.zeros((V, V), np.float32))
        plan = build_advance(g, schedule=schedule, num_blocks=4, path=path,
                             delta="auto", compact=True)
        assert plan.num_edges == 0 and plan.delta == 1.0
        depth = np.asarray(bfs(g, 2, plan=plan))
        want_depth = np.full(V, -1); want_depth[2] = 0
        np.testing.assert_array_equal(depth, want_depth)
        dist = np.asarray(sssp(g, 2, plan=plan))
        want_dist = np.full(V, np.inf, np.float32); want_dist[2] = 0.0
        assert_bitwise_equal(dist, want_dist)
        assert_bitwise_equal(delta_stepping(g, 2, plan=plan), want_dist)
        batched = np.asarray(bfs_multi(g, [0, 6], plan=plan))
        assert batched.shape == (2, V)
        assert (batched >= 0).sum() == 2     # each source reaches itself

    def test_vertexless_graph(self):
        g = graph_of(np.zeros((0, 0), np.float32))
        assert g.num_vertices == 0 and g.num_edges == 0
        # build_advance handles the empty CSR in every direction
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             delta="auto")
        assert plan.num_edges == 0
        # there is no valid source: the validators reject every candidate
        for fn in (lambda: bfs(g, 0, plan=plan),
                   lambda: sssp(g, 0, plan=plan),
                   lambda: delta_stepping(g, 0, plan=plan)):
            with pytest.raises(ValueError):
                fn()
        # source-free entry points return empty results, like pagerank
        assert np.asarray(bfs_multi(g, [], plan=plan)).shape == (0, 0)
        assert np.asarray(pagerank(g)).shape == (0,)

    def test_edgeless_pagerank_is_uniform(self):
        V = 5
        g = graph_of(np.zeros((V, V), np.float32))
        pr = np.asarray(pagerank(g, num_iters=10))
        np.testing.assert_allclose(pr, np.full(V, 1.0 / V), rtol=1e-6)


class TestSsspDirectionCounts:
    """sssp reports (push, pull) iteration counts like bfs (parity fix)."""

    def test_sssp_direction_counts_report_the_switch(self):
        g = graph_of(GRAPHS["powerlaw"])
        plan = build_advance(g, schedule="merge_path", num_blocks=4,
                             direction_threshold=0.3)
        dist, counts = sssp(g, 0, plan=plan, direction="auto",
                            return_direction_counts=True)
        counts = np.asarray(counts)
        assert counts.sum() > 0
        assert counts[0] > 0, "push never ran"
        assert counts[1] > 0, "pull never ran"
        assert_bitwise_equal(dist, sssp(g, 0, plan=plan, direction="pull"))
        # forcing the threshold to the extremes pins the direction
        for thr, idx in ((0.0, 0), (1.0, 1)):
            p = build_advance(g, schedule="merge_path", num_blocks=4,
                              direction_threshold=thr)
            _, c = sssp(g, 0, plan=p, direction="auto",
                        return_direction_counts=True)
            assert np.asarray(c)[idx] == 0, (thr, np.asarray(c))

    def test_sssp_fixed_direction_counts_are_pinned(self):
        g = graph_of(GRAPHS["self_loops"])
        plan = build_advance(g, schedule="merge_path", num_blocks=2)
        _, c_push = sssp(g, 0, plan=plan, direction="push",
                         return_direction_counts=True)
        _, c_pull = sssp(g, 0, plan=plan, direction="pull",
                         return_direction_counts=True)
        assert np.asarray(c_push)[1] == 0 and np.asarray(c_push)[0] > 0
        assert np.asarray(c_pull)[0] == 0 and np.asarray(c_pull)[1] > 0
