"""Sharded advance conformance: multi-device == single-device == oracle.

The sharded plan pair (:mod:`repro.sparse.shard`) must be a *pure
decomposition*: partitioning the vertex set over a ``("shard",)`` mesh,
exchanging frontier halos with collectives, and recombining per-shard
results must reproduce the single-device drivers **bitwise** — same
reduction order per destination (the contiguous-slice property), same
direction switches (the density threshold is computed globally), same
f32 rounding in every relaxation.

Run the full matrix on forced host devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_shard_advance.py

On a single device the multi-shard cases skip and the suite degrades to
the 1-shard == unsharded contract plus construction/validation logic.

``REPRO_TEST_BOUNDARY`` (default ``equal_width``) selects the boundary
schedule the main acceptance matrix builds with — CI's ``multi-device``
job runs the whole file once per registered schedule.  The
``TestBoundarySchedules`` class additionally sweeps all schedules
unconditionally, so even a single matrix leg covers every one.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import Schedule
from repro.core.balance import modeled_sharded_cost
from repro.launch.mesh import make_graph_mesh
from repro.sparse import (CSR, SHARD_SCHEDULES, Graph, ShardedAdvancePlan,
                          bfs, bfs_multi, build_advance,
                          build_sharded_advance, delta_stepping, pagerank,
                          shard_boundaries, sharded_bfs, sharded_bfs_multi,
                          sharded_delta_stepping, sharded_pagerank,
                          sharded_sssp, sssp)
from _conformance import (SCHEDULE_PATH_CASES, adversarial_graphs,
                          assert_bitwise_equal, np_bfs, np_delta_stepping,
                          np_pagerank, np_sssp, powerlaw_graph_dense,
                          shard_slices)

_NDEV = len(jax.devices())
_BOUNDARY = os.environ.get("REPRO_TEST_BOUNDARY", "equal_width")
assert _BOUNDARY in SHARD_SCHEDULES, _BOUNDARY


def _counts(*counts):
    """Parametrize over shard counts, skipping those the host can't mesh."""
    return [pytest.param(s, marks=pytest.mark.skipif(
        _NDEV < s, reason=f"needs {s} devices ({_NDEV} available)"),
        id=f"s{s}") for s in counts]


MULTI_COUNTS = _counts(2, 4, 8)
ALL_COUNTS = _counts(1, 2, 4, 8)

_WEIGHTS = powerlaw_graph_dense(24, avg_degree=3.0, seed=7)
_GRAPH = Graph(CSR.from_dense(_WEIGHTS))


def _build(graph, num_shards, **kw):
    """Build a sharded plan under the CI matrix's boundary schedule."""
    kw.setdefault("shard_schedule", _BOUNDARY)
    return build_sharded_advance(graph, num_shards, **kw)


def _dyadic_weights(V: int = 32, seed: int = 1) -> np.ndarray:
    """Unit weights, power-of-two out-degrees: PageRank stays dyadic, so
    the damping=0.5 power iteration is bit-exact in any summation order."""
    rng = np.random.default_rng(seed)
    deg = 2 ** rng.integers(0, 3, V)
    w = np.zeros((V, V), np.float32)
    for i in range(V):
        cols = rng.choice([c for c in range(V) if c != i], size=deg[i],
                          replace=False)
        w[i, cols] = 1.0
    return w


class TestShardedMatchesSingleDevice:
    """The CI acceptance matrix: >= 3 shard counts x all 6 schedules x
    both execution paths, bit-identical to the single-device drivers."""

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    @pytest.mark.parametrize("schedule,path", SCHEDULE_PATH_CASES)
    def test_bfs_bitwise(self, num_shards, schedule, path):
        splan = _build(_GRAPH, num_shards, schedule=schedule, path=path,
                       num_blocks=4)
        want_d, want_p = bfs(_GRAPH, 0, schedule=schedule, path=path,
                             num_blocks=4, return_parents=True)
        got_d, got_p = sharded_bfs(splan, 0, return_parents=True)
        np.testing.assert_array_equal(got_d, want_d)
        np.testing.assert_array_equal(got_p, want_p)
        oracle_d, oracle_p = np_bfs(_WEIGHTS, 0)
        np.testing.assert_array_equal(got_d, oracle_d)
        np.testing.assert_array_equal(got_p, oracle_p)

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    @pytest.mark.parametrize("schedule,path", SCHEDULE_PATH_CASES)
    def test_sssp_bitwise(self, num_shards, schedule, path):
        splan = _build(_GRAPH, num_shards, schedule=schedule, path=path,
                       num_blocks=4)
        want = sssp(_GRAPH, 0, schedule=schedule, path=path, num_blocks=4)
        got = sharded_sssp(splan, 0)
        assert_bitwise_equal(got, want, f"sssp s{num_shards} {schedule}")
        np.testing.assert_allclose(np.asarray(got), np_sssp(_WEIGHTS, 0),
                                   rtol=1e-6)

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    @pytest.mark.parametrize("schedule,path", SCHEDULE_PATH_CASES)
    def test_pagerank_dyadic_bitwise(self, num_shards, schedule, path):
        w = _dyadic_weights()
        g = Graph(CSR.from_dense(w))
        splan = _build(g, num_shards, schedule=schedule, path=path,
                       num_blocks=4)
        want = pagerank(g, damping=0.5, num_iters=3, tol=0.0,
                        schedule=schedule, path=path, num_blocks=4)
        got = sharded_pagerank(splan, damping=0.5, num_iters=3, tol=0.0)
        assert_bitwise_equal(got, want, f"pagerank s{num_shards} {schedule}")

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    @pytest.mark.parametrize("direction", ["auto", "pull", "push"])
    def test_direction_policies_bitwise(self, num_shards, direction):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        want_d = bfs(_GRAPH, 0, schedule="merge_path", path="pure",
                     num_blocks=4, direction=direction)
        got_d = sharded_bfs(splan, 0, direction=direction)
        np.testing.assert_array_equal(got_d, want_d)
        want_s = sssp(_GRAPH, 0, schedule="merge_path", path="pure",
                      num_blocks=4, direction=direction)
        assert_bitwise_equal(sharded_sssp(splan, 0, direction=direction),
                             want_s, f"sssp dir={direction}")


class TestShardedDeltaStepping:
    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    @pytest.mark.parametrize("schedule,path",
                             [("merge_path", "pure"), ("chunked", "native"),
                              ("group_mapped", "pure")])
    def test_delta_bitwise_vs_single_device(self, num_shards, schedule, path):
        splan = _build(_GRAPH, num_shards, schedule=schedule, path=path,
                       num_blocks=4, delta="auto")
        want = delta_stepping(_GRAPH, 0, schedule=schedule, path=path,
                              num_blocks=4, compact=None)
        got = sharded_delta_stepping(splan, 0)
        assert_bitwise_equal(got, want, f"delta s{num_shards} {schedule}")
        assert_bitwise_equal(got, np_delta_stepping(_WEIGHTS, 0),
                             "delta vs oracle")

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    def test_explicit_delta_width(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4, delta=3.0)
        want = delta_stepping(_GRAPH, 0, delta=3.0, schedule="merge_path",
                              path="pure", num_blocks=4, compact=None)
        assert_bitwise_equal(sharded_delta_stepping(splan, 0, delta=3.0),
                             want, "explicit delta width")
        assert_bitwise_equal(sharded_delta_stepping(splan, 0, delta=3.0),
                             np_delta_stepping(_WEIGHTS, 0, 3.0),
                             "explicit delta vs oracle")

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    def test_with_delta_rebuilds_light_masks(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        assert splan.delta is None
        widened = splan.with_delta(None)     # None -> estimate from weights
        assert widened.delta is not None and widened.delta > 0
        want = delta_stepping(_GRAPH, 0, schedule="merge_path", path="pure",
                              num_blocks=4, compact=None)
        assert_bitwise_equal(sharded_delta_stepping(widened, 0), want,
                             "with_delta rebuild")


class TestMeshGlobalCompactCapacity:
    """``compact=`` must resolve against the *global* edge count, exactly
    as the single-device builder resolves it — not against any per-shard
    padded edge count, which varies with the mesh size (PR 7 remainder)."""

    @pytest.mark.parametrize("num_shards", ALL_COUNTS)
    @pytest.mark.parametrize("compact", [True, 0.25, 17],
                             ids=["auto", "fraction", "explicit"])
    def test_capacity_matches_single_device(self, num_shards, compact):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4, compact=compact)
        want = build_advance(_GRAPH, schedule="merge_path", path="pure",
                             num_blocks=4, compact=compact).compact_capacity
        assert splan.template.compact_capacity == want

    @pytest.mark.parametrize("compact", [0.0, 1.5, 0, -3],
                             ids=["zero-frac", "over-frac", "zero", "neg"])
    def test_invalid_compact_rejected(self, compact):
        with pytest.raises(ValueError):
            build_sharded_advance(_GRAPH, 1, schedule="merge_path",
                                  path="pure", num_blocks=4, compact=compact)

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    def test_compacted_delta_bitwise(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4, delta="auto",
                       compact=True)
        want = delta_stepping(_GRAPH, 0, schedule="merge_path", path="pure",
                              num_blocks=4, compact=True)
        assert_bitwise_equal(sharded_delta_stepping(splan, 0), want,
                             f"compacted delta s{num_shards}")


class TestShardedCompactLadder:
    """Each shard runs the compaction rung its own active count needs,
    inside ``shard_map``; the combining collective stays outside the
    switch, so sharded push traversals keep the single-device bits."""

    @pytest.mark.parametrize("num_shards", ALL_COUNTS)
    def test_push_traversals_bitwise(self, num_shards):
        # 1,500 vertices, 8,463 edges: push levels of 1 to 4,933 active
        # edges span the rungs of (2200, 1100, 550) and the fallback
        w = powerlaw_graph_dense(1500, avg_degree=6.0, seed=4)
        g = Graph(CSR.from_dense(w))
        splan = _build(g, num_shards, schedule="chunked", path="pure",
                       num_blocks=8, compact=2200, delta="auto")
        assert splan.template.compact_capacity == 2200
        plan = build_advance(g, schedule="chunked", path="pure",
                             num_blocks=8, compact=None, delta="auto")
        assert_bitwise_equal(
            sharded_bfs(splan, 0, direction="push"),
            bfs(g, 0, plan=plan, direction="push"), f"bfs s{num_shards}")
        assert_bitwise_equal(
            sharded_delta_stepping(splan, 0, direction="push"),
            delta_stepping(g, 0, plan=plan, direction="push"),
            f"delta s{num_shards}")

    @pytest.mark.skipif(_NDEV > 1, reason="the mesh cases run in-process")
    def test_push_traversals_bitwise_on_four_host_devices(self):
        # the parametrized test above on four forced host devices
        here = pathlib.Path(__file__).resolve()
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "-p", "no:xdist", "-p", "no:randomly",
             f"{here}::TestShardedCompactLadder::test_push_traversals_bitwise"],
            cwd=here.parent.parent, env=env, capture_output=True, text=True,
            timeout=900)
        assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
        assert "3 passed, 1 skipped" in proc.stdout, proc.stdout[-2000:]


class TestShardedPagerank:
    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    def test_pagerank_close_general_graph(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        want = pagerank(_GRAPH, num_iters=12, schedule="merge_path",
                        path="pure", num_blocks=4)
        got = sharded_pagerank(splan, num_iters=12)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(got),
                                   np_pagerank(_WEIGHTS, num_iters=12),
                                   rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    def test_pagerank_mass_conserved(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        got = np.asarray(sharded_pagerank(splan, num_iters=20))
        assert got.shape == (_GRAPH.csr.shape[0],)
        np.testing.assert_allclose(got.sum(), 1.0, rtol=1e-5)


class TestPerShardOwnership:
    """Each device's slice of the result equals the oracle's slice — the
    halo exchange never leaks another shard's vertices into local state."""

    @pytest.mark.parametrize("num_shards", ALL_COUNTS)
    def test_bfs_slices_match_oracle_slices(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        got = np.asarray(sharded_bfs(splan, 0))
        oracle_d, _ = np_bfs(_WEIGHTS, 0)
        V = _WEIGHTS.shape[0]
        slices = shard_slices(V, num_shards)
        assert sum(hi - lo for lo, hi in slices) == V
        for lo, hi in slices:
            np.testing.assert_array_equal(got[lo:hi], oracle_d[lo:hi])

    @pytest.mark.parametrize("num_shards", ALL_COUNTS)
    def test_local_views_cover_every_edge_exactly_once(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        E = _GRAPH.csr.nnz
        assert int(np.asarray(splan.arrays["pull_valid"]).sum()) == E
        assert int(np.asarray(splan.arrays["push_valid"]).sum()) == E
        out_deg = np.asarray(splan.arrays["out_degrees"])
        assert int(out_deg.sum()) == E


class TestAdversarialGraphs:
    @pytest.mark.parametrize("num_shards", _counts(4))
    @pytest.mark.parametrize("name", sorted(adversarial_graphs()))
    def test_bfs_sssp_bitwise(self, name, num_shards):
        w = adversarial_graphs()[name]
        g = Graph(CSR.from_dense(w))
        splan = build_sharded_advance(g, num_shards, schedule="group_mapped",
                                      path="pure", num_blocks=4)
        np.testing.assert_array_equal(
            sharded_bfs(splan, 0),
            bfs(g, 0, schedule="group_mapped", path="pure", num_blocks=4))
        assert_bitwise_equal(
            sharded_sssp(splan, 0),
            sssp(g, 0, schedule="group_mapped", path="pure", num_blocks=4),
            name)

    @pytest.mark.parametrize("num_shards", _counts(8))
    def test_graph_smaller_than_mesh(self, num_shards):
        """V=5 over 8 shards: trailing shards hold only padding."""
        w = powerlaw_graph_dense(5, avg_degree=2.0, seed=3)
        g = Graph(CSR.from_dense(w))
        splan = build_sharded_advance(g, num_shards, schedule="merge_path",
                                      path="pure")
        assert splan.num_shards == num_shards
        np.testing.assert_array_equal(
            sharded_bfs(splan, 0),
            bfs(g, 0, schedule="merge_path", path="pure"))
        assert_bitwise_equal(sharded_sssp(splan, 0),
                             sssp(g, 0, schedule="merge_path", path="pure"),
                             "tiny graph sssp")

    @pytest.mark.parametrize("num_shards", _counts(2))
    def test_single_vertex_graph(self, num_shards):
        g = Graph(CSR.from_dense(np.zeros((1, 1), np.float32)))
        splan = build_sharded_advance(g, num_shards, schedule="merge_path",
                                      path="pure")
        np.testing.assert_array_equal(sharded_bfs(splan, 0), [0])


class TestOneShardMatchesUnsharded:
    """The recursion's base case, runnable on any device count: a 1-shard
    mesh must be a bitwise no-op relative to the unsharded drivers."""

    @pytest.mark.parametrize("schedule,path", SCHEDULE_PATH_CASES)
    def test_bfs_sssp_bitwise(self, schedule, path):
        splan = _build(_GRAPH, 1, schedule=schedule, path=path,
                       num_blocks=4)
        want_d, want_p = bfs(_GRAPH, 0, schedule=schedule, path=path,
                             num_blocks=4, return_parents=True)
        got_d, got_p = sharded_bfs(splan, 0, return_parents=True)
        np.testing.assert_array_equal(got_d, want_d)
        np.testing.assert_array_equal(got_p, want_p)
        assert_bitwise_equal(
            sharded_sssp(splan, 0),
            sssp(_GRAPH, 0, schedule=schedule, path=path, num_blocks=4),
            f"1-shard sssp {schedule}@{path}")

    def test_threshold_matches_unsharded_inspector(self):
        splan = build_sharded_advance(_GRAPH, 1, schedule="merge_path",
                                      path="pure", num_blocks=4)
        plan = build_advance(_GRAPH, schedule="merge_path", path="pure",
                             num_blocks=4)
        assert splan.direction_threshold == plan.direction_threshold


class TestShardedBfsMulti:
    @pytest.mark.parametrize("num_shards", ALL_COUNTS)
    def test_batched_sources_bitwise(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        sources = [0, 5, 11]
        want = bfs_multi(_GRAPH, sources, schedule="merge_path", path="pure",
                         num_blocks=4)
        got = sharded_bfs_multi(splan, sources)
        np.testing.assert_array_equal(got, want)
        for i, s in enumerate(sources):
            np.testing.assert_array_equal(np.asarray(got)[i],
                                          np_bfs(_WEIGHTS, s)[0])


class TestDriverMeshDispatch:
    """``mesh=`` on the top-level drivers routes through the sharded path."""

    @pytest.mark.parametrize("num_shards", _counts(2))
    def test_bfs_mesh_kwarg(self, num_shards):
        mesh = make_graph_mesh(num_shards)
        np.testing.assert_array_equal(
            bfs(_GRAPH, 0, mesh=mesh, schedule="merge_path", path="pure",
                num_blocks=4),
            bfs(_GRAPH, 0, schedule="merge_path", path="pure", num_blocks=4))

    @pytest.mark.parametrize("num_shards", _counts(2))
    def test_sssp_prebuilt_plan(self, num_shards):
        splan = _build(_GRAPH, num_shards, schedule="merge_path",
                       path="pure", num_blocks=4)
        assert isinstance(splan, ShardedAdvancePlan)
        assert_bitwise_equal(
            sssp(_GRAPH, 0, plan=splan),
            sssp(_GRAPH, 0, schedule="merge_path", path="pure", num_blocks=4),
            "prebuilt sharded plan via sssp driver")

    @pytest.mark.parametrize("num_shards", _counts(2))
    def test_pagerank_and_delta_mesh_kwarg(self, num_shards):
        mesh = make_graph_mesh(num_shards)
        np.testing.assert_allclose(
            np.asarray(pagerank(_GRAPH, num_iters=8, mesh=mesh,
                                schedule="merge_path", path="pure",
                                num_blocks=4)),
            np.asarray(pagerank(_GRAPH, num_iters=8, schedule="merge_path",
                                path="pure", num_blocks=4)),
            rtol=1e-6, atol=1e-7)
        assert_bitwise_equal(
            delta_stepping(_GRAPH, 0, mesh=mesh, schedule="merge_path",
                           path="pure", num_blocks=4, compact=None),
            delta_stepping(_GRAPH, 0, schedule="merge_path", path="pure",
                           num_blocks=4, compact=None),
            "delta_stepping mesh kwarg")

    def test_mesh_with_wrong_plan_type_raises(self):
        plan = build_advance(_GRAPH, schedule="merge_path", path="pure",
                             num_blocks=4)
        mesh = make_graph_mesh(1)
        with pytest.raises(TypeError):
            bfs(_GRAPH, 0, plan=plan, mesh=mesh)


class TestConstructionValidation:
    def test_make_graph_mesh_rejects_bad_counts(self):
        with pytest.raises(ValueError):
            make_graph_mesh(0)
        with pytest.raises(ValueError):
            make_graph_mesh(_NDEV + 1)

    def test_build_rejects_nonpositive_counts(self):
        with pytest.raises(ValueError):
            build_sharded_advance(_GRAPH, 0)
        with pytest.raises(ValueError):
            build_sharded_advance(_GRAPH, -2)

    @pytest.mark.skipif(_NDEV < 2, reason="needs a 2-axis mesh")
    def test_build_rejects_multi_axis_mesh(self):
        from jax.sharding import Mesh
        devs = np.asarray(jax.devices()[:2]).reshape(2, 1)
        bad = Mesh(devs, ("a", "b"))
        with pytest.raises(ValueError):
            build_sharded_advance(_GRAPH, bad)

    def test_auto_selection_returns_valid_plan(self):
        splan = build_sharded_advance(_GRAPH, None, schedule="auto")
        assert splan.num_shards >= 1
        assert splan.num_shards <= _NDEV
        np.testing.assert_array_equal(
            sharded_bfs(splan, 0),
            bfs(_GRAPH, 0, schedule=splan.schedule, path=splan.path))


def _hub_graph(V: int = 16384) -> Graph:
    """A planted-hub digraph, built directly in CSR form: a ring plus an
    in-hub (every vertex points at vertex 0), so the pull view's tile 0
    owns ~V atoms while every other tile owns 1 — the skew equal-width
    boundaries pay max-over-shards cost for."""
    rows = np.concatenate([np.arange(V), np.arange(1, V)])
    cols = np.concatenate([(np.arange(V) + 1) % V, np.zeros(V - 1, np.int64)])
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    roff = np.cumsum(np.bincount(rows + 1, minlength=V + 1))
    return Graph(CSR(jnp.asarray(roff, jnp.int32), jnp.asarray(cols, jnp.int32),
                     jnp.ones(len(cols), jnp.float32), (V, V), len(cols)))


class TestBoundarySchedules:
    """Every registered boundary schedule, swept unconditionally (no env
    matrix needed): contiguous uneven shards must stay bitwise-identical
    to single-device, own every edge exactly once, and keep equal_width's
    layout byte-identical to the pre-boundary-schedule identity."""

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    @pytest.mark.parametrize("boundary", sorted(SHARD_SCHEDULES))
    def test_bfs_sssp_delta_bitwise(self, boundary, num_shards):
        splan = build_sharded_advance(_GRAPH, num_shards,
                                      schedule="merge_path", path="pure",
                                      num_blocks=4, shard_schedule=boundary,
                                      delta="auto")
        assert splan.shard_schedule == boundary
        want_d, want_p = bfs(_GRAPH, 0, schedule="merge_path", path="pure",
                             num_blocks=4, return_parents=True)
        got_d, got_p = sharded_bfs(splan, 0, return_parents=True)
        np.testing.assert_array_equal(got_d, want_d)
        np.testing.assert_array_equal(got_p, want_p)
        assert_bitwise_equal(
            sharded_sssp(splan, 0),
            sssp(_GRAPH, 0, schedule="merge_path", path="pure", num_blocks=4),
            f"sssp {boundary} s{num_shards}")
        assert_bitwise_equal(
            sharded_delta_stepping(splan, 0),
            delta_stepping(_GRAPH, 0, schedule="merge_path", path="pure",
                           num_blocks=4, compact=None),
            f"delta {boundary} s{num_shards}")

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    @pytest.mark.parametrize("boundary", sorted(SHARD_SCHEDULES))
    def test_edges_owned_exactly_once(self, boundary, num_shards):
        splan = build_sharded_advance(_GRAPH, num_shards,
                                      schedule="merge_path", path="pure",
                                      num_blocks=4, shard_schedule=boundary)
        E = _GRAPH.csr.nnz
        assert int(np.asarray(splan.arrays["pull_valid"]).sum()) == E
        assert int(np.asarray(splan.arrays["push_valid"]).sum()) == E
        assert int(np.asarray(splan.arrays["out_degrees"]).sum()) == E
        bounds = np.asarray(splan.boundaries)
        assert bounds[0] == 0 and bounds[-1] == _GRAPH.num_vertices
        assert (np.diff(bounds) >= 0).all()

    @pytest.mark.parametrize("num_shards", ALL_COUNTS)
    def test_equal_width_permutation_is_identity(self, num_shards):
        """The byte-identity guard: the default layout's global<->padded
        maps must be the identity, so equal_width plans index, gather, and
        slice exactly as the pre-boundary-schedule implementation did."""
        splan = build_sharded_advance(_GRAPH, num_shards,
                                      schedule="merge_path", path="pure",
                                      num_blocks=4)
        assert splan.shard_schedule == "equal_width"
        ident = np.arange(splan.padded_vertices, dtype=np.int32)
        np.testing.assert_array_equal(np.asarray(splan.glob2pad), ident)
        np.testing.assert_array_equal(np.asarray(splan.pad2glob), ident)
        np.testing.assert_array_equal(
            np.asarray(splan.boundaries),
            [min(s * splan.shard_size, _GRAPH.num_vertices)
             for s in range(num_shards + 1)])

    @pytest.mark.parametrize("boundary", ["edge_balanced", "lpt_contiguous"])
    def test_driver_shard_schedule_kwarg(self, boundary):
        if _NDEV < 2:
            pytest.skip("needs 2 devices")
        mesh = make_graph_mesh(2)
        np.testing.assert_array_equal(
            bfs(_GRAPH, 0, mesh=mesh, shard_schedule=boundary,
                schedule="merge_path", path="pure", num_blocks=4),
            bfs(_GRAPH, 0, schedule="merge_path", path="pure", num_blocks=4))
        assert_bitwise_equal(
            sssp(_GRAPH, 0, mesh=mesh, shard_schedule=boundary,
                 schedule="merge_path", path="pure", num_blocks=4),
            sssp(_GRAPH, 0, schedule="merge_path", path="pure", num_blocks=4),
            f"sssp driver shard_schedule={boundary}")

    @pytest.mark.parametrize("boundary", ["edge_balanced", "lpt_contiguous"])
    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    def test_pad_atoms_spread_over_empty_slots(self, boundary, num_shards):
        """Uneven boundaries must not dump all padding atoms into one pad
        segment: a monolithic pad tile (plus the narrow shards' long runs
        of zero-atom slots) inflates the blocked executor's static
        window/local-tile maxima, and the mesh-uniform statics impose that
        worst block shape on every shard — a multiple of the advance cost
        for nothing.  Padding is masked, so the only contract on its
        placement is balance: no tile's segment may exceed the even split
        of the shard's pad atoms over its empty slots + pad tile."""
        splan = build_sharded_advance(_GRAPH, num_shards,
                                      schedule="merge_path", path="pure",
                                      num_blocks=4, shard_schedule=boundary)
        bounds = np.asarray(splan.boundaries)
        for s in range(splan.num_shards):
            spec = jax.tree_util.tree_unflatten(
                splan.pull_spec_treedef,
                [l[s] for l in splan.pull_spec_leaves])
            counts = np.diff(np.asarray(spec.tile_offsets))
            width = int(bounds[s + 1] - bounds[s])
            pad_counts = counts[width:]
            if pad_counts.size == 0:
                continue
            cap = -(-int(pad_counts.sum()) // pad_counts.size)
            assert pad_counts.max() <= cap, (
                f"shard {s}: pad segment {pad_counts.max()} exceeds even "
                f"split {cap} over {pad_counts.size} padding tiles")

    @pytest.mark.parametrize("num_shards", MULTI_COUNTS)
    def test_bfs_multi_and_pagerank_uneven(self, num_shards):
        splan = build_sharded_advance(_GRAPH, num_shards,
                                      schedule="merge_path", path="pure",
                                      num_blocks=4,
                                      shard_schedule="edge_balanced")
        np.testing.assert_array_equal(
            sharded_bfs_multi(splan, [0, 5, 11]),
            bfs_multi(_GRAPH, [0, 5, 11], schedule="merge_path", path="pure",
                      num_blocks=4))
        np.testing.assert_allclose(
            np.asarray(sharded_pagerank(splan, num_iters=8)),
            np.asarray(pagerank(_GRAPH, num_iters=8, schedule="merge_path",
                                path="pure", num_blocks=4)),
            rtol=1e-6, atol=1e-7)


class TestBoundaryCostModel:
    """The planted-hub cost-model contract: degree-aware boundaries must
    strictly lower the modeled max-shard cost the autotuner ranks on."""

    @pytest.mark.parametrize("num_shards", [2, 4, 8])
    def test_edge_balanced_strictly_beats_equal_width_on_hub(self,
                                                             num_shards):
        g = _hub_graph()
        spec = g.csr.transpose().workspec()
        costs = {}
        for name in ("equal_width", "edge_balanced", "lpt_contiguous"):
            bounds = shard_boundaries(g, num_shards, name)
            costs[name] = modeled_sharded_cost(
                spec, Schedule.MERGE_PATH, 3, path="pure", atom_work=2,
                halo_elems=g.num_vertices, boundaries=bounds)
        assert costs["edge_balanced"] < costs["equal_width"], costs
        assert costs["lpt_contiguous"] <= costs["edge_balanced"], costs

    def test_boundaries_cover_and_balance(self):
        g = _hub_graph(4096)
        roff = np.asarray(g.csr.row_offsets)
        rev_roff = np.asarray(g.csr.transpose().row_offsets)
        loads = np.diff(roff) + np.diff(rev_roff) + 1
        for name in SHARD_SCHEDULES:
            b = shard_boundaries(g, 4, name)
            assert b[0] == 0 and b[-1] == g.num_vertices
            assert (np.diff(b) >= 0).all()
        eq = shard_boundaries(g, 4, "equal_width")
        eb = shard_boundaries(g, 4, "edge_balanced")
        seg = lambda bb: max(loads[lo:hi].sum()
                             for lo, hi in zip(bb[:-1], bb[1:]))
        assert seg(eb) < seg(eq)


class TestNumShardsValidation:
    """Degree-aware schedules reject S > V outright (there is no
    contiguous non-degenerate split); equal_width keeps the documented
    all-empty-trailing-shards contract."""

    def test_degree_aware_rejects_more_shards_than_vertices(self):
        w = powerlaw_graph_dense(5, avg_degree=2.0, seed=3)
        g = Graph(CSR.from_dense(w))
        for name in ("edge_balanced", "lpt_contiguous"):
            with pytest.raises(ValueError, match=r"V=5.*S=8"):
                shard_boundaries(g, 8, name)
        if _NDEV >= 8:
            for name in ("edge_balanced", "lpt_contiguous"):
                with pytest.raises(ValueError, match=r"V=5.*S=8"):
                    build_sharded_advance(g, 8, schedule="merge_path",
                                          path="pure", shard_schedule=name)

    def test_unknown_shard_schedule_rejected(self):
        with pytest.raises(ValueError, match="unknown shard schedule"):
            build_sharded_advance(_GRAPH, 1, schedule="merge_path",
                                  path="pure", shard_schedule="bogus")
        with pytest.raises(ValueError, match="unknown shard schedule"):
            shard_boundaries(_GRAPH, 2, "bogus")

    @pytest.mark.parametrize("num_shards", _counts(8))
    def test_equal_width_keeps_small_graph_contract(self, num_shards):
        """V=5 over 8 equal-width shards stays legal (trailing padding)."""
        w = powerlaw_graph_dense(5, avg_degree=2.0, seed=3)
        g = Graph(CSR.from_dense(w))
        splan = build_sharded_advance(g, num_shards, schedule="merge_path",
                                      path="pure",
                                      shard_schedule="equal_width")
        np.testing.assert_array_equal(
            sharded_bfs(splan, 0),
            bfs(g, 0, schedule="merge_path", path="pure"))

    def test_auto_boundary_on_small_graph_falls_back(self):
        """Joint auto-selection over a mesh wider than the graph must not
        crash on the degree-aware candidates — they are skipped, and the
        equal_width fallback survives."""
        w = powerlaw_graph_dense(5, avg_degree=2.0, seed=3)
        g = Graph(CSR.from_dense(w))
        splan = build_sharded_advance(g, None, schedule="merge_path",
                                      path="pure", shard_schedule="auto")
        assert splan.num_shards >= 1
        np.testing.assert_array_equal(
            sharded_bfs(splan, 0),
            bfs(g, 0, schedule="merge_path", path="pure"))
