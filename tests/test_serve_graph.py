"""Continuous-batching graph serving: bitwise identity + no-retrace.

The serving contract (serve/graph.py) is that every query retired off the
lane batch carries exactly the bits the single-query driver would have
produced for it — regardless of admission order, lane width, kind mix, or
where retire/backfill boundaries fall — and that the whole stream is
served with exactly ONE trace of the step and admit functions.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sparse import CSR, Graph
from repro.sparse.graph import bfs, pagerank, sssp
from repro.serve.graph import GraphServer


def _graph(seed=0, V=20, density=0.18):
    rng = np.random.default_rng(seed)
    w = np.where(rng.random((V, V)) < density,
                 rng.random((V, V)).astype(np.float32) + 0.1,
                 0.0).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    return Graph(CSR.from_dense(w))


def test_optimization_barrier_batches_under_vmap():
    # the PageRank body pins its rounding with optimization_barrier; it
    # must stay usable under vmap (jax's own batching rule, no patch)
    x = jnp.arange(12.0, dtype=jnp.float32).reshape(3, 4)
    out = jax.vmap(lambda r: jax.lax.optimization_barrier((r * 2, r + 1)))(x)
    np.testing.assert_array_equal(out[0], np.asarray(x) * 2)
    np.testing.assert_array_equal(out[1], np.asarray(x) + 1)


def _driver_answer(g, plan, kind, source, direction="pull"):
    if kind == "bfs":
        return np.asarray(bfs(g, source, plan=plan, direction=direction))
    if kind == "sssp":
        return np.asarray(sssp(g, source, plan=plan, direction=direction))
    return np.asarray(pagerank(g, plan=plan, direction=direction))


def _assert_bitwise(server, results, queries, qids, direction="pull"):
    g = server.graph
    for qid, q in zip(qids, queries):
        kind, source = (q, 0) if isinstance(q, str) else q
        r = results[qid]
        ref = _driver_answer(g, server.plan, kind, source, direction)
        got = np.asarray(r.value)
        assert got.dtype == ref.dtype, (kind, got.dtype, ref.dtype)
        np.testing.assert_array_equal(
            got, ref, err_msg=f"qid {qid} kind {kind} source {source}")


MIXED = [("bfs", 5), ("sssp", 2), "pagerank", ("bfs", 0),
         "pagerank", ("sssp", 7), ("bfs", 11)]


class TestBitwiseIdentity:
    def test_mixed_stream_matches_drivers(self):
        srv = GraphServer(_graph(), lanes=3)
        qids = [srv.submit(*(q if isinstance(q, tuple) else (q,)))
                for q in MIXED]
        results = {r.qid: r for r in srv.drain()}
        assert len(results) == len(MIXED)
        _assert_bitwise(srv, results, MIXED, qids)

    @pytest.mark.parametrize("order", [
        list(range(7)), list(reversed(range(7))), [3, 0, 6, 2, 5, 1, 4]])
    def test_admission_order_invariant(self, order):
        g = _graph(seed=1)
        srv = GraphServer(g, lanes=2)
        queries = [MIXED[i] for i in order]
        qids = [srv.submit(*(q if isinstance(q, tuple) else (q,)))
                for q in queries]
        results = {r.qid: r for r in srv.drain()}
        _assert_bitwise(srv, results, queries, qids)

    @pytest.mark.parametrize("lanes", [1, 2, 7, 16])
    def test_lane_width_invariant(self, lanes):
        g = _graph(seed=2)
        srv = GraphServer(g, lanes=lanes)
        qids = [srv.submit(*(q if isinstance(q, tuple) else (q,)))
                for q in MIXED]
        results = {r.qid: r for r in srv.drain()}
        _assert_bitwise(srv, results, MIXED, qids)

    def test_more_queries_than_lanes_backfills(self):
        # 12 queries through 2 lanes forces repeated retire/backfill
        # boundaries mid-stream; every answer must still be driver bits
        g = _graph(seed=3, V=16)
        srv = GraphServer(g, lanes=2)
        queries = [("bfs", i) for i in range(5)] + \
                  [("sssp", i) for i in range(5)] + ["pagerank", "pagerank"]
        qids = [srv.submit(*(q if isinstance(q, tuple) else (q,)))
                for q in queries]
        results = {r.qid: r for r in srv.drain()}
        assert len(results) == 12 and srv.served == 12
        _assert_bitwise(srv, results, queries, qids)

    def test_staggered_arrivals_mid_flight(self):
        # submissions interleaved with ticks: lanes free up and are
        # backfilled while earlier queries are still converging
        g = _graph(seed=4)
        srv = GraphServer(g, lanes=2)
        queries = [("bfs", 3), ("sssp", 1), "pagerank", ("bfs", 9)]
        qids, results = [], {}
        for q in queries:
            qids.append(srv.submit(*(q if isinstance(q, tuple) else (q,))))
            for r in srv.tick():
                results[r.qid] = r
        for r in srv.drain():
            results[r.qid] = r
        _assert_bitwise(srv, results, queries, qids)

    def test_auto_direction_matches_auto_driver(self):
        # direction="auto" switches per-lane on the measured density
        # carry; min-combiner relax is exact in both directions, so the
        # served bits still match the auto driver's
        g = _graph(seed=5)
        srv = GraphServer(g, lanes=2, direction="auto")
        queries = [("bfs", 2), ("sssp", 6)]
        qids = [srv.submit(*q) for q in queries]
        results = {r.qid: r for r in srv.drain()}
        _assert_bitwise(srv, results, queries, qids, direction="auto")


class TestLifecycle:
    def test_empty_stream(self):
        srv = GraphServer(_graph(), lanes=2)
        assert srv.drain() == []
        assert srv.serve([]) == {}
        assert srv.steps == 0 and srv.served == 0

    def test_single_trace_across_whole_stream(self):
        srv = GraphServer(_graph(seed=6), lanes=2)
        srv.serve(MIXED)
        assert srv.step_traces == 1, "serving step re-traced"
        assert srv.admit_traces == 1, "admit re-traced"

    def test_single_trace_across_separate_streams(self):
        # a second wave of queries reuses the same compiled step/admit
        srv = GraphServer(_graph(seed=7), lanes=2)
        srv.serve([("bfs", 1), "pagerank"])
        srv.serve([("sssp", 4), ("bfs", 8)])
        assert srv.step_traces == 1 and srv.admit_traces == 1

    def test_queue_and_flight_accounting(self):
        srv = GraphServer(_graph(seed=8), lanes=2)
        for q in [("bfs", 0), ("bfs", 1), ("bfs", 2)]:
            srv.submit(*q)
        assert srv.queued == 3 and srv.in_flight == 0
        srv.tick()
        assert srv.queued == 1 and srv.in_flight == 2
        srv.drain()
        assert srv.queued == 0 and srv.in_flight == 0

    def test_result_metadata(self):
        srv = GraphServer(_graph(seed=9), lanes=1)
        results = srv.serve([("sssp", 3)])
        (r,) = results.values()
        assert r.kind == "sssp" and r.source == 3
        assert r.iterations >= 1
        assert r.completed_at >= r.admitted_at >= r.submitted_at
        assert r.latency >= 0.0

    def test_bfs_depths_are_int32(self):
        srv = GraphServer(_graph(seed=10), lanes=1)
        results = srv.serve([("bfs", 0)])
        (r,) = results.values()
        assert r.value.dtype == np.int32

    def test_submit_validates(self):
        srv = GraphServer(_graph(), lanes=1)
        with pytest.raises(ValueError):
            srv.submit("pagerankk")
        with pytest.raises(ValueError):
            srv.submit("bfs", source=10_000)

    def test_constructor_validates(self):
        with pytest.raises(ValueError):
            GraphServer(_graph(), lanes=0)
        with pytest.raises(ValueError):
            GraphServer(_graph(), direction="sideways")
