"""Program telemetry: spans, the counter registry, compile attribution, the
garbage-collection span, and the device scopes the trace metrics read."""
import gc
import json
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (adaptive_inspection_count, adaptive_partition,
                        compact_rungs, make_partition, measurement_count,
                        partition_build_count, telemetry, time_fn)
from repro.core.work import WorkSpec
from repro.sparse import CSR, Graph, build_advance
from repro.sparse import graph as graph_mod
from _conformance import powerlaw_graph_dense


def test_spans_nest_and_record_their_host_time():
    before = len(telemetry.recent_spans("t.outer"))
    with telemetry.span("t.outer"):
        assert telemetry._open()[-1] == "t.outer"
        with telemetry.span("t.inner"):
            assert telemetry._open()[-2:] == ["t.outer", "t.inner"]
        assert telemetry._open()[-1] == "t.outer"
    assert "t.outer" not in telemetry._open()
    outer = telemetry.recent_spans("t.outer")
    inner = telemetry.recent_spans("t.inner")[-1]
    assert len(outer) == before + 1
    start, end = outer[-1]
    assert start <= inner[0] <= inner[1] <= end


def test_span_closes_when_its_block_raises_and_works_as_decorator():
    @telemetry.span("t.deco")
    def boom():
        assert telemetry._open()[-1] == "t.deco"
        raise KeyError("x")

    depth = len(telemetry._open())
    with pytest.raises(KeyError):
        boom()
    assert len(telemetry._open()) == depth
    assert telemetry.recent_spans("t.deco")


def test_span_history_is_bounded():
    for _ in range(telemetry.SPAN_HISTORY + 5):
        with telemetry.span("t.many"):
            pass
    assert len(telemetry.recent_spans("t.many")) == telemetry.SPAN_HISTORY
    assert telemetry.recent_spans("t.never") == []


def test_counter_registry_adds_and_snapshots():
    base = telemetry.counters().get("t.count", 0)
    telemetry.count("t.count")
    telemetry.count("t.count", 4)
    snap = telemetry.counters()
    assert snap["t.count"] == base + 5
    snap["t.count"] = -1            # a copy: the registry is unchanged
    assert telemetry.counters()["t.count"] == base + 5


def test_the_three_old_counters_read_the_registry():
    offsets = jnp.asarray(np.array([0, 3, 3, 10, 11], np.int32))
    spec = WorkSpec(offsets, 11, 4)
    builds, inspections = partition_build_count(), adaptive_inspection_count()
    measured = measurement_count()
    make_partition(spec, "merge_path", 2)
    adaptive_partition(spec, 2, cache=False)
    time_fn(lambda: jnp.zeros(2), warmup=1, iters=1)
    snap = telemetry.counters()
    assert partition_build_count() == builds + 1 \
        == snap["partition_builds"]
    assert adaptive_inspection_count() == inspections + 1 \
        == snap["adaptive_inspections"]
    assert measurement_count() == measured + 1 == snap["measurements"]


def test_a_compile_counts_under_the_innermost_open_span():
    before = telemetry.counters()
    with telemetry.span("t.compile"):
        # a fresh function: JAX's caches cannot hold its program yet
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    jax.jit(lambda x: x - 7)(jnp.arange(3)).block_until_ready()
    after = telemetry.counters()
    assert after["programs.t.compile"] - before.get(
        "programs.t.compile", 0) >= 1
    assert after["programs.outside"] - before.get(
        "programs.outside", 0) >= 1


def test_each_garbage_collection_is_a_gc_span():
    start = time.perf_counter()
    seen = []
    gc.callbacks.insert(0, lambda phase, info: seen.append(
        list(telemetry._open())) if phase == "stop" else None)
    try:
        gc.collect()
    finally:
        gc.callbacks.pop(0)
    # the history may be full: the newest span is this collection's
    assert telemetry.recent_spans("gc")[-1][0] >= start
    # the hook of this test runs before telemetry's closes the span
    assert seen and seen[-1][-1] == "gc"
    assert "gc" not in telemetry._open()


# -- device scopes: a refactor must not drop a scope a metric reads ---------

def _scale8_graph() -> Graph:
    w = powerlaw_graph_dense(256, avg_degree=8.0, seed=1)
    return Graph(CSR.from_dense(np.asarray(w, np.float32)))


def _scopes_of(lowered) -> set:
    text = lowered.compile().as_text()
    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in path.split("/")}


@pytest.fixture(scope="module")
def scale8():
    g = _scale8_graph()
    # chunked/native so the kernel runs; compaction so both push modes do
    traverse = build_advance(g, schedule="chunked", path="native",
                             num_blocks=8, compact=True, delta="auto")
    reduce = build_advance(g, schedule="chunked", path="native",
                           num_blocks=8, workload="reduce")
    return g, traverse, reduce


def test_bfs_loop_carries_every_traversal_scope(scale8):
    _, plan, _ = scale8
    found = _scopes_of(graph_mod._bfs_loop.lower(
        plan, jnp.int32(3), 256, "auto", False))
    want = {"bfs.level", "push", "pull", "mask", "compact", "compact.r0",
            "masked", "windows", "scatter", "fixup", "frontier", "kernel"}
    assert want <= found, want - found
    assert want <= set(telemetry.SCOPES)


def test_every_compaction_rung_has_a_scope():
    rungs = compact_rungs(2 ** 31 - 1)
    assert {f"compact.r{k}" for k in range(len(rungs))} <= set(
        telemetry.SCOPES)


def test_pagerank_loop_carries_every_pagerank_scope(scale8):
    g, _, plan = scale8
    found = _scopes_of(graph_mod._pagerank_loop.lower(
        plan, g.out_degrees().astype(jnp.float32), damping=0.85,
        num_iters=3, tol=0.0, direction="pull"))
    want = {"pagerank.iter", "gather", "update", "windows", "fixup",
            "scatter", "kernel"}
    assert want <= found, want - found
    assert want <= set(telemetry.SCOPES)


@pytest.mark.parametrize("loop,scope", [("_sssp_loop", "sssp.iter"),
                                        ("_delta_loop", "delta.bucket")])
def test_sssp_loops_carry_their_iteration_scope(scale8, loop, scope):
    _, plan, _ = scale8
    kwargs = {"direction": "auto"}
    if loop == "_sssp_loop":
        kwargs["max_iters"] = 256
    else:
        kwargs.update(max_outer=258, light_cap=plan.compact_capacity,
                      heavy_cap=plan.compact_capacity)
    found = _scopes_of(getattr(graph_mod, loop).lower(plan, jnp.int32(3),
                                                      **kwargs))
    assert {scope, "push", "pull", "mask", "compact"} <= found
    assert scope in telemetry.SCOPES


def test_drivers_open_their_spans_and_the_inspector_its_own():
    g = _scale8_graph()
    names = ["inspect", "inspect.pull", "inspect.push", "inspect.autotune",
             "bfs", "bfs.plan", "bfs.dispatch", "pagerank",
             "pagerank.plan", "pagerank.dispatch"]
    before = {n: len(telemetry.recent_spans(n)) for n in names}
    plan = build_advance(g, num_blocks=8)
    graph_mod.bfs(g, 3, plan=plan).block_until_ready()
    graph_mod.pagerank(g, plan=plan, num_iters=2).block_until_ready()
    grew = {n for n in names if len(telemetry.recent_spans(n)) > before[n]}
    assert grew == set(names)


# -- per-rung engagement from a trace (tools/rung_profile.py) ---------------

def _rung_profile():
    import importlib.util
    path = pathlib.Path(__file__).resolve().parent.parent / "tools" \
        / "rung_profile.py"
    spec = importlib.util.spec_from_file_location("rung_profile", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_rung_profile_counts_each_stretch_of_a_rung_once():
    rung_runs = _rung_profile().rung_runs
    level = {"bfs.level", "push", "compact"}
    ops = {"/device:TPU:0": [
        ("a", 0, 1, level | {"compact.r3"}),
        ("b", 1, 2, level | {"compact.r3", "windows"}),
        ("copy", 2, 2.5, set()),                # added by XLA, no scope
        ("b2", 2.5, 2.7, level | {"compact.r3", "scatter"}),
        ("c", 2.7, 3, {"bfs.level", "mask"}),
        ("d", 3, 4, level | {"compact.r3"}),
        ("e", 4, 5, level | {"compact.r0"}),
        ("f", 5, 6, level | {"compact.r0", "scatter"}),
        ("g", 9, 10, level | {"compact.r1"}),      # outside the window
    ]}
    assert rung_runs(ops, 0, 8) == {"compact.r3": 2, "compact.r0": 1}
    assert rung_runs(ops, 20, 30) == {}


def test_rung_profile_reads_a_recorded_trace(capsys):
    # a trace recorded before the ladder: it has a window and no rung
    data = pathlib.Path(__file__).resolve().parent.parent / "bench" \
        / "tests" / "data" / "v5e_scoped.xplane.pb"
    assert _rung_profile().main([str(data)]) == 0
    assert json.loads(capsys.readouterr().out) == {}
