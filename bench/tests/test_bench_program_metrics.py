"""The metrics that read the program's own counters and spans, through
``run_cell`` at scale 8 on the CPU (the ``root`` checkout of
``conftest.py``), and their silence where the program has no telemetry."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, trace  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PROGRAM_METRICS = {"kron-s20.bfs": ("inspector_programs",
                                    "driver_host_ms.bfs"),
                   "urand-s20.bfs": ("inspector_programs",
                                     "driver_host_ms.bfs"),
                   "kron-s20.pagerank": ("inspector_programs",
                                         "driver_host_ms.pr")}


@pytest.fixture
def fake_trace(monkeypatch):
    fake = trace.Reduced(window_s=2.0, busy_s=1.5, chips=1, top_ops=[],
                         idle_gaps=[])
    monkeypatch.setattr(trace, "reduce_trace", lambda path: fake)


def _traced(root, cell):
    return harness.run_cell(root, cell, seed=11, seconds=0.05, trace=True,
                            t_start=time.perf_counter(), device=dict(CPU))


@pytest.mark.parametrize("cell", sorted(PROGRAM_METRICS))
def test_traced_run_reports_the_program_metrics(root, cell, fake_trace):
    from repro.core import telemetry
    result = _traced(root, cell)
    metrics = result["metrics"]
    programs, host_ms = PROGRAM_METRICS[cell]
    assert set(PROGRAM_METRICS[cell]) <= set(metrics)
    inspected = sum(n for k, n in telemetry.counters().items()
                    if k.split(".")[:2] == ["programs", "inspect"])
    assert metrics[programs] == {"value": inspected, "unit": "count"}
    assert metrics[host_ms]["unit"] == "ms"
    # the window's calls are the driver's last spans
    span = "bfs" if cell.endswith("bfs") else "pagerank"
    calls = result["attempted"]
    last = telemetry.recent_spans(span)[-calls:]
    assert metrics[host_ms]["value"] == pytest.approx(
        1e3 * sum(b - a for a, b in last) / calls)
    assert 0 < metrics[host_ms]["value"] < 1e3 * 0.05 * 20


def test_inspector_programs_count_the_inspectors_compiles(root):
    import jax
    import jax.numpy as jnp
    from repro.core import telemetry
    reader = harness.load_reader(root, "inspector_programs")
    x = jnp.arange(7).block_until_ready()
    before = reader.read(None)
    with telemetry.span("inspect.pull"):
        jax.jit(lambda x: x * 5 - 2)(x).block_until_ready()
    assert reader.read(None) == before + 1


@pytest.mark.parametrize("metric", ["inspector_programs",
                                    "driver_host_ms.bfs",
                                    "driver_host_ms.pr"])
def test_readers_say_nothing_without_the_programs_telemetry(root, metric,
                                                            monkeypatch):
    # a program without repro.core.telemetry, as an older checkout has
    import repro.core
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    run = harness.Run(kind="pagerank" if metric.endswith("pr") else "bfs",
                      vertices=1, edges=1, setup_s=1.0, plan_build_s=1.0,
                      window_s=1.0, work={"calls": 2}, compiles_in_window=0,
                      peaks={})
    assert harness.load_reader(root, metric).read(run) is None


def test_driver_host_ms_needs_its_kind_and_a_span_per_call(root):
    bfs = harness.load_reader(root, "driver_host_ms.bfs")
    pr = harness.load_reader(root, "driver_host_ms.pr")
    run = harness.Run(kind="bfs", vertices=1, edges=1, setup_s=1.0,
                      plan_build_s=1.0, window_s=1.0,
                      work={"calls": 10 ** 6}, compiles_in_window=0,
                      peaks={})
    assert pr.read(run) is None
    assert bfs.read(run) is None        # more calls than spans kept
