"""The harness end to end on the CPU at scale 8, through its discovery code.

The cells run from the ``root`` fixture's checkout (``conftest.py``): the
configurations cut to 256 vertices, one traffic mix added as a data file
only, and one traffic kind added as a file with its mix.  ``run_cell`` is called past the look for a chip; ``bench/run.py``
itself is run once to see that it refuses to measure without a TPU.
"""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, trace  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(root, cell, seed=5, trace_on=False):
    return harness.run_cell(root, cell, seed=seed, seconds=0.05,
                            trace=trace_on, t_start=time.perf_counter(),
                            device=dict(CPU))


def _expected(root, cell, key):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"] for m in spec[key]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", ["kron-s20.bfs", "kron-s20.pagerank",
                                  "urand-s20.bfs", "urand-s20.pagerank",
                                  "kron-s20.bfs-4roots", "kron-s20.reach"])
def test_cell_runs_and_reports_its_end_to_end_metrics(root, cell, capsys):
    result = _run(root, cell)
    assert list(result)[:5] == RESULT_KEYS
    assert list(result)[-1] == "compared"
    assert "breakdown" not in result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == _expected(root, cell, "end_to_end")
    for m in result["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes",
                                     "memory_peak_bytes_per_chip"}
    assert len(result["device"]["memory_peak_bytes_per_chip"]) == 1
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1].startswith("bench compared ")
    assert re.search(r"limit=", err[-1])


def test_traced_run_reports_per_layer_metrics_and_breakdown(root,
                                                            monkeypatch):
    fake = trace.Reduced(window_s=2.0, busy_s=1.5, chips=1,
                         top_ops=[["fusion", 1.0]],
                         idle_gaps=[["bench.call", 0.25]])
    monkeypatch.setattr(trace, "reduce_trace", lambda path: fake)
    for cell in ("kron-s20.bfs", "kron-s20.pagerank"):
        result = _run(root, cell, trace_on=True)
        assert list(result)[:5] == RESULT_KEYS
        assert list(result)[-2:] == ["breakdown", "compared"]
        assert set(result["metrics"]) == _expected(root, cell, "per_layer")
        assert result["device"]["busy_s"] == 1.5
        assert result["device"]["window_s"] == 2.0
        assert result["breakdown"] == {"device_ops": [["fusion", 1.0]],
                                       "idle_gaps": [["bench.call", 0.25]]}
        idle = [v["value"] for k, v in result["metrics"].items()
                if k.startswith("idle_share")]
        assert idle == [pytest.approx(25.0)]


def test_run_cell_leaves_the_process_as_it_found_it(root):
    import gc
    from jax._src import monitoring
    listeners = list(monitoring.get_event_duration_listeners())
    cache_dir = jax.config.jax_compilation_cache_dir
    env = dict(os.environ)
    _run(root, "kron-s20.bfs")
    assert list(monitoring.get_event_duration_listeners()) == listeners
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert dict(os.environ) == env
    assert gc.get_freeze_count() == 0


class _FakeDevice:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_is_the_fullest_of_the_cells_devices(root, monkeypatch):
    devices = [_FakeDevice({"peak_bytes_in_use": b, "bytes_in_use": 1})
               for b in (7, 300, 12, 299)]
    assert harness.memory_peaks(devices) == [7, 300, 12, 299]
    assert harness.memory_peaks([_FakeDevice(None)]) == [0]
    build = harness.build_plan
    monkeypatch.setattr(harness, "build_plan",
                        lambda *a: (build(*a)[0], devices))
    device = _run(root, "kron-s20.pagerank")["device"]
    assert device["memory_peak_bytes"] == 300
    assert device["memory_peak_bytes_per_chip"] == [7, 300, 12, 299]


def test_run_py_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "kron-s20.bfs", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_peak_lookup_knows_v5e_and_refuses_unknown_devices():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_unknown_cell_is_refused(root):
    with pytest.raises(KeyError, match="no workload"):
        harness.find_cell(root, "kron-s20.nothing")


# -- the timed path broken underneath: ``correct`` has to come out false ----

def _break_bfs_step(monkeypatch):
    """A BFS step that returns its frontier as reached: state unchanged."""
    import repro.sparse.graph as g
    monkeypatch.setattr(g, "advance_frontier",
                        lambda plan, frontier, **kw: frontier)


def _alter_bfs_answer(monkeypatch):
    """One vertex's depth altered where the BFS loop produces it."""
    import repro.sparse.graph as g
    loop = g._bfs_loop

    def altered(*args, **kwargs):
        depth, parent, counts = loop(*args, **kwargs)
        return depth.at[depth.shape[0] // 2].add(1), parent, counts
    monkeypatch.setattr(g, "_bfs_loop", altered)


def _break_pagerank_step(monkeypatch):
    """Every PageRank step returns the uniform start: state unchanged."""
    import repro.sparse.graph as g
    monkeypatch.setattr(
        g, "_pagerank_update",
        lambda contrib, dangling, damping, V: jnp.full_like(contrib, 1 / V))


def _alter_pagerank_answer(monkeypatch):
    """One rank off by a relative 1e-3 where the PageRank loop produces it."""
    import repro.sparse.graph as g
    loop = g._pagerank_loop

    def altered(*args, **kwargs):
        pr = loop(*args, **kwargs)
        return pr.at[0].multiply(1.001)
    monkeypatch.setattr(g, "_pagerank_loop", altered)


@pytest.mark.parametrize("cell,fault", [
    ("kron-s20.bfs", _break_bfs_step),
    ("kron-s20.bfs", _alter_bfs_answer),
    ("urand-s20.bfs", _break_bfs_step),
    ("kron-s20.pagerank", _break_pagerank_step),
    ("kron-s20.pagerank", _alter_pagerank_answer),
    ("urand-s20.pagerank", _break_pagerank_step),
])
def test_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    fault(monkeypatch)
    jax.clear_caches()
    try:
        result = _run(root, cell)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert result["correct"] is False
    assert result["failed"] >= 1
    (value, limit), = [(v["value"], v["limit"])
                       for v in result["compared"].values()]
    assert value > limit
