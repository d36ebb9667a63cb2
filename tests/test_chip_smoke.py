"""chip_smoke.py off the chip: its phases at Graph500 scale 8 on the CPU.

The script's entry point refuses to run without a TPU; these tests call its
phases (``run``) directly, which is the only way to run them on the CPU.
The four-device phases need four devices before JAX starts, so they run in
a child process with forced host devices.
"""
from __future__ import annotations

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(tmp_path, **extra):
    return dict(os.environ, JAX_PLATFORMS="cpu",
                REPRO_AUTOTUNE_CACHE=str(tmp_path / "autotune.json"), **extra)


def test_kronecker_graph_is_symmetric_and_simple(smoke):
    offsets, cols, weights = smoke.kronecker_edges(8, seed=3)
    n = offsets.size - 1
    assert n == 256 and offsets[-1] == cols.size == weights.size
    rows = [r for r in range(n) for _ in range(offsets[r + 1] - offsets[r])]
    edges = dict(zip(zip(rows, cols.tolist()), weights.tolist()))
    assert len(edges) == cols.size                      # deduplicated
    assert all(u != v for u, v in edges)                # no self-loops
    assert all(edges[(v, u)] == w for (u, v), w in edges.items())
    assert ((weights >= 0) & (weights < 1)).all()
    again = smoke.kronecker_edges(8, seed=3)
    assert all((a == b).all() for a, b in zip(again, (offsets, cols,
                                                       weights)))


def test_one_chip_phases_on_cpu(smoke, monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    smoke.run(8, 0, 1)
    out = capsys.readouterr().out
    for marker in ("bfs=ok", "sssp_delta=ok", "pagerank=ok", "serve=ok",
                   "spmv=ok"):
        assert f"smoke {marker}" in out


def test_four_device_phases_on_cpu(tmp_path):
    code = "import chip_smoke; chip_smoke.run(8, 0, 4)"
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=_env(
            tmp_path,
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    for marker in ("sharded_bfs=bitwise_ok",
                   "sharded_delta_stepping=bitwise_ok",
                   "sharded_pagerank=ok"):
        assert f"smoke {marker}" in proc.stdout
    devices = {line.split("device=")[1].split()[0]
               for line in proc.stdout.splitlines()
               if "sharded_plan_bytes" in line}
    assert len(devices) == 4


def test_entry_point_refuses_the_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--scale", "8"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=_env(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
