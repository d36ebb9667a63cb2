"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name in it
has its file: configuration, traffic mix and metric reader."""
from __future__ import annotations

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def _text(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    cells = 24     # what later PRs may grow to
    check_s = (2 + 14 * cells) * (SPEC["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200
    assert check_s <= 43200
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_configs_have_their_files_and_are_used():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _text(c["source"]) and _text(c["why"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])


def test_cells_have_their_traffic_and_one_chip():
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _text(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "traffic" / "kinds"
                / f"{mix['kind']}.py").exists()
    assert len(set(CELLS)) == len(CELLS)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_have_readers_and_valid_fields(kind):
    for m in SPEC[kind]:
        keys = METRIC_KEYS | ({"bound"} if kind == "end_to_end"
                              else {"layer", "moves"})
        assert set(m) - {"workloads"} == keys, m["name"]
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert _text(m["layer"])
            e2e = {e["name"]: e for e in SPEC["end_to_end"]}
            assert m["moves"] in e2e
            assert set(m["workloads"]) <= set(
                e2e[m["moves"]].get("workloads", CELLS))


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    for cell in CELLS:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        layer = [m["name"] for m in SPEC["per_layer"]
                 if cell in m.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
