"""The program's view of a trace (``bench/scopes.py``): device time by named
scope, the program's spans, and idle gaps labelled with the span the host
was in, against numbers worked out by hand."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import scopes, trace  # noqa: E402

NAMES = ("bfs.level", "push", "compact", "mask", "fixup", "scatter", "gather")
LOOP = {"jit(f)", "while", "body", "bfs.level"}


def _events():
    ops = {"/device:TPU:0": [
        ("%copy", -20, 4, set()),                    # clipped to [0, 4]
        ("%while", 5, 60, {"jit(f)"}),               # holds the next two
        ("%fusion.1", 10, 30, LOOP | {"push", "compact"}),
        ("%fusion.2", 30, 50, LOOP | {"mask"}),
        ("%fusion.3", 70, 90, {"jit(f)", "bfs.level", "fixup", "scatter"}),
    ]}
    spans = [("bench.window", 0, 100), ("bench.call", 2, 66),
             ("repro.bfs", 3, 9), ("repro.bfs.dispatch", 4, 8),
             ("repro.gc", 62, 68), ("bench.call", 80, 99),
             ("repro.pagerank", 150, 160)]
    return ops, spans


def test_scope_time_is_the_self_time_of_the_ops_under_it():
    got = scopes.reduce_events(*_events(), NAMES)
    assert got.chips == 1
    assert got.window_s == pytest.approx(100e-9)
    # busy: [0, 4] + [5, 60] + [70, 90]
    assert got.busy_s == pytest.approx(79e-9)
    assert got.scope_s == pytest.approx({
        "bfs.level": 60e-9, "push": 20e-9, "compact": 20e-9, "mask": 20e-9,
        "fixup": 20e-9, "scatter": 20e-9, "gather": 0.0})
    # the copy (4) and the loop's own time (55 - 40)
    assert got.unscoped_s == pytest.approx(19e-9)
    assert got.unscoped_ops == [["%while", pytest.approx(15e-9)],
                                ["%copy", pytest.approx(4e-9)]]


def test_scoped_and_unscoped_time_add_up_to_busy_time():
    got = scopes.reduce_events(*_events(), NAMES)
    assert got.scoped_s == pytest.approx(60e-9)
    assert got.scoped_s + got.unscoped_s == pytest.approx(got.busy_s)


def test_program_spans_inside_the_window_from_its_start():
    got = scopes.reduce_events(*_events(), NAMES)
    assert got.spans == [["repro.bfs", pytest.approx(3e-9),
                          pytest.approx(9e-9)],
                         ["repro.bfs.dispatch", pytest.approx(4e-9),
                          pytest.approx(8e-9)],
                         ["repro.gc", pytest.approx(62e-9),
                          pytest.approx(68e-9)]]


def test_gaps_are_labelled_with_the_innermost_span_of_either_prefix():
    got = scopes.reduce_events(*_events(), NAMES)
    assert got.idle_gaps == [["repro.gc", pytest.approx(10e-9)],
                             ["bench.call", pytest.approx(10e-9)],
                             ["repro.bfs.dispatch", pytest.approx(1e-9)]]


def test_scope_time_is_divided_by_the_chips_used():
    ops, spans = _events()
    ops["/device:TPU:1"] = [("%fusion.9", 20, 40, LOOP | {"gather"})]
    got = scopes.reduce_events(ops, spans, NAMES)
    assert got.chips == 2
    assert got.scope_s["bfs.level"] == pytest.approx(80e-9 / 2)
    assert got.scope_s["gather"] == pytest.approx(20e-9 / 2)
    assert got.busy_s == pytest.approx((79e-9 + 20e-9) / 2)


def test_scope_names_are_the_paths_components_but_the_op():
    assert scopes.scope_names(
        "jit(_bfs_loop)/while/body/bfs.level/push/compact/gather:") == {
        "jit(_bfs_loop)", "while", "body", "bfs.level", "push", "compact"}
    assert scopes.scope_names("") == set()


def test_summary_lists_the_scopes_that_ran_and_the_spans_per_name():
    got = scopes.summary(scopes.reduce_events(*_events(), NAMES), top=3)
    assert [name for name, _ in got["scopes"]] == ["bfs.level", "push",
                                                    "compact"]
    assert got["spans"]["repro.bfs"] == {"count": 1,
                                         "mean_ms": pytest.approx(6e-6),
                                         "max_ms": pytest.approx(6e-6)}


def test_the_benchmarks_own_reduction_is_unchanged_by_program_spans():
    ops, spans = _events()
    plain = {chip: [(n, a, b) for n, a, b, _ in evs]
             for chip, evs in ops.items()}
    reduced = trace.reduce_events(plain, [s for s in spans
                                          if s[0].startswith("bench.")])
    got = scopes.reduce_events(ops, spans, NAMES)
    assert reduced.busy_s == pytest.approx(got.busy_s)
    assert [label for label, _ in reduced.idle_gaps] == ["bench.call"] * 3


# -- a recorded trace -------------------------------------------------------

FIXTURE = pathlib.Path(__file__).parent / "data" / "v5e_scoped.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """``data/v5e_scoped.xplane.pb``, recorded on one TPU v5e by
    ``record_scoped_fixture.py``: a scale-8 Kronecker graph (256 vertices,
    4,270 directed edges), one BFS call from its vertex of largest degree
    and a 3-iteration PageRank call, each in a ``bench.call`` span, with a
    ``gc.collect()`` between them, inside a 48,425,440 ns ``bench.window``.

    Device clock against host clock on this trace: the PageRank loop's
    first operation (46,375,197 ns after the window opens) lies 0.911 ms
    before the ``repro.pagerank.dispatch`` span that launched it opens
    (47,286,320 ns), and its last one ends (46,614,009 ns) 1.809 ms before
    that call's ``block_until_ready`` returns (48,423,320 ns): the device
    clock leads by 0.91-1.81 ms, as the ~1.1 ms of ``v5e_small``.

    By hand, from the device events (ns): the kernel launches
    (``chunk_walk_reduce.10`` 1,803 twice and ``.9`` 31,786 in the BFS,
    ``.5`` 31,748, 31,745 and 31,751 in PageRank) and PageRank's gather
    (``fusion.38`` 72, 72, 73; ``pad_clamp_fusion.3`` 13, 13, 12;
    ``fusion.39`` 33,135, 33,135, 33,137) nest nothing.  The BFS's last
    operation ends at 471,856 and PageRank's first eager operation starts
    at 44,534,278; the PageRank loop's last ends at 46,614,009.
    """
    return scopes.reduce_trace(str(FIXTURE))


def _ns(ns):
    return pytest.approx(ns * 1e-9, abs=1e-9)


def test_recorded_kernel_and_gather_time(recorded):
    assert recorded.chips == 1
    assert recorded.window_s == _ns(48_425_440)
    assert recorded.scope_s["kernel"] == _ns(
        1803 + 31786 + 1803 + 31748 + 31745 + 31751)
    assert recorded.scope_s["gather"] == _ns(
        72 + 13 + 33135 + 72 + 13 + 33135 + 73 + 12 + 33137)


def test_recorded_scopes_add_up_to_the_base_reductions_busy_time(recorded):
    base = trace.reduce_trace(str(FIXTURE))
    assert recorded.busy_s == pytest.approx(base.busy_s)
    assert recorded.window_s == pytest.approx(base.window_s)
    assert recorded.scoped_s + recorded.unscoped_s == pytest.approx(
        recorded.busy_s)
    # every BFS level and PageRank iteration ran under its loop scope
    for scope in ("bfs.level", "pagerank.iter", "push", "pull", "compact",
                  "mask", "windows", "scatter", "fixup", "update"):
        assert recorded.scope_s[scope] > 0, scope
    assert recorded.scope_s["bfs.level"] + recorded.scope_s[
        "pagerank.iter"] <= recorded.scoped_s


def test_recorded_program_spans(recorded):
    got = {name: (a, b) for name, a, b in recorded.spans}
    assert list(got) == ["repro.bfs", "repro.bfs.plan", "repro.bfs.dispatch",
                         "repro.gc", "repro.pagerank", "repro.pagerank.plan",
                         "repro.pagerank.dispatch"]
    assert got["repro.bfs.dispatch"] == (_ns(166_940), _ns(1_418_230))
    assert got["repro.gc"] == (_ns(2_338_090), _ns(44_933_980))
    assert got["repro.pagerank.dispatch"] == (_ns(47_286_320),
                                              _ns(47_797_780))


def test_recorded_gaps_name_the_span_the_host_was_in(recorded):
    gaps = recorded.idle_gaps
    # the collection between the calls; then the device done while the
    # host was still in PageRank's dispatch, up to the window's end
    assert gaps[0] == ["repro.gc", _ns(44_534_278 - 471_856)]
    assert gaps[1] == ["repro.pagerank.dispatch",
                       _ns(48_425_440 - 46_614_009)]
    assert {label for label, _ in gaps} <= {
        "repro.gc", "repro.bfs", "repro.pagerank", "repro.bfs.dispatch",
        "repro.pagerank.dispatch", "bench.call"}
    # the benchmark's own reduction sees the same gaps under bench. spans
    base = trace.reduce_trace(str(FIXTURE))
    assert [s for _, s in base.idle_gaps] == [s for _, s in gaps]
