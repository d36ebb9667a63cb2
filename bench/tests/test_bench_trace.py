"""The trace reduction, against numbers worked out by hand.

``data/v5e_small.xplane.pb`` was recorded on one TPU v5e: inside a
``bench.window`` span, three rounds of a ``bench.call`` span running a
``tanh(x @ x) + 1`` program, a 3 ms ``bench.host_gap`` sleep and an
``x * 2 - 1`` program, on a 1024 x 1024 float32 ``x``.  Its device events
(ns) inside the window: ``%multiply_add_fusion`` 13,173 + 13,162 + 12,958;
``%fusion`` 13,336 + 13,301; ``%copy-done`` 5,881 + 5,929; ``%copy-start``
13 + 14; all disjoint.  The first round's device events lie before the
window's start on the trace's clock (the device clock leads by ~1.1 ms).
"""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

FIXTURE = pathlib.Path(__file__).parent / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(str(FIXTURE))


def test_window_busy_and_idle_share(reduced):
    busy_ns = 13173 + 13162 + 12958 + 13336 + 13301 + 5881 + 5929 + 13 + 14
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx(15_735_659e-9)
    assert reduced.busy_s == pytest.approx(busy_ns * 1e-9)
    assert reduced.idle_share == pytest.approx(1 - busy_ns / 15_735_659)


def test_top_ops_by_name(reduced):
    assert reduced.top_ops == [
        ["%multiply_add_fusion = f32[1024,1024] fusion",
         pytest.approx((13173 + 13162 + 12958) * 1e-9)],
        ["%fusion = f32[1024,1024] fusion",
         pytest.approx((13336 + 13301) * 1e-9)],
        ["%copy-done = f32[1024,1024] copy-done",
         pytest.approx((5881 + 5929) * 1e-9)],
        ["%copy-start = (f32[1024,1024], f32[1024,1024], u32[]) copy-start",
         pytest.approx(27e-9)],
    ]


def test_idle_gaps_longest_first_with_their_host_span(reduced):
    # window [44_201_179, 59_936_838]; gaps between the events above
    want = [58_203_571 - 53_633_452,          # after round 3 tanh
            52_865_703 - 48_248_681,          # round 2's sleep
            47_486_913 - 44_201_179,          # window start to round 1's x*2
            59_936_838 - 58_216_529,          # last op to window end
            53_614_206 - 52_878_865,
            48_229_447 - 47_500_086]
    got = reduced.idle_gaps
    assert len(got) == 10
    assert [label for label, _ in got] == ["bench.host_gap"] * 10
    assert sorted((s for _, s in got[:6]), reverse=True) == [
        pytest.approx(w * 1e-9) for w in sorted(want, reverse=True)]
    assert all(s < 3e-9 for _, s in got[6:])


def test_self_time_of_nested_ops():
    events = [("while", 0, 100), ("cond", 10, 60), ("fusion", 20, 30),
              ("fusion", 70, 90)]
    assert trace.self_times(events) == {"while": 30, "cond": 40,
                                        "fusion": 30}


def test_merge_and_gaps():
    merged = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert trace.gaps(merged, -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_reduction_needs_a_window_and_a_device_op():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_events({}, [("bench.call", 0, 1)])
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce_events({"/device:TPU:0": [("op", 5, 6)]},
                            [("bench.window", 0, 2)])


def test_short_names_drop_layouts_and_operands():
    hlo = ("%fusion.39 = f32[31401674]{0:T(1024)} fusion(f32[1048576]"
           "{0:T(1024)S(1)} %get-tuple-element.296), kind=kCustom")
    assert trace.short_name(hlo) == "%fusion.39 = f32[31401674] fusion"
