"""The trace reduction: busy/idle union, per-pass time, top ops and gap
attribution, on hand-made intervals and on a small trace recorded on a
TPU v5e (``fixtures/``)."""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "batch_v5e.xplane.pb")


def test_merge_overlapping_nested_and_clipped():
    covered, gaps = tr.merge([(5, 8), (0, 2), (1, 3), (6, 7), (9, 20)],
                             1, 12)
    assert covered == (3 - 1) + (8 - 5) + (12 - 9)
    assert gaps == [(3, 5), (8, 9)]


def test_merge_of_nothing_is_one_gap():
    assert tr.merge([], 0, 10) == (0.0, [(0, 10)])


def test_reduce_hand_made():
    ops = {"/device:TPU:0": [("gather", 10, 40), ("sum", 40, 50),
                             ("gather", 70, 90), ("early", 0, 20)]}
    spans = [("bench.window", 10, 110), ("bench.solve", 10, 60),
             ("bench.submit", 60, 75), ("bench.solve", 75, 110)]
    s = tr.reduce(ops, spans)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(60e-9)
    assert s.idle_pct == pytest.approx(40.0)
    assert s.device_ops[0] == ["gather", pytest.approx(50e-9)]
    # gaps 50-70 (midpoint in the shorter submit span) and 90-110
    assert dict(s.idle_gaps) == {"bench.submit": pytest.approx(20e-9),
                                 "bench.solve": pytest.approx(20e-9)}


def test_container_ops_leave_the_op_list_but_not_the_union():
    ops = {"/device:TPU:0": [("%while.4 = (s32[], f32[8]) while(%t)", 0, 100),
                             ("%fusion.1 = f32[8]{0:T(1024)} fusion(%a)",
                              0, 30),
                             ("%fusion.2 = f32[8]{0} fusion(%b)", 40, 90)]}
    s = tr.reduce(ops, [("bench.window", 0, 100)])
    assert s.busy_s == pytest.approx(100e-9)
    assert [n for n, _ in s.device_ops] == ["%fusion.2 fusion f32[8]",
                                            "%fusion.1 fusion f32[8]"]


@pytest.mark.parametrize("hlo,short", [
    ("%fusion.36 = f32[67108864]{0:T(1024)} fusion(f32[20933669]{0} %a)",
     "%fusion.36 fusion f32[67108864]"),
    ("%while.4 = (s32[]{:T(128)}, f32[20]{0}) while((s32[], f32[20]) %t)",
     "%while.4 while tuple"),
    ("jit_convert_element_type(1538)", "jit_convert_element_type(1538)"),
])
def test_short_name(hlo, short):
    assert tr.short_name(hlo) == short


def test_reduce_averages_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 10)], "/device:TPU:1": [("a", 0, 4)]}
    s = tr.reduce(ops, [("bench.window", 0, 10)])
    assert s.busy_s == pytest.approx(7e-9)
    assert s.devices == 2


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        tr.reduce({"/device:TPU:0": [("a", 0, 1)]}, [])


def brute_force_busy(events, lo, hi):
    """Union length on a 1 ns grid: independent of ``merge``."""
    lo_i, hi_i = int(np.floor(lo)), int(np.ceil(hi))
    grid = np.zeros(hi_i - lo_i, bool)
    for _, s, e in events:
        a, b = max(int(s), lo_i), min(int(np.ceil(e)), hi_i)
        if b > a:
            grid[a - lo_i:b - lo_i] = True
    return grid.sum()


def test_recorded_chip_trace():
    """Three solves of a scale-12 batch window, traced on a TPU v5e."""
    ops, spans = tr.read_events(FIXTURE)
    assert list(ops) == ["/device:TPU:0"]
    names = {s[0] for s in spans}
    assert {"bench.window", "bench.solve"} <= names
    s = tr.reduce(ops, spans)
    _, lo, hi = next(x for x in spans if x[0] == "bench.window")
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    # the device ops lie inside the host's window on one clock
    events = ops["/device:TPU:0"]
    assert any(lo <= st and en <= hi for _, st, en in events)
    want = brute_force_busy(events, lo, hi)
    assert s.busy_s * 1e9 == pytest.approx(want, rel=1e-3, abs=len(events))
    assert 0 < s.busy_s <= s.window_s
    idle = sum(v for _, v in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert {g[0] for g in s.idle_gaps} <= names | {"no bench span"}
    times = [v for _, v in s.device_ops]
    assert times == sorted(times, reverse=True) and len(times) <= tr.TOP
