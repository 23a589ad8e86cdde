"""The reduction from a profiler trace to busy time, idle gaps and the
breakdown: the interval arithmetic by hand, and a small trace recorded on
a TPU v5e."""
import os

import pytest

import rehearse  # noqa: F401 - puts bench/ on the path
import device_trace as dt

RECORDED = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")


def test_union_of_intervals():
    assert dt.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)]) == [
        (0, 3), (5, 9), (10, 11)]
    assert dt.merge([(0, 10), (2, 3)]) == [(0, 10)]


def test_clip_and_gaps():
    busy = dt.merge(dt.clip([(-5, 2), (4, 6), (9, 20)], 0, 10))
    assert busy == [(0, 2), (4, 6), (9, 10)]
    assert dt.gaps(busy, 0, 10) == [(2, 4), (6, 9)]
    assert dt.gaps([], 0, 10) == [(0, 10)]


def test_gap_takes_the_shortest_host_event_covering_half():
    events = sorted([("outer", 0, 100), ("inner", 40, 70), ("tiny", 41, 42)],
                    key=lambda e: e[1])
    starts = [e[1] for e in events]
    assert dt._host_label(events, starts, 45, 65, "w") == "inner"
    assert dt._host_label(events, starts, 10, 30, "w") == "outer"
    assert dt._host_label(events, starts, 200, 300, "w") == "no host event"


def test_recorded_chip_trace():
    r = dt.reduce(RECORDED, "bench.window")
    assert 0 < r["busy_s"] <= r["window_s"]
    ops = r["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and all(s > 0 for _, s in ops)
    idle = sum(s for _, s in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
