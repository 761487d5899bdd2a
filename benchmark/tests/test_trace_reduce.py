"""The trace reduction: on hand-made events, and on a small trace recorded
on the chip in this PR (tests/record_trace.py)."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return (name, int(start_ms * MS), int(dur_ms * MS))


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr._subtract([(0, 10)], [(2, 4), (6, 7)]) == 7
    assert tr._subtract([(0, 2), (5, 6)], [(1, 5)]) == 2


def test_reduce_by_hand():
    trace = {"devices": {
        0: [ev("fusion.1", 0, 2), ev("all-reduce.1", 1, 3),   # 1 ms exposed... 2 after fusion ends
            ev("fusion.2", 6, 2), ev("fusion.1", 10, 1)],
        1: [ev("fusion.1", 0, 11)]},
        "phases": [("data_wait", 4 * MS, 2 * MS), ("drain", 8 * MS, 2 * MS)]}
    r = tr.reduce(trace, min_gap_ns=MS // 2)
    assert r["window_s"] == pytest.approx(0.011)
    # device 0 busy: [0,4] + [6,8] + [10,11] = 7 ms; device 1: 11 ms
    assert r["busy_s"] == pytest.approx((0.007 + 0.011) / 2)
    assert r["idle_share"] == pytest.approx(1 - 9 / 11)
    # the all-reduce runs 1..4; fusion.1 covers 1..2, so 2 ms are exposed (dev 0)
    assert r["collective_exposed_s"] == pytest.approx(0.002 / 2)
    assert r["collective_s"] == pytest.approx(0.003 / 2)
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == pytest.approx(0.003)
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"data_wait": 0.002, "drain": 0.002})


def test_no_device_events_is_nothing():
    assert tr.reduce({"devices": {}, "phases": []}) is None


def test_recorded_trace():
    path = os.path.join(HERE, "small_trace.xplane.pb")
    trace = tr.load(path)
    assert list(trace["devices"]) == [0]
    assert {p[0] for p in trace["phases"]} == {"dispatch", "data_wait"}
    r = tr.reduce(trace)
    # five products of 2048^3 on one chip: some tenths of a millisecond of
    # device time, inside a window that holds a 20 ms host sleep
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] > 0.02
    assert r["idle_share"] > 0.5
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert max(gaps, key=gaps.get) == "data_wait" and gaps["data_wait"] >= 0.019
    assert r["collective_s"] == 0.0
