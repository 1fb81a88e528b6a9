"""The reduction from trace events to busy time, self times, collectives
and idle gaps: on hand-made events with known answers, and on a small
trace recorded on the chip, against a slow independent count."""
import os

import pytest

from benchmark.lib import xplane

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib", "testdata")


def hand_made():
    us = 1000.0
    dev0 = [["while.1", 100 * us, 400 * us],         # holds the next three
            ["fusion.1", 100 * us, 100 * us],
            ["all-reduce.3", 200 * us, 50 * us],
            ["fusion.1", 300 * us, 200 * us],
            ["copy.2", 580 * us, 100 * us],          # after an 80 us gap
            ["fusion.9", 950 * us, 200 * us]]        # runs past the window
    dev1 = [["fusion.1", 0 * us, 1000 * us]]
    host = [["bench.tick", 50 * us, 500 * us],
            ["bench.harvest", 550 * us, 450 * us]]
    return {"device": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host}


def test_hand_made_events():
    r = xplane.reduce(hand_made())
    # window: first span's start to last span's end = 50..1000 us
    assert r["window_s"] == pytest.approx(950e-6)
    # chip 0 busy: 100-500, 580-680, 950-1000 = 550 us; chip 1: all 950
    assert r["busy_s_by_chip"] == pytest.approx([550e-6, 950e-6])
    assert r["busy_s"] == pytest.approx(750e-6)
    assert r["collective_s_chip0"] == pytest.approx(50e-6)
    ops = dict(r["device_ops"])
    # self times, mean over the two chips; while.1 keeps 400-350 = 50 us
    assert ops["while.1"] == pytest.approx(25e-6)
    assert ops["fusion.1"] == pytest.approx((300e-6 + 950e-6) / 2)
    assert ops["fusion.9"] == pytest.approx(25e-6)          # clipped
    gaps = dict((k, v) for k, v in r["idle_gaps"] if k.startswith("all:"))
    # chip 0 idle: 50-100 and 500-580 (most of each under bench.tick),
    # 680-950 (under bench.harvest); a gap goes whole to one span
    assert gaps["all:bench.tick"] == pytest.approx(130e-6)
    assert gaps["all:bench.harvest"] == pytest.approx(270e-6)
    assert r["idle_gaps"][2] == ["longest:bench.harvest",
                                 pytest.approx(270e-6)]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_given_window_and_no_spans():
    t = hand_made()
    t["host"] = []
    r = xplane.reduce(t)        # extent of the device events: 0..1150 us
    assert r["window_s"] == pytest.approx(1150e-6)
    r = xplane.reduce(t, window=(100e3, 300e3))
    assert r["busy_s_by_chip"] == pytest.approx([200e-6, 200e-6])
    assert r["idle_gaps"] == []


def slow_busy_ns(events, lo, hi, step):
    """Busy time by sampling the timeline every `step` ns."""
    n = 0
    t = lo
    while t < hi:
        if any(s <= t < s + d for _, s, d in events):
            n += 1
        t += step
    return n * step


RECORDED = sorted(f for f in os.listdir(TESTDATA) if f.endswith(".json"))


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace(name):
    trace = xplane.load_json(os.path.join(TESTDATA, name))
    r = xplane.reduce(trace)
    lo = min(s for _, s, _ in trace["host"])
    hi = max(s + d for _, s, d in trace["host"])
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    for chip, plane in enumerate(sorted(trace["device"])):
        step = (hi - lo) / 20000
        slow = slow_busy_ns(trace["device"][plane], lo, hi, step) * 1e-9
        assert r["busy_s_by_chip"][chip] == pytest.approx(slow, rel=0.01)
    # self times add up to busy time (nothing counted twice or dropped)
    total = sum(xplane.self_times(xplane._clip(
        [tuple(e) for e in trace["device"][sorted(trace["device"])[0]]],
        lo, hi)).values())
    assert total == pytest.approx(r["busy_s_by_chip"][0], rel=1e-3)
    idle = sum(v for k, v in r["idle_gaps"] if k.startswith("all:"))
    assert idle <= r["window_s"] - r["busy_s_by_chip"][0] + 1e-9


def test_recorded_traces_exist():
    assert RECORDED, "benchmark/lib/testdata holds no recorded trace"
