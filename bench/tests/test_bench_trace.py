"""The reduction from a profiler trace to busy time, operation times and
idle gaps (``bench/lib/profile.py``)."""

import pathlib

import pytest

from bench.lib import profile

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(kind, name, start, dur, device=None):
    return {"kind": kind, "device": device, "name": name, "start": float(start),
            "dur": float(dur)}


def test_hand_counted_window():
    # Device 0 runs A [0, 10), B [5, 20) and C [30, 40) of the window
    # [0, 50) ns; D [45, 60) is cut at the window's end.  Busy: [0, 20),
    # [30, 40) and [45, 50), 35 ns.  Gaps: [20, 30) inside the harness's
    # bench.tick [25, 35) (the innermost span at its midpoint 25) and
    # [40, 45) inside bench.step [0, 48).
    events = [
        ev("host", "bench.window", 0, 50),
        ev("host", "bench.step", 0, 48),
        ev("host", "bench.tick", 25, 10),
        ev("op", "A", 0, 10, 0), ev("op", "B", 5, 15, 0), ev("op", "C", 30, 10, 0),
        ev("op", "D", 45, 15, 0), ev("op", "E", 60, 5, 0),
    ]
    red = profile.Reduction(events)
    assert red.window_s == pytest.approx(50e-9)
    assert red.busy_s() == pytest.approx(35e-9)
    assert red.idle_share() == pytest.approx(0.3)
    assert red.op_seconds() == pytest.approx({"A": 10e-9, "B": 15e-9, "C": 10e-9, "D": 5e-9})
    assert red.gaps() == [(0, 20.0, 30.0), (0, 40.0, 45.0)]
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["B", pytest.approx(15e-9)]
    assert bd["idle_gaps"] == [["bench.tick", pytest.approx(10e-9)],
                               ["bench.step", pytest.approx(5e-9)]]


def test_busy_time_is_averaged_over_chips():
    events = [
        ev("host", "bench.window", 0, 100),
        ev("op", "x", 0, 100, 0),
        ev("op", "x", 0, 50, 1),
    ]
    red = profile.Reduction(events)
    assert red.busy_s() == pytest.approx(75e-9)
    assert red.op_seconds() == pytest.approx({"x": 75e-9})
    assert red.idle_share() == pytest.approx(0.25)


def sweep_busy_ns(events, t0, t1):
    """Busy time by a sweep over interval endpoints: a count of open
    operations, independent of the reduction's merge of intervals."""
    points = []
    for e in events:
        if e["kind"] == "op":
            s, end = max(e["start"], t0), min(e["start"] + e["dur"], t1)
            if end > s:
                points += [(s, 1), (end, -1)]
    busy, open_, last = 0.0, 0, None
    for t, step in sorted(points):
        if open_ > 0:
            busy += t - last
        open_ += step
        last = t
    return busy


def test_recorded_v5e_serving_trace():
    # 150 ms of the serving cell's traced window, recorded on one TPU v5e:
    # 226 device operations inside one engine tick, which the host spent in
    # the harness's bench.tick span.
    events = profile.load_saved(DATA / "trace-serve-v5e.json.gz")
    red = profile.Reduction(events)
    assert sum(1 for e in events if e["kind"] == "op") == 226
    assert red.window_s == pytest.approx(0.150)
    assert red.busy_s() == pytest.approx(0.147746595, abs=1e-9)
    assert red.busy_s() * 1e9 == pytest.approx(sweep_busy_ns(events, red.t0, red.t1))
    assert red.idle_share() == pytest.approx(0.0150227, abs=1e-6)
    # Self time adds up to busy time (the while loop's body is not counted twice).
    assert sum(red.op_seconds().values()) == pytest.approx(red.busy_s())
    bd = red.breakdown()
    assert bd["device_ops"][0][0].startswith("%paged_flash_decode_pallas")
    assert bd["device_ops"][0][1] == pytest.approx(0.130920854, abs=1e-9)
    assert bd["idle_gaps"][0] == ["bench.tick", pytest.approx(2.253394e-3, abs=1e-9)]
