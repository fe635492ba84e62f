"""The trace reduction (``harness/trace.py``): exact arithmetic on a
hand-made timeline, and a small trace recorded on a v5e chip
(``data/small.xplane.pb``, made by ``tools/record_trace.py``: three
device programs inside ``perfbench.verify`` spans, 20 ms host sleeps
between them)."""

import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def test_top_level_keeps_outer_ops_only():
    got = trace._top_level([
        ("%while.3 = (s32[]) while(...)", 10.0, 40.0),
        ("%fusion.7 = s32[8] fusion(...)", 12.0, 20.0),
        ("%copy.1 = s32[8] copy(...)", 40.0, 45.0),
    ])
    assert got == [("while.3", 10.0, 40.0), ("copy.1", 40.0, 45.0)]


def test_reduce_on_a_hand_made_timeline():
    devices = {"/device:TPU:0": {
        "ops": [("a", 10.0, 20.0), ("b", 20.0, 30.0), ("c", 50.0, 60.0),
                ("late", 95.0, 120.0)],
        "modules": [("jit__f(1)", 10.0, 30.0), ("jit__g(2)", 50.0, 60.0),
                    ("jit__h(3)", 95.0, 120.0)],
    }}
    spans = [("perfbench.window", 0.0, 100.0), ("perfbench.verify", 5.0, 35.0)]
    got = trace.reduce(devices, spans)
    assert got["window_s"] == pytest.approx(100e-9)
    # busy: [10, 30] + [50, 60] + [95, 100] (clipped to the window)
    assert got["busy_s"] == pytest.approx(35e-9)
    assert got["idle_pct"] == pytest.approx(65.0)
    ops = dict((k, v) for k, v in got["device_ops"])
    assert ops == pytest.approx({"f/a": 10e-9, "f/b": 10e-9, "g/c": 10e-9,
                                 "h/late": 5e-9})
    gaps = dict((k, v) for k, v in got["idle_gaps"])
    # [0, 10] lies inside the verify span; [30, 50] and [60, 95] in none
    assert gaps == pytest.approx({"verify": 10e-9, "idle": 55e-9})


def test_reduce_needs_a_window_and_device_work():
    devices = {"/device:TPU:0": {"ops": [("a", 10.0, 20.0)], "modules": []}}
    with pytest.raises(ValueError):
        trace.reduce(devices, [("perfbench.verify", 0.0, 50.0)])
    with pytest.raises(ValueError):
        trace.reduce(devices, [("perfbench.window", 30.0, 50.0)])


def test_reduce_on_a_recorded_chip_trace():
    devices, spans = trace.load(DATA)
    assert devices, "no device plane in the recorded trace"
    names = [s[0] for s in spans]
    assert names.count("perfbench.window") == 1
    assert names.count("perfbench.verify") == 3
    got = trace.reduce(devices, spans)
    assert 0.06 < got["window_s"] < 1.0
    assert 0.0 < got["busy_s"] < got["window_s"]
    # three 20 ms sleeps with nothing on the device
    assert got["idle_pct"] > 100.0 * 0.06 / got["window_s"] * 0.9
    assert got["device_ops"] and all(v > 0 for _, v in got["device_ops"])
    labels = {k for k, _ in got["idle_gaps"]}
    assert labels <= {"verify", "idle"} and "idle" in labels
