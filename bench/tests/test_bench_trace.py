"""CPU tests of the trace reduction: on hand-made events, and on a small
trace recorded on one TPU v5e with the harness's profiler options
(``data/small_window.xplane.pb``: a 0.1-s window of a closed loop of small
jitted computations, each in a ``bench.solve`` span)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps():
    assert trace.union_length([(0, 10), (5, 12), (20, 25), (24, 30)]) == 22
    assert trace.union_length([]) == 0


def test_reduction_on_hand_made_events():
    spans = [(0, 1000, "bench.window"), (0, 300, "bench.generate"),
             (300, 1000, "bench.request"), (600, 1000, "bench.solve")]
    ops = [(100, 200, "fusion.1"), (150, 250, "fusion.2"),
           (400, 500, "while.3"), (700, 900, "while.4"),
           (1110, 1150, "while.5"), (1150, 1160, "copy.6")]
    red = trace.reduce_events(spans, {"/device:TPU:0": ops})
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((150 + 100 + 200) * 1e-9)
    gaps = dict(red["idle_gaps"])
    # each gap goes whole to the innermost span over its middle:
    # [0, 100) generate; [250, 400) request; [500, 700), [900, 1000) solve
    assert gaps["bench.generate"] == pytest.approx(100e-9)
    assert gaps["bench.request"] == pytest.approx(150e-9)
    assert gaps["bench.solve"] == pytest.approx(300e-9)
    ops_t = dict(red["device_ops"])
    assert ops_t["while.4"] == pytest.approx(200e-9)
    assert "while.5" not in ops_t                # outside the window


def test_reduction_on_a_chip_trace():
    path = DATA / "small_window.xplane.pb"
    spans, devices = trace._events(str(path))
    assert list(devices) == ["/device:TPU:0"]
    red = trace.reduce_events(spans, devices)
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(s for _, s in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert 0 < len(red["device_ops"]) <= trace.TOP
    assert {n for n, _ in red["idle_gaps"]} <= {
        "bench.window", "bench.generate", "bench.request", "bench.build",
        "bench.solve"}
