"""CPU tests of the benchmark's own arithmetic: the window statistics."""

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib.window import p50, p90, run_window  # noqa: E402


class _FakeLoop:
    """Requests that take ``base`` seconds, except those in ``stall``."""

    def __init__(self, base, stall=(), stall_s=0.0):
        self.base, self.stall, self.stall_s = base, set(stall), stall_s
        self.kept = []

    def next_request(self, i):
        return i

    def serve(self, i):
        time.sleep(self.stall_s if i in self.stall else self.base)
        return i, {}

    def keep(self, i, req, answer, rec):
        self.kept.append(i)


def test_percentiles_are_over_all_requests():
    ts = [float(x) for x in range(1, 101)]
    assert p50(ts) == 50.5
    assert p90(ts) == pytest.approx(90.9)
    assert p90([3.0]) == 3.0


def test_window_counts_every_request_and_a_stall_moves_the_metrics():
    calm = run_window(_FakeLoop(0.002), 0.3)
    n = len(calm["records"])
    assert n >= 20 and calm["window_s"] >= 0.3
    assert len(calm["gen_s"]) == n
    ts = [r["t"] for r in calm["records"]]
    # every fifth request stalls: the tail moves, the median does not
    tail = run_window(_FakeLoop(0.002, stall=range(0, 1000, 5),
                                stall_s=0.02), 0.3)
    tt = [r["t"] for r in tail["records"]]
    assert p90(tt) > 5 * p90(ts)
    assert p50(tt) < 3 * p50(ts) + 0.003
    # every request stalls: the median moves too, and fewer requests fit
    slow = run_window(_FakeLoop(0.002, stall=range(1000), stall_s=0.02),
                      0.3)
    st = [r["t"] for r in slow["records"]]
    assert p50(st) > 5 * p50(ts)
    assert len(st) < n / 5
