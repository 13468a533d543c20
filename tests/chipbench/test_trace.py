"""The trace reduction: busy union, idle share, top operations, idle gaps.

``data/tpu_small.xplane.pb`` was recorded on one TPU v5e: inside a
``chipbench.window`` span, three rounds of an elementwise program, a 2 ms
host sleep under ``fixture.host_wait`` and a matmul program.
"""

import os

import numpy as np
import pytest

from chipbench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tpu_small.xplane.pb")


def test_union_merges_overlaps_and_touching_intervals():
    s = np.array([5, 0, 2, 10, 11, 20])
    e = np.array([6, 3, 4, 12, 15, 21])
    np.testing.assert_array_equal(
        trace.union(s, e), [[0, 4], [5, 6], [10, 15], [20, 21]])
    assert trace.union(np.array([]), np.array([])).shape == (0, 2)


def test_gaps_are_the_rest_of_the_window():
    busy = np.array([[2, 4], [6, 7]])
    np.testing.assert_array_equal(trace.gaps(busy, 0, 10),
                                  [[0, 2], [4, 6], [7, 10]])
    np.testing.assert_array_equal(trace.gaps(np.array([[0, 10]]), 0, 10),
                                  np.zeros((0, 2)))


def _naive(path):
    """Busy time by walking the events one at a time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    win = None
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    spans = []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    for ev in line.events:
                        a = max(ev.start_ns, win[0])
                        b = min(ev.start_ns + ev.duration_ns, win[1])
                        if b > a:
                            spans.append((a, b))
    spans.sort()
    busy, end = 0, -1
    for a, b in spans:
        if a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    return (win[1] - win[0]) * 1e-9, busy * 1e-9


def test_reduce_a_recorded_tpu_trace():
    got = trace.reduce(FIXTURE)
    window_s, busy_s = _naive(FIXTURE)
    assert got.devices == 1
    assert got.window_s == pytest.approx(window_s)
    assert got.busy_s == pytest.approx(busy_s)
    assert 0 < got.busy_s < got.window_s
    assert got.idle_share == pytest.approx(1 - busy_s / window_s)
    assert got.device_ops and got.device_ops[0][1] >= got.device_ops[-1][1]
    assert sum(t for _, t in got.device_ops) >= got.busy_s * 0.99
    # the longest gap is the host's 2 ms sleep, and is named for it
    label, seconds = got.idle_gaps[0]
    assert label == "fixture.host_wait" and seconds > 0.002


def test_reduce_needs_the_window_span(monkeypatch):
    monkeypatch.setattr(trace, "WINDOW", "no.such.span")
    with pytest.raises(ValueError):
        trace.reduce(FIXTURE)
