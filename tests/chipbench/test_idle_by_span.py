"""Device idle time put down to the store's spans (``idle_by_span``):
on synthetic intervals, and on a trace recorded on the CPU here, where no
TPU plane exists and the whole window counts as idle."""

import threading
import time

import numpy as np
import pytest

from chipbench import idle_by_span as ibs
from chipbench import trace


def test_covered_intersects_two_sorted_sets():
    a = np.array([[0, 10], [20, 30], [40, 50]])
    b = np.array([[5, 25], [28, 45], [60, 70]])
    assert ibs.covered(a, b) == 5 + 5 + 2 + 5
    assert ibs.covered(a, np.zeros((0, 2), np.int64)) == 0
    assert ibs.covered(a, a) == 30


def test_attribute_unions_each_name_and_all_of_them():
    idle = trace.gaps(np.array([[100, 200], [300, 400]]), 0, 500)
    got = dict(ibs.attribute(idle, {
        # two threads in one layer: their overlap counts once
        "store.parse": (np.array([0, 50]), np.array([80, 150])),
        "store.h2d": (np.array([250]), np.array([350])),
        "store.stage": (np.array([120]), np.array([180])),
    }))
    assert got["store.parse"] == pytest.approx(100e-9)
    assert got["store.h2d"] == pytest.approx(50e-9)
    assert got["store.stage"] == 0.0
    assert got["any"] == pytest.approx(150e-9)
    ranked = ibs.attribute(idle, {"a": (np.array([0]), np.array([10])),
                                  "b": (np.array([50]), np.array([90]))})
    assert [n for n, _ in ranked] == ["any", "b", "a"]
    assert ibs.attribute(idle, {}) == []


def test_idle_by_span_on_a_trace_recorded_here(tmp_path):
    import jax
    from repro.lake import spans

    spans.enable(True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                def work():
                    with spans.span("store.parse"):
                        time.sleep(0.03)

                others = [threading.Thread(target=work) for _ in range(2)]
                for t in others:
                    t.start()
                for t in others:
                    t.join(timeout=10)
                with spans.span("store.stage"):
                    time.sleep(0.02)
                time.sleep(0.02)
                with jax.profiler.TraceAnnotation("unrelated.host_work"):
                    time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
    finally:
        spans.enable(False)
        spans.reset()
    path = trace.find(str(tmp_path))
    assert path is not None
    got = dict(ibs.idle_by_span(path))
    assert set(got) == {"store.parse", "store.stage", "any"}
    # two overlapping parses count as one stretch of about 30 ms
    assert 0.03 <= got["store.parse"] < 0.05
    assert 0.02 <= got["store.stage"] < 0.04
    assert got["any"] == pytest.approx(got["store.parse"] + got["store.stage"])
    window = trace.reduce(path).window_s
    assert got["any"] < window - 0.025
