"""Each metric's arithmetic, on a hand-made window record."""

import importlib

import pytest

from chipbench.trace import Summary


def rec(**kw):
    base = {"reads": 4, "window_s": 2.0, "bytes": 8_000_000_000,
            "latencies": [0.1, 0.2, 0.3, 0.4], "setup_s": 33.0,
            "stored_bytes": 450, "logical_bytes": 1000,
            "io": {"cache_hits": 3, "cache_misses": 1, "decode_s": 0.2,
                   "frame_bytes_decoded": 12_000_000_000,
                   "fetch_wait_s": 2.0, "decode_queue_s": 0.8},
            "counters": {},
            "spans": {
                "store.parse": {"count": 8, "total_s": 0.5, "self_s": 0.4},
                "store.stage": {"count": 4, "total_s": 0.2, "self_s": 0.2},
                "store.h2d": {"count": 4, "total_s": 0.05, "self_s": 0.04}},
            "compiles": 0, "kernel_bytes": [819_000_000] * 4,
            "trace": Summary(window_s=2.0, busy_s=0.5, devices=1),
            "peaks": {"hbm_bytes_per_s": 819e9}}
    base.update(kw)
    return base


def read(name, **kw):
    return importlib.import_module(f"chipbench.metrics.{name}").read(rec(**kw))


@pytest.mark.parametrize("name, want", [
    ("read_GBps", 4.0),
    ("read_p95_ms", 385.0),
    ("stored_bytes_per_byte", 0.45),
    ("setup_s", 33.0),
    ("decoded_bytes_per_byte", 1.5),
    ("cache_hit_pct", 75.0),
    ("decode_ms_per_read", 50.0),
    ("compiles_in_window", 0),
    ("coo_scatter_roofline", 0.8),
    ("device_idle_pct", 75.0),
    ("fetch_wait_ms_per_read", 500.0),
    ("decode_queue_ms_per_read", 200.0),
    ("parse_ms_per_read", 100.0),
    ("stage_ms_per_read", 50.0),
    ("h2d_ms_per_read", 10.0),
])
def test_metric(name, want):
    assert read(name) == pytest.approx(want)


@pytest.mark.parametrize("name, kw", [
    ("read_GBps", {"reads": 0}),
    ("read_p95_ms", {"latencies": []}),
    ("decoded_bytes_per_byte", {"bytes": 0}),
    ("cache_hit_pct", {"io": {"cache_hits": 0, "cache_misses": 0}}),
    ("decode_ms_per_read", {"reads": 0}),
    ("coo_scatter_roofline", {"trace": None}),
    ("coo_scatter_roofline", {"kernel_bytes": [0, 0]}),
    ("coo_scatter_roofline", {"kernel_bytes": [5, None]}),
    ("device_idle_pct", {"trace": None}),
    ("device_idle_pct", {"trace": Summary(window_s=1.0, busy_s=0.0,
                                          devices=0)}),
    ("fetch_wait_ms_per_read", {"reads": 0}),
    ("fetch_wait_ms_per_read", {"io": {"decode_s": 0.2}}),
    ("decode_queue_ms_per_read", {"reads": 0}),
    ("decode_queue_ms_per_read", {"io": {"decode_s": 0.2}}),
    ("parse_ms_per_read", {"spans": None}),
    ("parse_ms_per_read", {"spans": {}}),
    ("parse_ms_per_read", {"reads": 0}),
    ("stage_ms_per_read", {"spans": None}),
    ("stage_ms_per_read", {"reads": 0}),
    ("h2d_ms_per_read", {"spans": None}),
    ("h2d_ms_per_read", {"spans": {"store.parse": {
        "count": 1, "total_s": 1.0, "self_s": 1.0}}}),
])
def test_metric_with_nothing_to_read_returns_nothing(name, kw):
    assert read(name, **kw) is None
