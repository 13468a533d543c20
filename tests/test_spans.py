"""Spans and wait counters of the read path (``repro.lake.spans``).

Off, a span is one shared no-op that records nothing, constructs no
``TraceAnnotation`` and imports no jax. On, each span adds to a table per
name (count, total and self seconds) and carries the id of the read it
belongs to, across the fetch and decode pools. ``ReadStats.fetch_wait_s``
and ``decode_queue_s`` count the waits.
"""

import os
import subprocess
import sys
import threading
import time
from collections import Counter, defaultdict

import numpy as np
import pytest

from repro.core import DeltaTensorStore
from repro.lake import InMemoryObjectStore, LatencyModel, ReadExecutor, spans

from .test_encodings import sparse_tensor

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps every span's
    name, attributes and thread."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **attrs):
        self.events.append((name, dict(attrs), threading.get_ident()))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture
def spans_off():
    spans.enable(False)
    spans.reset()
    yield
    spans.reset()


@pytest.fixture
def recorded(monkeypatch):
    """Spans on, annotations caught by a :class:`Recorder`."""
    rec = Recorder()
    spans.enable(True)
    monkeypatch.setattr(spans, "_annotation", rec)
    spans.reset()
    yield rec
    spans.enable(False)
    spans.reset()


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_off_span_is_the_shared_noop_and_records_nothing(spans_off,
                                                         monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(spans, "_annotation", rec)
    a, b = spans.span("store.plan"), spans.span("store.h2d", bytes=4)
    assert a is b
    assert spans.new_read() is a and spans.in_read(7) is a
    with a, b:
        pass
    with spans.timed("store.decode") as t:
        time.sleep(0.001)
    assert t.seconds >= 0.001
    assert spans.snapshot() == {} and rec.events == []
    assert spans.current_read() is None


def test_off_spans_import_no_jax():
    code = ("import sys\n"
            "from repro.lake import spans\n"
            "with spans.span('store.plan'), spans.new_read():\n"
            "    with spans.timed('store.decode'):\n"
            "        pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_on_counts_totals_and_self_times(recorded):
    for _ in range(3):
        with spans.span("store.parse"):
            time.sleep(0.002)
    with spans.timed("store.decode") as t:
        time.sleep(0.002)
    got = spans.snapshot()
    assert got["store.parse"]["count"] == 3
    assert got["store.parse"]["total_s"] >= 0.006
    assert got["store.decode"]["total_s"] == t.seconds
    for row in got.values():
        assert 0 <= row["self_s"] <= row["total_s"]
    assert [e[0] for e in recorded.events] == ["store.parse"] * 3 + [
        "store.decode"]
    spans.reset()
    assert spans.snapshot() == {}


def test_nested_span_leaves_its_parent_the_rest(recorded):
    with spans.span("store.decode"):
        time.sleep(0.002)
        with spans.span("store.fetch"):
            time.sleep(0.02)
    got = spans.snapshot()
    dec, fet = got["store.decode"], got["store.fetch"]
    assert fet["self_s"] == fet["total_s"] >= 0.02
    assert dec["total_s"] >= fet["total_s"] + 0.002
    assert dec["self_s"] == pytest.approx(dec["total_s"] - fet["total_s"])
    assert dec["self_s"] < fet["total_s"]


def test_threads_keep_their_read_ids_apart(recorded):
    barrier = threading.Barrier(4)
    ids = {}

    def reader(k):
        with spans.new_read() as rid:
            ids[threading.get_ident()] = rid
            barrier.wait(timeout=10)
            for _ in range(5):
                with spans.span("store.stage"):
                    with spans.span("store.h2d", bytes=k):
                        pass

    threads = [threading.Thread(target=reader, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert len(set(ids.values())) == 4
    assert len(recorded.events) == 40
    for name, attrs, thread in recorded.events:
        assert attrs["read"] == ids[thread]
    assert spans.snapshot()["store.h2d"]["count"] == 20
    assert spans.current_read() is None


# ---------------------------------------------------------------------------
# spans inside the read path
# ---------------------------------------------------------------------------

def _store(io, compression="zstd"):
    return DeltaTensorStore(InMemoryObjectStore(), "tensors", io=io,
                            compression=compression)


def _counts():
    return {k: v["count"] for k, v in spans.snapshot().items()}


def _io_delta(io, before):
    s = io.stats
    return {k: getattr(s, k) - v for k, v in before.items()}


def _io_now(io):
    s = io.stats
    return {k: getattr(s, k) for k in ("gets", "cache_misses",
                                       "frames_decoded", "decode_s")}


@pytest.mark.parametrize("decode_workers", [0, 2])
def test_framed_ftsf_slice_read_records_each_layer(recorded, decode_workers):
    io = ReadExecutor(max_workers=4, decode_workers=decode_workers)
    store = _store(io)
    x = np.arange(8 * 4 * 16, dtype=np.float32).reshape(8, 4, 16)
    # one 256-byte row a file: X[2:5] reads three files
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2,
              target_file_bytes=300)
    with store.open("x") as ref:
        ref.header  # noqa: B018 - the header's fetch is not the read's
        spans.reset()
        recorded.events.clear()
        before = _io_now(io)
        out = ref.read_device([(2, 5)])
        np.testing.assert_array_equal(np.asarray(out), x[2:5])
    io.shutdown()
    d = _io_delta(io, before)
    got = _counts()
    assert d["cache_misses"] == d["gets"] == 3 == d["frames_decoded"]
    assert got == {"store.plan": 1, "store.fetch": 3, "store.decode": 3,
                   "store.parse": 3, "store.stage": 1, "store.h2d": 1}
    # one timer feeds the decode span and decode_s
    assert spans.snapshot()["store.decode"]["total_s"] == pytest.approx(
        d["decode_s"], rel=1e-9)
    reads = {a.get("read") for _, a, _ in recorded.events}
    assert len(reads) == 1 and None not in reads
    h2d = [a for n, a, _ in recorded.events if n == "store.h2d"]
    assert h2d == [{"bytes": x[2:5].nbytes, "read": reads.pop()}]


def test_framed_coo_slice_read_records_each_layer(recorded):
    io = ReadExecutor(max_workers=4)
    store = _store(io)
    t = sparse_tensor((6, 9, 8), density=0.2, seed=3)
    store.put(t, tensor_id="s", layout="coo", target_file_bytes=2048)
    with store.open("s") as ref:
        ref.header  # noqa: B018
        files = len(ref._adds(ref.codec.slice_filters(
            ref.header, [(1, 3), (0, 9), (0, 8)])))
        spans.reset()
        recorded.events.clear()
        before = _io_now(io)
        out = ref.read_device([(1, 3)])
        np.testing.assert_array_equal(np.asarray(out), t[1:3])
    io.shutdown()
    d = _io_delta(io, before)
    got = _counts()
    assert files >= 1 and d["gets"] == d["frames_decoded"] == files
    assert got == {"store.plan": 1, "store.fetch": files,
                   "store.decode": files, "store.parse": files,
                   "store.stage": 1, "store.h2d": 1, "store.dispatch": 1}
    assert len({a.get("read") for _, a, _ in recorded.events}) == 1


def test_cache_hits_fetch_and_decode_nothing(recorded):
    io = ReadExecutor(max_workers=2)
    store = _store(io)
    x = np.ones((4, 8, 8), np.uint8)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2)
    with store.open("x") as ref:
        ref.read_device([(0, 2)])
        spans.reset()
        ref.read_device([(0, 2)])
    io.shutdown()
    assert _counts() == {"store.plan": 1, "store.parse": 1,
                         "store.stage": 1, "store.h2d": 1}


def test_concurrent_reads_keep_their_ids_across_pools(recorded):
    io = ReadExecutor(max_workers=4, cache_bytes=0)
    store = _store(io)
    x = np.arange(8 * 4 * 16, dtype=np.float32).reshape(8, 4, 16)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2,
              target_file_bytes=300)
    refs = [store.open("x"), store.open("x")]
    for r in refs:
        r.header  # noqa: B018
    recorded.events.clear()
    barrier = threading.Barrier(2)
    errors = []

    def reader(ref, lo, hi):
        try:
            barrier.wait(timeout=10)
            ref.read_device([(lo, hi)])
        except BaseException as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(refs[0], 0, 1)),
               threading.Thread(target=reader, args=(refs[1], 2, 6))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads) and not errors
    for r in refs:
        r.close()
    io.shutdown()
    per_read = defaultdict(Counter)
    for name, attrs, _ in recorded.events:
        per_read[attrs.get("read")][name] += 1
    assert None not in per_read and len(per_read) == 2
    files = sorted(c["store.fetch"] for c in per_read.values())
    assert files == [1, 4]
    for c in per_read.values():
        n = c["store.fetch"]
        assert c["store.decode"] == c["store.parse"] == n
        assert c["store.plan"] == c["store.stage"] == c["store.h2d"] == 1


def test_off_read_path_constructs_no_annotation(spans_off, monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(spans, "_annotation", rec)
    io = ReadExecutor(max_workers=2)
    store = _store(io)
    x = np.ones((4, 8, 8), np.uint8)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2)
    store.put(sparse_tensor((4, 5, 6), density=0.2, seed=1), tensor_id="s",
              layout="coo")
    with store.open("x") as ref:
        ref.read_device([(1, 3)])
    with store.open("s") as ref:
        ref.read_device([(0, 2)])
    io.shutdown()
    assert rec.events == [] and spans.snapshot() == {}
    assert store.io_stats()["spans"] == {}


# ---------------------------------------------------------------------------
# wait counters
# ---------------------------------------------------------------------------

def test_waits_rise_under_a_slow_store_and_slow_decode():
    lm = LatencyModel(rtt_s=0.03, bandwidth_bps=1e12, virtual_clock=False)
    io = ReadExecutor(max_workers=4, decode_workers=1, cache_bytes=0)
    store = DeltaTensorStore(InMemoryObjectStore(latency=lm), "tensors",
                             io=io, compression="zstd")
    x = np.arange(8 * 4 * 16, dtype=np.float32).reshape(8, 4, 16)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2,
              target_file_bytes=300)
    decode_wire = io._decode_wire

    def slow_decode(*a, **kw):
        time.sleep(0.02)
        return decode_wire(*a, **kw)

    io._decode_wire = slow_decode
    with store.open("x") as ref:
        ref.header  # noqa: B018
        io.stats.reset()
        assert io.stats.fetch_wait_s == io.stats.decode_queue_s == 0.0
        np.testing.assert_array_equal(np.asarray(ref.read_device([(0, 4)])),
                                      x[0:4])
    s = store.io_stats()
    io.shutdown()
    # four files fetched side by side, decoded one at a time: the reader
    # waits at least one round trip, and frames queue behind the decoder
    assert s["fetch_wait_s"] >= 0.03
    assert s["decode_queue_s"] >= 0.02
    assert s["decode_s"] >= 4 * 0.02
    io.stats.reset()
    assert io.stats.fetch_wait_s == io.stats.decode_queue_s == 0.0
