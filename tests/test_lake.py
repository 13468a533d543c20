
import numpy as np
import pytest
from ._hypothesis_compat import given, settings, st  # skips property tests if hypothesis is missing

from repro.lake import (CommitConflict, DeltaLog, DeltaTable, InMemoryObjectStore,
                        LatencyModel, LocalFSObjectStore, ObjectNotFoundError,
                        PutIfAbsentError, ReadExecutor, columnar)


# ---------------------------------------------------------------------------
# columnar (parq-lite)
# ---------------------------------------------------------------------------

def test_columnar_roundtrip_mixed():
    cols = {
        "id": ["t1"] * 3 + ["t2"] * 2,
        "chunk_index": np.arange(5, dtype=np.int64),
        "payload": [b"a" * 10, b"", b"xyz", b"\x00\x01", b"q"],
        "dims": [np.array([4, 3]), np.array([4, 3]), np.array([4, 3]),
                 np.array([7]), np.array([7])],
        "score": np.linspace(0, 1, 5).astype(np.float32),
    }
    data, stats = columnar.write_table(cols)
    out = columnar.read_table(data)
    assert list(out["id"]) == cols["id"]
    np.testing.assert_array_equal(out["chunk_index"], cols["chunk_index"])
    assert out["payload"] == cols["payload"]
    for a, b in zip(out["dims"], cols["dims"]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(out["score"], cols["score"], rtol=0)
    assert stats["column_stats"]["chunk_index"] == {"min": 0, "max": 4}
    assert columnar.num_rows(data) == 5


def test_columnar_projection():
    cols = {"a": np.arange(10), "b": np.arange(10.0)}
    data, _ = columnar.write_table(cols)
    out = columnar.read_table(data, columns=["a"])
    assert set(out) == {"a"}
    with pytest.raises(KeyError):
        columnar.read_table(data, columns=["missing"])


def test_columnar_projection_never_decodes_skipped_list_block(monkeypatch):
    cols = {"a": np.arange(6), "dims": [np.array([4, i]) for i in range(6)],
            "b": np.arange(6.0)}
    data, _ = columnar.write_table(cols)
    decoded = []
    real = columnar._decode_column

    def spy(raw, meta):
        decoded.append(meta["name"])
        return real(raw, meta)

    monkeypatch.setattr(columnar, "_decode_column", spy)
    out = columnar.read_table(data, columns=["b", "a"])
    assert set(out) == {"a", "b"} and sorted(decoded) == ["a", "b"]
    decoded.clear()
    out, skipped = columnar.read_projected(data, ["a"], optional=["nope"])
    assert set(out) == {"a"} and decoded == ["a"] and skipped == 2
    decoded.clear()
    assert columnar.read_projected(data)[1] == 0 and len(decoded) == 3


def test_columnar_dictionary_compresses_repeats():
    # the paper's point: repeated metadata columns compress to ~nothing
    rep = {"meta": np.full(100_000, 7, dtype=np.int64)}
    uniq = {"meta": np.arange(100_000, dtype=np.int64)}
    rep_data, _ = columnar.write_table(rep)
    uniq_data, _ = columnar.write_table(uniq)
    assert len(rep_data) < len(uniq_data) / 100


@settings(max_examples=50, deadline=None)
@given(
    vals=st.lists(st.integers(-2**31, 2**31 - 1), min_size=0, max_size=200),
    dtype=st.sampled_from(["int32", "int64", "float32", "float64"]),
)
def test_columnar_array_roundtrip_property(vals, dtype):
    arr = np.asarray(vals, dtype=dtype)
    data, _ = columnar.write_table({"v": arr, "pad": np.zeros(len(arr))}) if len(arr) else (None, None)
    if data is None:
        return
    out = columnar.read_table(data)["v"]
    np.testing.assert_array_equal(out, arr)
    assert out.dtype == arr.dtype


@settings(max_examples=30, deadline=None)
@given(blobs=st.lists(st.binary(max_size=64), min_size=1, max_size=40))
def test_columnar_bytes_roundtrip_property(blobs):
    data, _ = columnar.write_table({"b": blobs})
    assert columnar.read_table(data)["b"] == blobs


# ---------------------------------------------------------------------------
# object stores
# ---------------------------------------------------------------------------

@pytest.fixture(params=["mem", "fs"])
def store(request, tmp_path):
    if request.param == "mem":
        return InMemoryObjectStore()
    return LocalFSObjectStore(str(tmp_path / "store"))


def test_object_store_basics(store):
    store.put("a/b/c", b"hello")
    assert store.get("a/b/c") == b"hello"
    assert store.head("a/b/c") == 5
    assert list(store.list("a/")) == ["a/b/c"]
    store.put("a/b/c", b"hi2")  # overwrite allowed without if_absent
    assert store.get("a/b/c") == b"hi2"
    with pytest.raises(PutIfAbsentError):
        store.put("a/b/c", b"x", if_absent=True)
    store.delete("a/b/c")
    with pytest.raises(ObjectNotFoundError):
        store.get("a/b/c")


def test_latency_model_accounting():
    lm = LatencyModel(rtt_s=0.01, bandwidth_bps=1e9, virtual_clock=True)
    s = InMemoryObjectStore(latency=lm)
    s.put("k", b"x" * 125_000_000)  # 1 Gb -> 1 s at 1 Gbps
    assert lm.elapsed_s == pytest.approx(1.01, rel=1e-6)
    lm.reset()
    s.get("k")
    assert lm.requests == 1 and lm.bytes_moved == 125_000_000


# ---------------------------------------------------------------------------
# delta log
# ---------------------------------------------------------------------------

def test_log_commit_snapshot_time_travel(store):
    log = DeltaLog(store, "tbl")
    v0 = log.commit([{"metaData": {"name": "t"}}])
    v1 = log.commit([{"add": {"path": "f1", "size": 10, "stats": {}}}])
    v2 = log.commit([{"add": {"path": "f2", "size": 20, "stats": {}}}])
    v3 = log.commit([{"remove": {"path": "f1"}}])
    assert (v0, v1, v2, v3) == (0, 1, 2, 3)
    snap = log.snapshot()
    assert set(snap.files) == {"f2"}
    # time travel
    assert set(log.snapshot(2).files) == {"f1", "f2"}
    assert set(log.snapshot(1).files) == {"f1"}
    assert log.snapshot().metadata == {"name": "t"}


def test_log_checkpoint_replay(store):
    log = DeltaLog(store, "tbl")
    for i in range(25):
        log.commit([{"add": {"path": f"f{i}", "size": i, "stats": {}}}])
    snap = log.snapshot()
    assert len(snap.files) == 25
    # a checkpoint file must exist (interval 10)
    assert any(k.endswith(".checkpoint.json") for k in store.list("tbl/_delta_log/"))
    # time travel before the checkpoint still works
    assert len(log.snapshot(4).files) == 5


def test_log_expected_version_fencing(store):
    log = DeltaLog(store, "tbl")
    log.commit([{"metaData": {}}])
    with pytest.raises(CommitConflict):
        log.commit([{"add": {"path": "x", "size": 1, "stats": {}}}], expected_version=5)


def test_log_crash_before_commit_invisible():
    store = InMemoryObjectStore()
    t = DeltaTable.create(store, "tbl")
    t.append({"a": np.arange(3)})
    # simulate a writer that uploads a data file but dies before commit
    add = t.append({"a": np.arange(7)}, commit=False)
    assert add["path"]  # the orphan exists in the store...
    batches = list(t.scan())
    assert len(batches) == 1 and len(batches[0]["a"]) == 3  # ...but is invisible
    # vacuum removes the orphan
    res = t.vacuum()
    assert res.files_deleted == 1 and res.bytes_reclaimed > 0


def test_log_latest_version_cache_refreshes_on_miss():
    # regression: a DeltaLog whose probe-forward latest cache went stale
    # under an EXTERNAL writer must refresh on a version miss instead of
    # raising ValueError for a commit that exists
    from repro.lake import FaultInjectingObjectStore, FaultRule

    inner = InMemoryObjectStore()
    faulty = FaultInjectingObjectStore(inner)
    writer = DeltaLog(inner, "tbl")
    writer.commit([{"metaData": {}}])
    reader = DeltaLog(faulty, "tbl")
    assert reader.latest_version() == 0
    v1 = writer.commit([{"add": {"path": "f", "size": 1, "stats": {}}}])
    # eventual consistency: the reader's forward head probes 404, so its
    # cached latest stays stale at 0...
    faulty.add_rule(FaultRule(op="head", key="_delta_log",
                              action="notfound", count=2))
    assert reader.latest_version() == 0
    # ...but an explicit request for the missed version invalidates the
    # cache and replays the commit
    snap = reader.snapshot(v1)
    assert snap.version == v1 and set(snap.files) == {"f"}
    assert reader.latest_version() >= v1


# ---------------------------------------------------------------------------
# delta table
# ---------------------------------------------------------------------------

def test_table_append_scan_skipping():
    store = InMemoryObjectStore(latency=LatencyModel())
    t = DeltaTable.create(store, "tensors/t1")
    rng = np.random.default_rng(0)
    for lo in range(0, 100, 10):
        t.append({"chunk_index": np.arange(lo, lo + 10),
                  "val": np.full(10, lo),
                  "payload": [rng.bytes(4096) for _ in range(10)]})
    assert t.version() == 10  # create + 10 appends

    store.latency.reset()
    full = t.read_all()
    full_bytes = store.latency.bytes_moved
    assert len(full["chunk_index"]) == 100

    store.latency.reset()
    sl = t.read_all(filters={"chunk_index": (42, 44)})
    slice_bytes = store.latency.bytes_moved
    np.testing.assert_array_equal(sl["chunk_index"], [42, 43, 44])
    # data skipping: the slice read touched ~1 file out of 10
    assert slice_bytes < full_bytes / 5


def test_fetch_adds_projection_without_filter_column_still_filters():
    io = ReadExecutor(max_workers=2)
    t = DeltaTable.create(InMemoryObjectStore(), "tensors/t1", io=io)
    for lo in range(0, 40, 10):
        t.append({"chunk_index": np.arange(lo, lo + 10),
                  "val": 2 * np.arange(lo, lo + 10),
                  "dims": [np.array([lo, i]) for i in range(10)]})
    filters = {"chunk_index": (12, 14)}
    adds = t.plan_scan(filters=filters)
    assert len(adds) == 1
    (batch,) = t.fetch_adds(adds, ["val"], filters=filters)
    assert set(batch) == {"val"}
    np.testing.assert_array_equal(batch["val"], [24, 26, 28])
    assert io.stats.parse_columns_skipped == 1  # dims; chunk_index was masked
    # no projection: every column, nothing skipped
    (full,) = t.fetch_adds(adds, filters=filters)
    assert set(full) == {"chunk_index", "val", "dims"}
    assert io.stats.parse_columns_skipped == 1


def test_table_time_travel_and_compact():
    store = InMemoryObjectStore()
    t = DeltaTable.create(store, "tbl")
    t.append({"x": np.arange(4)})
    v_before = t.version()
    t.append({"x": np.arange(4, 8)})
    assert len(t.read_all()["x"]) == 8
    assert len(t.read_all(version=v_before)["x"]) == 4
    t.compact()
    assert len(t.files()) == 1
    np.testing.assert_array_equal(np.sort(t.read_all()["x"]), np.arange(8))


def test_two_phase_commit_atomicity():
    store = InMemoryObjectStore()
    t = DeltaTable.create(store, "tbl")
    adds = [t.append({"x": np.arange(i, i + 2)}, commit=False) for i in range(0, 6, 2)]
    assert t.read_all() == {}  # nothing visible yet
    t.commit_adds(adds, op="CHECKPOINT")
    assert len(t.read_all()["x"]) == 6  # all-or-nothing
