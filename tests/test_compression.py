"""Chunk-blob compression subsystem: codecs, shuffle, and every layer above.

Covers the acceptance surface of the compression tentpole:

* codec x layout round-trips (dense/FTSF, COO, CSR, slice reads);
* byte-identical reads of pre-compression tables (frame passthrough);
* recompress-via-compact under a live lease (migration safety);
* shuffle∘unshuffle identity for all fixed-width dtypes (property test);
* decoded block cache, add-action metadata, storage_stats accounting,
  store-manifest defaults, and the gc CLI ``--recompress`` path.
"""

import json

import numpy as np
import pytest

from repro.core import DeltaTensorStore, SparseCOO
from repro.lake import (InMemoryObjectStore, LatencyModel, LocalFSObjectStore,
                        ReadExecutor, available_codecs, decode_frame,
                        encode_frame, frame_info, parse_compression,
                        register_compressor)
from repro.lake.compression import (CompressionSpec, DeltaBase,
                                    UnknownCodecError, byte_shuffle,
                                    byte_undelta, byte_unshuffle, unframe)
from repro.launch import gc as gc_cli

# an identity codec that never shrinks anything: the deterministic way to
# exercise the incompressible-fallback path through the full store stack
register_compressor("identity-test", lambda b: b, lambda b: b)

from ._hypothesis_compat import given, settings, st

RNG = np.random.default_rng(7)

# every codec this process can actually run (zstd/lz4 join when importable;
# the identity test codec is excluded — it exists to force the fallback)
CODECS = [c for c in available_codecs() if c != "identity-test"]
SPECS = [c for c in CODECS if c != "none"] + \
        [f"{c}+shuffle" for c in CODECS if c != "none"]


def compressible(shape, dtype=np.float32):
    """Low-mantissa-entropy floats: the workload compression should win on."""
    x = RNG.standard_normal(shape)
    return (np.round(x * 64) / 64).astype(dtype)


def fresh(compression=None, **kw):
    io = ReadExecutor(max_workers=4)
    store = DeltaTensorStore(InMemoryObjectStore(), "tensors", io=io,
                             compression=compression, **kw)
    return store


# ---------------------------------------------------------------------------
# spec parsing / registry
# ---------------------------------------------------------------------------


def test_parse_compression_specs():
    assert parse_compression(None) is None
    s = parse_compression("zlib+shuffle")
    assert s == CompressionSpec("zlib", True) and s.id == "zlib+shuffle"
    assert parse_compression("ZLIB").id == "zlib"
    assert not parse_compression("none").active
    assert parse_compression(CompressionSpec("lzma", False)).id == "lzma"
    with pytest.raises(UnknownCodecError):
        parse_compression("snappy")
    with pytest.raises(ValueError):
        parse_compression("zlib+zlib+shuffle")
    with pytest.raises(ValueError):
        parse_compression(42)
    with pytest.raises(ValueError):
        # shuffle-without-codec would disable legacy block compression
        # while compressing nothing: a silent space regression
        parse_compression("none+shuffle")


def test_available_codecs_stdlib_floor():
    assert {"none", "zlib", "lzma"} <= set(CODECS)


# ---------------------------------------------------------------------------
# frame format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", SPECS)
def test_frame_roundtrip(spec):
    raw = compressible(4096).tobytes()
    frame, codec_id = encode_frame(raw, parse_compression(spec), itemsize=4)
    assert decode_frame(frame) == raw
    info = frame_info(frame)
    assert info["raw_size"] == len(raw)
    assert codec_id == spec  # compressible payload: no fallback


def test_frame_passthrough_unframed():
    for blob in (b"", b"PQL1junk", b'{"json": true}', bytes(100)):
        assert decode_frame(blob) == blob
        assert frame_info(blob) is None


def test_delta_frame_unframes_to_its_residue_and_decode_frame_refuses_it():
    base = compressible(4096).tobytes()
    raw = bytearray(base)
    raw[100:108] = bytes(8)
    frame, _ = encode_frame(bytes(raw), parse_compression("zlib+shuffle"),
                            itemsize=4,
                            delta_base=DeltaBase("t/base.pq", base, "h"))
    info = frame_info(frame)
    assert info["delta_base"] == "t/base.pq"
    assert byte_undelta(unframe(frame, info), base) == bytes(raw)
    with pytest.raises(ValueError, match="delta frame"):
        decode_frame(frame)


def test_frame_incompressible_falls_back_to_raw_unframed():
    raw = RNG.integers(0, 256, 1 << 16, dtype=np.uint8).tobytes()
    stored, codec_id = encode_frame(raw, parse_compression("zlib+shuffle"),
                                    itemsize=4)
    assert codec_id == "none"
    assert stored == raw  # zero overhead: raw bytes, no frame
    assert decode_frame(stored) == raw


def test_fallback_put_records_request_only():
    """A put whose frame cannot pay for itself stores raw unframed files
    with ratio exactly 1.0 and only a codecRequested marker (which keeps
    recompress idempotent). The registered identity codec triggers this
    path deterministically: it never shrinks anything."""
    store = fresh(compression="identity-test")
    x = RNG.integers(0, 256, (8, 64, 64), dtype=np.uint8)
    store.put(x, layout="ftsf", tensor_id="t")
    adds = store.catalog().entry("t").chunk_adds
    assert all("codec" not in a and "rawSize" not in a for a in adds)
    assert all(a.get("codecRequested") == "identity-test" for a in adds)
    st = store.storage_stats()
    assert st["by_codec"]["none"]["ratio"] == 1.0  # exact, never < 1
    assert np.array_equal(store.get("t"), x)
    # idempotent: nothing to rewrite under the same requested codec
    assert not store.compact(recompress="identity-test")[0]


def test_storage_never_inflates_past_raw():
    """The fallback guarantee at the store level: whatever the data,
    stored physical bytes never exceed logical bytes per file."""
    store = fresh(compression="zlib+shuffle")
    store.put(RNG.integers(0, 256, (8, 64, 64), dtype=np.uint8),
              layout="ftsf", tensor_id="noise")
    store.put(compressible((8, 64, 64)), layout="ftsf", tensor_id="smooth")
    for tid in ("noise", "smooth"):
        for add in store.catalog().entry(tid).chunk_adds:
            assert int(add.get("rawSize", add["size"])) >= int(add["size"])
    assert store.storage_stats()["ratio"] >= 1.0


# ---------------------------------------------------------------------------
# byte shuffle
# ---------------------------------------------------------------------------

FIXED_WIDTH_DTYPES = ["int8", "uint8", "int16", "uint16", "int32", "uint32",
                      "int64", "uint64", "float16", "float32", "float64",
                      "complex64", "complex128", "bool"]


@pytest.mark.parametrize("dtype", FIXED_WIDTH_DTYPES)
def test_shuffle_identity_every_fixed_width_dtype(dtype):
    it = np.dtype(dtype).itemsize
    for n in (0, 1, it - 1, it, 7 * it + 3, 4096):
        raw = RNG.integers(0, 256, max(n, 0), dtype=np.uint8).tobytes()
        assert byte_unshuffle(byte_shuffle(raw, it), it) == raw


@settings(max_examples=200, deadline=None)
@given(st.binary(min_size=0, max_size=4096),
       st.integers(min_value=1, max_value=16))
def test_shuffle_unshuffle_identity_property(raw, itemsize):
    """shuffle∘unshuffle is the identity for any buffer and item width."""
    assert byte_unshuffle(byte_shuffle(raw, itemsize), itemsize) == raw


def test_shuffle_groups_bytes():
    # [0,1,2,3]*k shuffled at itemsize 4 puts all the 0s first: runs a
    # byte codec can crush — the reason the filter exists
    raw = bytes(range(4)) * 64
    shuf = byte_shuffle(raw, 4)
    assert shuf[:64] == bytes(64 * [0])
    assert shuf[64:128] == bytes(64 * [1])


# ---------------------------------------------------------------------------
# codec x layout round trips through the store
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", SPECS)
def test_dense_ftsf_roundtrip(spec):
    store = fresh(compression=spec)
    x = compressible((16, 32, 32))
    store.put(x, layout="ftsf", tensor_id="t")
    assert np.array_equal(store.get("t"), x)
    # slice read through codec pushdown on compressed chunk files
    assert np.array_equal(store.get_slice("t", [(3, 9)]), x[3:9])
    with store.open("t") as ref:
        assert np.array_equal(ref[2:5, 1:7], x[2:5, 1:7])


@pytest.mark.parametrize("spec", ["zlib+shuffle", "lzma"])
def test_sparse_layouts_roundtrip(spec):
    store = fresh(compression=spec)
    dense = np.zeros((64, 64), dtype=np.float32)
    dense[RNG.integers(0, 64, 200), RNG.integers(0, 64, 200)] = \
        RNG.standard_normal(200).astype(np.float32)
    store.put(dense, layout="coo", tensor_id="c")
    store.put(dense, layout="csr", tensor_id="r")
    assert np.array_equal(store.get("c"), dense)
    assert np.array_equal(store.get("r"), dense)
    coo = store.get_coo("c")
    assert isinstance(coo, SparseCOO)
    assert np.array_equal(coo.to_dense(), dense)


def test_int_dtype_roundtrip():
    store = fresh(compression="zlib+shuffle")
    x = RNG.integers(-1000, 1000, (32, 128), dtype=np.int64)
    store.put(x, layout="ftsf", tensor_id="i")
    got = store.get("i")
    assert got.dtype == np.int64 and np.array_equal(got, x)


def test_per_put_override_beats_store_default():
    store = fresh(compression="zlib+shuffle")
    x = compressible((8, 64, 64))
    store.put(x, layout="ftsf", tensor_id="default")
    store.put(x, layout="ftsf", tensor_id="raw", compression="none")
    by = store.storage_stats()["by_codec"]
    # headers are always raw; the override kept "raw"'s chunks raw too
    raw_chunk = [a for a in store.catalog().entry("raw").chunk_adds]
    assert all("codec" not in a for a in raw_chunk)
    assert by["zlib+shuffle"]["files"] >= 1


# ---------------------------------------------------------------------------
# back-compat: pre-compression tables
# ---------------------------------------------------------------------------


def test_uncompressed_layout_byte_identical():
    """A store without compression writes the exact legacy byte layout."""
    obj = InMemoryObjectStore()
    store = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2))
    x = compressible((8, 32))
    store.put(x, layout="ftsf", tensor_id="t")
    for key in obj.list("tensors/"):
        if key.endswith(".pql"):
            assert obj.get(key)[:4] == b"PQL1"  # no frame, plain parq-lite
    adds = store.catalog().entry("t").header_adds + \
        store.catalog().entry("t").chunk_adds
    assert all("codec" not in a and "rawSize" not in a for a in adds)
    # no manifest either: byte-compatible with pre-sharding tables
    assert not obj.exists("tensors/_store_manifest.json")


def test_precompression_table_reads_back_identically():
    """Tables written by a codec-less client read fine from any client."""
    obj = InMemoryObjectStore()
    old = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2))
    x = compressible((8, 32))
    old.put(x, layout="ftsf", tensor_id="t")
    # a new client configured with a default codec changes nothing about
    # how existing files read back (codec "none" implied per file)
    new = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2),
                           compression="zlib+shuffle")
    assert np.array_equal(new.get("t"), x)
    stats = new.storage_stats()
    assert set(stats["by_codec"]) == {"none"}
    assert stats["ratio"] == 1.0


def test_mixed_codec_store_reads_all():
    store = fresh(compression=None)
    xs = {}
    for i, spec in enumerate([None, "zlib", "zlib+shuffle", "lzma"]):
        x = compressible((4, 32, 32))
        xs[f"t{i}"] = x
        store.put(x, layout="ftsf", tensor_id=f"t{i}", compression=spec)
    for tid, x in xs.items():
        assert np.array_equal(store.get(tid), x)


# ---------------------------------------------------------------------------
# add-action metadata + storage_stats
# ---------------------------------------------------------------------------


def test_add_action_records_codec_and_sizes():
    store = fresh(compression="zlib+shuffle")
    x = compressible((8, 64, 64))
    store.put(x, layout="ftsf", tensor_id="t")
    entry = store.catalog().entry("t")
    assert entry.header_adds and "codec" not in entry.header_adds[0]
    for add in entry.chunk_adds:
        assert add["codec"] == "zlib+shuffle"
        assert add["itemsize"] == 4
        assert add["rawSize"] > add["size"]  # it actually compressed
    # physical tensor bytes (what refs report) shrink accordingly
    with store.open("t") as ref:
        assert ref.nbytes < x.nbytes


def test_storage_stats_accounting():
    store = fresh(compression="zlib+shuffle")
    x = compressible((8, 64, 64))
    store.put(x, layout="ftsf", tensor_id="t")
    st = store.storage_stats()
    assert st["tensors"] == 1
    assert st["physical_bytes"] < st["logical_bytes"]
    assert st["ratio"] > 1.5
    assert st["compression"] == "zlib+shuffle"
    total = sum(r["physical_bytes"] for r in st["by_codec"].values())
    assert total == st["physical_bytes"]
    # physical matches what the object store actually holds for data files
    empty = fresh().storage_stats()
    assert empty["ratio"] == 1.0 and empty["files"] == 0


# ---------------------------------------------------------------------------
# read path: the cache stores decoded blocks, the wire moves compressed
# ---------------------------------------------------------------------------


def test_block_cache_stores_decoded_blocks():
    lm = LatencyModel(virtual_clock=True)
    obj = InMemoryObjectStore(latency=lm)
    io = ReadExecutor(max_workers=2)
    store = DeltaTensorStore(obj, "tensors", io=io,
                             compression="zlib+shuffle")
    x = compressible((8, 64, 64))
    store.put(x, layout="ftsf", tensor_id="t")
    io.stats.reset()
    assert np.array_equal(store.get("t"), x)
    first = io.stats.frames_decoded
    assert first >= 1
    assert io.stats.frame_bytes_wire < io.stats.frame_bytes_decoded
    assert np.array_equal(store.get("t"), x)  # warm: cache hit, no decode
    assert io.stats.frames_decoded == first
    assert io.stats.cache_hits >= 1


def test_wire_charges_compressed_bytes():
    def read_bytes(compression):
        lm = LatencyModel(virtual_clock=True)
        obj = InMemoryObjectStore(latency=lm)
        io = ReadExecutor(max_workers=2, cache_bytes=0)
        store = DeltaTensorStore(obj, "tensors", io=io,
                                 compression=compression)
        x = compressible((16, 64, 64))
        store.put(x, layout="ftsf", tensor_id="t")
        lm.reset()
        assert np.array_equal(store.get("t"), x)
        return x.nbytes, lm.bytes_moved

    logical, wire = read_bytes("zlib+shuffle")
    # the full read moved less than half the tensor's raw bytes over the
    # modeled wire, and strictly less than the legacy layout moves (which
    # already block-zlibs opportunistically — shuffle beats it further)
    assert wire < logical / 2
    assert wire < read_bytes(None)[1]


# ---------------------------------------------------------------------------
# maintenance: compact preserves codecs, recompress migrates, leases hold
# ---------------------------------------------------------------------------


def test_compact_preserves_codec():
    store = fresh(compression="zlib+shuffle")
    x = compressible((8, 64, 64))
    # two files per partition group: put in halves via overwrite-free tids
    store.put(x, layout="ftsf", tensor_id="t", target_file_bytes=x.nbytes // 3)
    before = store.storage_stats()
    res = store.compact()
    assert res[0].files_compacted > 0
    after = store.storage_stats()
    assert np.array_equal(store.get("t"), x)
    for add in store.catalog().entry("t").chunk_adds:
        assert add["codec"] == "zlib+shuffle"
    # compacting must not inflate the store back toward raw bytes
    assert after["physical_bytes"] <= before["physical_bytes"] * 1.1


def test_recompress_under_live_lease():
    """The migration path: recompress while a pinned ref reads old bytes."""
    store = fresh(compression=None)
    x = compressible((8, 64, 64))
    store.put(x, layout="ftsf", tensor_id="t")
    raw_bytes = store.storage_stats()["physical_bytes"]

    ref = store.open("t")  # leases the raw generation
    res = store.compact(recompress="zlib+shuffle")
    assert res[0] and res[0].files_recompressed > 0
    # pinned ref still reads its snapshot byte-identically
    assert np.array_equal(ref.read(), x)
    # new snapshot is compressed: smaller than the legacy layout (which
    # already block-zlibs what it can) and >=2x under the logical bytes
    migrated = store.storage_stats()
    assert "zlib+shuffle" in migrated["by_codec"]
    assert migrated["physical_bytes"] < raw_bytes
    assert migrated["ratio"] > 2.0
    assert np.array_equal(store.get("t"), x)

    # vacuum spares the leased raw generation, then reclaims it on release
    spared = store.vacuum(keep_versions=1)
    assert np.array_equal(ref.read(), x)
    ref.close()
    freed = store.vacuum(keep_versions=1)
    assert sum(r.bytes_reclaimed for r in freed) > 0
    assert np.array_equal(store.get("t"), x)
    assert sum(r.bytes_reclaimed for r in spared + freed) > 0


def test_recompress_is_idempotent():
    store = fresh(compression=None)
    store.put(compressible((8, 64, 64)), layout="ftsf", tensor_id="t")
    assert store.compact(recompress="zlib+shuffle")[0]
    v = store.version()
    # second pass: every file already carries the target codec -> no-op,
    # commit-free (maintenance crons must not grow the log doing nothing)
    assert not store.compact(recompress="zlib+shuffle")[0]
    assert store.version() == v


def test_recompress_idempotent_for_one_byte_dtypes():
    """itemsize-1 tensors skip shuffle, so the actual codec id drops the
    '+shuffle' suffix — codecRequested must still match the target or a
    recompress cron would rewrite (and grow the log) forever."""
    store = fresh(compression=None)
    x = np.tile(np.arange(64, dtype=np.uint8), (8, 64, 1))  # compressible
    store.put(x, layout="ftsf", tensor_id="mask")
    assert store.compact(recompress="zlib+shuffle")[0]
    v = store.version()
    for add in store.catalog().entry("mask").chunk_adds:
        assert add["codec"] == "zlib"  # shuffle skipped: itemsize 1
        assert add["codecRequested"] == "zlib+shuffle"
    for _ in range(3):  # repeated cron runs: commit-free no-ops
        assert not store.compact(recompress="zlib+shuffle")[0]
    assert store.version() == v
    assert np.array_equal(store.get("mask"), x)


def test_recompress_sharded_store():
    io = ReadExecutor(max_workers=4)
    store = DeltaTensorStore(InMemoryObjectStore(), "tensors", io=io,
                             shards=3)
    xs = {f"t{i}": compressible((4, 32, 32)) for i in range(6)}
    with store.batch() as b:
        for tid, x in xs.items():
            b.put(x, layout="ftsf", tensor_id=tid)
    results = store.compact(recompress="zlib+shuffle")
    assert sum(r.files_recompressed for r in results) >= 6
    for tid, x in xs.items():
        assert np.array_equal(store.get(tid), x)
    assert store.storage_stats()["ratio"] > 1.5


# ---------------------------------------------------------------------------
# manifest default + unknown-codec failure mode
# ---------------------------------------------------------------------------


def test_manifest_records_default_and_later_clients_inherit():
    obj = InMemoryObjectStore()
    DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2),
                     compression="zlib+shuffle")
    manifest = json.loads(obj.get("tensors/_store_manifest.json"))
    assert manifest["compression"] == "zlib+shuffle"
    # a later client with no explicit arg inherits the recorded default
    client = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2))
    assert client.compression is not None
    assert client.compression.id == "zlib+shuffle"
    x = compressible((4, 32, 32))
    client.put(x, layout="ftsf", tensor_id="t")
    assert client.storage_stats()["by_codec"]["zlib+shuffle"]["files"] >= 1


def test_sharded_manifest_records_compression():
    obj = InMemoryObjectStore()
    DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2),
                     shards=2, compression="lzma")
    manifest = json.loads(obj.get("tensors/_store_manifest.json"))
    assert manifest["shards"] == 2 and manifest["compression"] == "lzma"
    client = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2))
    assert client.compression.id == "lzma"


def test_opening_existing_table_does_not_write_manifest():
    obj = InMemoryObjectStore()
    store = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2))
    store.put(compressible((4, 16)), layout="ftsf", tensor_id="t")
    # opening with a codec default must not mutate a pre-existing store
    DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2),
                     compression="zlib+shuffle")
    assert not obj.exists("tensors/_store_manifest.json")


def test_manifest_with_unavailable_codec_still_opens_for_reads():
    """A manifest naming a codec this process lacks (e.g. zstd on a
    stdlib-only client) must not block opening: reads work on whatever
    frames ARE decodable; this client just degrades to raw writes."""
    obj = InMemoryObjectStore()
    store = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2),
                             compression="zlib+shuffle")
    x = compressible((4, 32, 32))
    store.put(x, layout="ftsf", tensor_id="t")
    manifest = json.loads(obj.get("tensors/_store_manifest.json"))
    manifest["compression"] = "imaginary-codec+shuffle"
    obj.delete("tensors/_store_manifest.json")
    obj.put("tensors/_store_manifest.json",
            json.dumps(manifest).encode("utf-8"))
    client = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2))
    assert client.compression is None  # degraded, not dead
    assert np.array_equal(client.get("t"), x)  # zlib frames still decode
    client.put(x, layout="ftsf", tensor_id="u")  # writes land raw
    assert all("codec" not in a
               for a in client.catalog().entry("u").chunk_adds)
    # an EXPLICIT unknown codec still fails fast
    with pytest.raises(UnknownCodecError):
        DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2),
                         compression="imaginary-codec")


def test_unknown_codec_fails_fast():
    with pytest.raises(UnknownCodecError):
        fresh(compression="snappy+shuffle")
    store = fresh()
    with pytest.raises(UnknownCodecError):
        store.put(np.ones(4), layout="ftsf", tensor_id="t",
                  compression="brotli")
    assert "t" not in store.catalog()  # nothing staged, nothing leaked


# ---------------------------------------------------------------------------
# gc CLI migration path
# ---------------------------------------------------------------------------


def test_gc_cli_recompress_roundtrip(tmp_path, capsys):
    obj = LocalFSObjectStore(str(tmp_path))
    store = DeltaTensorStore(obj, "tensors", io=ReadExecutor(max_workers=2))
    x = compressible((8, 64, 64))
    store.put(x, layout="ftsf", tensor_id="t")
    raw_bytes = store.storage_stats()["physical_bytes"]

    rc = gc_cli.main(["--dir", str(tmp_path), "--root", "tensors",
                      "--recompress", "zlib+shuffle", "--vacuum",
                      "--keep-versions", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "recompressed" in out and "storage after recompress" in out

    reopened = DeltaTensorStore(obj, "tensors",
                                io=ReadExecutor(max_workers=2))
    assert np.array_equal(reopened.get("t"), x)
    stats = reopened.storage_stats()
    assert stats["physical_bytes"] < raw_bytes
    assert stats["ratio"] > 2.0


# ---------------------------------------------------------------------------
# per-codec compression levels ("<codec>:<level>[+shuffle]")
# ---------------------------------------------------------------------------


def test_parse_compression_levels():
    s = parse_compression("zlib:9+shuffle")
    assert (s.codec, s.level, s.shuffle) == ("zlib", 9, True)
    assert s.id == "zlib:9+shuffle"
    assert parse_compression(s.id) == s          # id round-trips
    assert parse_compression("zlib").level is None
    for bad in ("zlib:", ":9", "zlib:x", "zlib:99", "none:3"):
        with pytest.raises(ValueError):
            parse_compression(bad)


def test_frame_header_records_level():
    raw = compressible((64, 64)).tobytes()
    frame, codec_id = encode_frame(raw, parse_compression("zlib:9"))
    assert codec_id == "zlib:9"
    info = frame_info(frame)
    assert info["codec"] == "zlib" and info["level"] == 9
    assert decode_frame(frame) == raw
    # level-less frames keep the old header shape (no "level" key)
    frame0, _ = encode_frame(raw, parse_compression("zlib"))
    assert "level" not in frame_info(frame0)
    assert decode_frame(frame0) == raw


def test_level_tradeoff_decodes_identically():
    raw = compressible((128, 256)).tobytes()
    lo, _ = encode_frame(raw, parse_compression("zlib:1"))
    hi, _ = encode_frame(raw, parse_compression("zlib:9"))
    assert decode_frame(lo) == raw == decode_frame(hi)
    assert len(hi) <= len(lo)  # more effort never stores more (zlib)


def test_recompress_across_levels_is_idempotent():
    store = fresh(compression="zlib:1+shuffle")
    x = compressible((8, 64, 64))
    store.put(x, layout="ftsf", tensor_id="t")
    res = store.compact(recompress="zlib:9+shuffle")
    assert sum(r.files_recompressed for r in res) > 0
    assert np.array_equal(store.get("t"), x)
    # the add-actions now record the levelled codec id: a second pass
    # under the same spec must be a commit-free no-op
    again = store.compact(recompress="zlib:9+shuffle")
    assert sum(r.files_recompressed for r in again) == 0
    assert all(r.version is None for r in again)
    assert np.array_equal(store.get("t"), x)


def test_level_store_default_roundtrip():
    store = fresh(compression="zlib:9+shuffle")
    x = compressible((4, 32, 32))
    store.put(x, layout="ftsf", tensor_id="t")
    codecs = store.storage_stats()["by_codec"]
    assert any(c.startswith("zlib:9") for c in codecs), codecs
    assert np.array_equal(store.get("t"), x)
