"""Device read path: read_device byte-identity, staged decode, pipeline stats.

Covers the PR's acceptance surface on CPU (jax device = host):

* ``read_device`` full/slice/COO results are byte-identical to the host
  ``read``/``read_slice``/``read_coo`` decode for every device-exact dtype;
* non-canonical dtypes (f64/i64 without x64) fall back to numpy, still exact;
* the staged decode pool produces the same bytes as inline decode and fills
  the new ``ReadStats`` counters (``decode_s``, ``decodes_offloaded``);
* ``LatencyModel.charge_compute`` keeps ``elapsed_s`` = pipelined makespan
  while ``io_elapsed_s`` stays pure wire time;
* ``read_many(device=True)`` and ``StreamLoader(device=True)`` land batches
  on device and bump ``bytes_to_device``.
"""

import numpy as np
import pytest

from repro.core import DeltaTensorStore
from repro.data.stream import StreamLoader
from repro.lake import (ChunkAssembler, InMemoryObjectStore, LatencyModel,
                        ReadExecutor, device)

from .test_encodings import sparse_tensor

RNG = np.random.default_rng(23)

# dtypes jax canonicalizes losslessly on CPU without x64
EXACT_DTYPES = ["float32", "float16", "int32", "int16", "uint8", "complex64",
                "bool"]


def make_store(io=None, compression=None):
    obj = InMemoryObjectStore()
    return DeltaTensorStore(obj, "tensors", io=io or ReadExecutor(max_workers=4),
                            compression=compression)


def dense(shape, dtype, seed=0):
    x = np.random.default_rng(seed).standard_normal(shape)
    if np.dtype(dtype).kind in "iub":
        return (x * 10).astype(dtype)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# byte identity: read_device vs host decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", EXACT_DTYPES)
def test_read_device_full_byte_identical(dtype):
    store = make_store()
    x = dense((6, 4, 8), dtype, seed=1)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2)
    with store.open("x") as ref:
        out, info = ref.read_device(with_info=True)
        want = ref.read()
    assert info.path == "staged" and info.on_device
    got = np.asarray(out)
    assert got.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, x)


def test_read_device_slice_byte_identical():
    store = make_store()
    x = dense((16, 3, 8, 8), "float32", seed=2)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=3)
    spec = [(4, 11), None, None, None]
    with store.open("x") as ref:
        out, info = ref.read_device(spec, with_info=True)
        want = ref.read_slice(spec)
    assert info.path == "staged" and info.on_device
    np.testing.assert_array_equal(np.asarray(out), want)
    # only the 7 wanted chunks were staged on the host, not the full tensor
    assert info.host_staged_bytes == 7 * 3 * 8 * 8 * 4
    assert info.host_staged_bytes < x.nbytes


def test_read_device_subchunk_slice_crops_on_device():
    store = make_store()
    x = dense((8, 6, 10), "float32", seed=3)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2)
    spec = [(2, 5), (1, 4), (0, 7)]   # trailing dims narrow inside the chunk
    with store.open("x") as ref:
        out, info = ref.read_device(spec, with_info=True)
        want = ref.read_slice(spec)
    assert info.on_device
    np.testing.assert_array_equal(np.asarray(out), want)


def test_read_device_coo_scatter_byte_identical():
    store = make_store()
    x = sparse_tensor((64, 64), density=0.012, seed=4).astype(np.float32)
    store.put(x, tensor_id="s", layout="coo")
    with store.open("s") as ref:
        out, info = ref.read_device(with_info=True)
        want = ref.read()
    assert info.path == "coo_scatter" and info.on_device
    np.testing.assert_array_equal(np.asarray(out), want)
    # sparse staging beats densify-then-transfer on the host
    assert info.host_staged_bytes < x.nbytes
    assert info.device_bytes == x.nbytes


def test_read_device_coo_complex_values():
    # complex can't go through Pallas; the jnp reference scatter keeps it
    # on-device and exact
    store = make_store()
    x = np.zeros((16, 16), dtype=np.complex64)
    x[3, 4] = 1 + 2j
    x[9, 1] = -0.5j
    store.put(x, tensor_id="c", layout="coo")
    with store.open("c") as ref:
        out, info = ref.read_device(with_info=True)
    assert info.path == "coo_scatter" and info.on_device
    np.testing.assert_array_equal(np.asarray(out), x)


def test_read_device_coo_slice():
    store = make_store()
    x = sparse_tensor((32, 48), density=0.05, seed=5).astype(np.float32)
    store.put(x, tensor_id="s", layout="coo")
    spec = [(8, 24), (0, 48)]
    with store.open("s") as ref:
        out, info = ref.read_device(spec, with_info=True)
        want = ref.read_slice(spec)
    assert info.path == "coo_scatter"
    np.testing.assert_array_equal(np.asarray(out), want)


def _coo_result_arrays(reader, store, ref, spec):
    """The arrays one read of ``ref`` returns, for a bitwise compare."""
    if reader == "read_device":
        return [np.asarray(ref.read_device(spec))]
    if reader == "read_slice":
        return [ref.read_slice(spec)]
    if reader == "read_coo":
        t = ref.read_coo()
        return [t.indices, t.values, np.asarray(t.shape)]
    (out,) = store.read_many([(ref.tensor_id, spec)], device=True)
    assert device.is_device_array(out)
    return [np.asarray(out)]


@pytest.mark.parametrize("reader", ["read_device", "read_slice", "read_coo",
                                    "read_many_device"])
def test_coo_reads_parse_only_codec_columns(reader, monkeypatch):
    store = make_store()
    x = sparse_tensor((24, 8, 16), density=0.1, seed=6).astype(np.float32)
    store.put(x, tensor_id="s", layout="coo", target_file_bytes=1024)
    spec = [(5, 17), None, (3, 11)]
    with store.open("s") as ref:
        filters = ref.codec.slice_filters(
            ref.header, ((5, 17), (0, 8), (3, 11)))
        files = len(ref._adds(filters)) if reader != "read_coo" \
            else ref.n_chunk_files
        # the slice crosses file boundaries without reading every file
        assert 1 < files and (reader == "read_coo" or files < ref.n_chunk_files)
        store.io.stats.reset()
        got = _coo_result_arrays(reader, store, ref, spec)
        # the per-row dense_shape block, once per file parsed
        assert store.io_stats()["parse_columns_skipped"] == files > 0
        monkeypatch.setattr(type(ref.codec), "chunk_columns",
                            lambda self, header: None)
        store.io.stats.reset()
        want = _coo_result_arrays(reader, store, ref, spec)
        assert store.io_stats()["parse_columns_skipped"] == 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    if reader != "read_coo":
        np.testing.assert_array_equal(got[0], x[5:17, :, 3:11])


def test_ftsf_reads_parse_every_column():
    store = make_store()
    x = dense((8, 4, 4), "float32", seed=13)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2)
    spec = [(1, 6), None, None]
    with store.open("x") as ref:
        assert ref.codec.chunk_columns(ref.header) is None
        np.testing.assert_array_equal(np.asarray(ref.read_device(spec)), x[1:6])
        np.testing.assert_array_equal(ref.read_slice(spec), x[1:6])
        np.testing.assert_array_equal(ref.read(), x)
    (out,) = store.read_many([("x", spec)], device=True)
    np.testing.assert_array_equal(np.asarray(out), x[1:6])
    assert store.io_stats()["parse_columns_skipped"] == 0


@pytest.mark.parametrize("dtype", ["float64", "int64"])
def test_read_device_noncanonical_dtype_falls_back_exact(dtype):
    # without jax x64 these would silently downcast; the path must stay numpy
    if device.device_dtype_exact(dtype):
        pytest.skip("x64 enabled: dtype is device-exact here")
    store = make_store()
    x = dense((4, 4, 6), dtype, seed=6)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2)
    with store.open("x") as ref:
        out, info = ref.read_device(with_info=True)
    assert info.path == "host_fallback" and not info.on_device
    assert isinstance(out, np.ndarray) and out.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(out, x)


def test_read_device_unsliceable_codec_raises(monkeypatch):
    from repro.core.encodings.ftsf import FTSFCodec
    store = make_store()
    store.put(dense((4, 8), "float32"), tensor_id="x", layout="ftsf")
    monkeypatch.setattr(FTSFCodec, "supports_slice", False)
    with store.open("x") as ref:
        with pytest.raises(NotImplementedError):
            ref.read_device([(0, 2), None])


def test_get_device_wrapper_and_bytes_to_device():
    store = make_store()
    x = dense((8, 16), "float32", seed=7)
    store.put(x, tensor_id="x", layout="ftsf")
    store.io.stats.reset()
    out = store.get_device("x")
    np.testing.assert_array_equal(np.asarray(out), x)
    assert store.io_stats()["bytes_to_device"] == x.nbytes


# ---------------------------------------------------------------------------
# ChunkAssembler
# ---------------------------------------------------------------------------

def test_chunk_assembler_gathers_arrival_order():
    asm = ChunkAssembler(3, 4, np.float32)
    rows = np.arange(12, dtype=np.float32).reshape(3, 4)
    # arrive out of order: slot 2 first
    for pos in (2, 0, 1):
        asm.add(pos, rows[pos].tobytes())
    assert asm.staged_bytes == rows.nbytes
    out = np.asarray(asm.gather())
    np.testing.assert_array_equal(out, rows)


def test_chunk_assembler_incomplete_raises():
    asm = ChunkAssembler(2, 4, np.float32)
    asm.add(0, np.zeros(4, np.float32).tobytes())
    with pytest.raises(ValueError):
        asm.gather()


def test_scatter_coo_empty_and_dense():
    out = device.scatter_coo(np.empty(0, np.int64),
                             np.empty(0, np.float32), (8,))
    np.testing.assert_array_equal(np.asarray(out), np.zeros(8, np.float32))
    out = device.scatter_coo(np.array([1, 5]),
                             np.array([2.0, 3.0], np.float32), (6,))
    want = np.zeros(6, np.float32)
    want[[1, 5]] = [2.0, 3.0]
    np.testing.assert_array_equal(np.asarray(out), want)
    # flat indices land row-major in an N-D result of the asked shape
    out = device.scatter_coo(np.array([1, 5]),
                             np.array([2.0, 3.0], np.float32), (2, 3))
    np.testing.assert_array_equal(np.asarray(out), want.reshape(2, 3))


# ---------------------------------------------------------------------------
# staged decode pool
# ---------------------------------------------------------------------------

def test_staged_decode_matches_inline_decode():
    x = dense((32, 4, 16), "float32", seed=8)
    outs = {}
    for workers in (0, 2):
        io = ReadExecutor(max_workers=4, decode_workers=workers)
        store = make_store(io=io, compression="zlib+shuffle")
        store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2,
                  target_file_bytes=2048)
        outs[workers] = store.get("x")
        if workers:
            assert io.stats.decodes_offloaded > 0
        else:
            assert io.stats.decodes_offloaded == 0
        assert io.stats.decode_s > 0.0
        io.shutdown()
    np.testing.assert_array_equal(outs[0], outs[2])
    np.testing.assert_array_equal(outs[2], x)


def test_decode_stats_surface_in_io_stats():
    io = ReadExecutor(max_workers=4)
    store = make_store(io=io, compression="zlib+shuffle")
    store.put(dense((16, 8), "float32", seed=9), tensor_id="x", layout="ftsf",
              target_file_bytes=1024)
    store.get("x")
    s = store.io_stats()
    for key in ("decode_s", "decode_overlap_frac", "decodes_offloaded",
                "bytes_to_device", "deltas_reconstructed", "fetch_wait_s",
                "decode_queue_s", "spans"):
        assert key in s
    assert s["decode_s"] > 0.0
    assert 0.0 <= s["decode_overlap_frac"] <= 1.0


def test_unframed_bytes_skip_decode_stage():
    io = ReadExecutor(max_workers=2)
    obj = InMemoryObjectStore()
    obj.put("k", b"plain bytes")
    assert io.fetch(obj, "k") == b"plain bytes"
    assert io.stats.decodes_offloaded == 0
    assert io.stats.decode_s == 0.0


# ---------------------------------------------------------------------------
# virtual-clock compute charging
# ---------------------------------------------------------------------------

def test_charge_compute_overlaps_under_parallel_clock():
    lm = LatencyModel(rtt_s=0.0, bandwidth_bps=1e9, parallelism=4,
                      virtual_clock=True)
    lm.charge(1_000_000)               # 8 ms wire on one channel
    io_done = lm.io_elapsed_s
    lm.charge_compute(0.3, not_before=lm.thread_done_s())
    # decode rode the same thread after its fetch: makespan extends,
    # wire time does not
    assert lm.io_elapsed_s == pytest.approx(io_done)
    assert lm.elapsed_s == pytest.approx(io_done + 0.3)
    assert lm.compute_s == pytest.approx(0.3)


def test_charge_compute_serial_clock_adds_up():
    lm = LatencyModel(rtt_s=0.01, bandwidth_bps=1e9, parallelism=1,
                      virtual_clock=True)
    lm.charge(1000)
    wire = lm.elapsed_s
    lm.charge_compute(0.05)
    assert lm.elapsed_s == pytest.approx(wire + 0.05)
    assert lm.io_elapsed_s == pytest.approx(wire)


def test_charge_compute_reset():
    lm = LatencyModel(rtt_s=0.0, bandwidth_bps=1e9, parallelism=2,
                      virtual_clock=True)
    lm.charge(1000)
    lm.charge_compute(0.1)
    lm.reset()
    assert lm.compute_s == 0.0 and lm.io_elapsed_s == 0.0


# ---------------------------------------------------------------------------
# batched + streaming device reads
# ---------------------------------------------------------------------------

def test_read_many_device_matches_host():
    store = make_store()
    a = dense((8, 4, 4), "float32", seed=10)
    b = dense((6, 4, 4), "float32", seed=11)
    store.put(a, tensor_id="a", layout="ftsf", chunk_dims=2)
    store.put(b, tensor_id="b", layout="ftsf", chunk_dims=2)
    reqs = [("a", None), ("b", [(1, 5), None, None]), ("a", [(0, 3), None, None])]
    host = store.read_many(reqs)
    store.io.stats.reset()
    dev = store.read_many(reqs, device=True)
    for h, d in zip(host, dev):
        assert device.is_device_array(d)
        np.testing.assert_array_equal(np.asarray(d), h)
    assert store.io_stats()["bytes_to_device"] == sum(h.nbytes for h in host)


def test_stream_loader_device_batches():
    store = make_store()
    x = dense((12, 3, 4), "float32", seed=12)
    store.put(x, tensor_id="x", layout="ftsf", chunk_dims=2)
    loader = StreamLoader(store, "x", batch_size=4, epochs=1, seed=0,
                          device=True)
    seen = 0
    for b in loader:
        assert device.is_device_array(b["data"])
        assert np.asarray(b["data"]).shape == (4, 3, 4)
        seen += 1
    assert seen == 3
    assert store.io.stats.bytes_to_device >= x.nbytes
