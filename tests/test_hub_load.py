"""Weight trees into device memory: ``ModelRepo.load(device=True)`` and
``Gateway.load_model(device=True)`` of a base model and its delta-stored
variants, each compared bit for bit with a plain numpy tree."""

import threading

import jax
import ml_dtypes
import numpy as np
import pytest

from repro.core import DeltaTensorStore
from repro.lake import (InMemoryObjectStore, ReadExecutor, encode_frame,
                        parse_compression, spans)
from repro.lake.compression import DeltaBase
from repro.serve import Gateway, ServeEngine

BF16 = ml_dtypes.bfloat16


def _base(seed=0):
    """A small bfloat16 tree in the shape of a stacked transformer's."""
    rng = np.random.default_rng(seed)

    def leaf(*shape):
        return (0.02 * rng.standard_normal(shape)).astype(BF16)

    return {"embed": leaf(96, 32), "final_norm": {"scale": leaf(32)},
            "blocks": {"attn": {"wq": leaf(2, 32, 32), "wo": leaf(2, 32, 32)},
                       "mlp": {"w_up": leaf(2, 32, 64)}}}


def _lora(base, seed=1, rank=4):
    """W + B @ A on the attention leaves, in float32, rounded to bfloat16."""
    rng = np.random.default_rng(seed)
    out = jax.tree_util.tree_map(lambda x: x, base)
    for name in ("wq", "wo"):
        w = base["blocks"]["attn"][name]
        b = 0.01 * rng.standard_normal((2, 32, rank), dtype=np.float32)
        a = 0.01 * rng.standard_normal((2, rank, 32), dtype=np.float32)
        out["blocks"]["attn"][name] = (w.astype(np.float32)
                                       + b @ a).astype(BF16)
    return out


def _full(base, seed=2):
    """Every leaf times 1 + 0.01 N(0, 1), rounded to bfloat16."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda x: (x.astype(np.float32) * (1 + 0.01 * rng.standard_normal(
            x.shape, dtype=np.float32))).astype(BF16), base)


def _same_bits(got, want):
    g, g_def = jax.tree_util.tree_flatten(got)
    w, w_def = jax.tree_util.tree_flatten(want)
    assert g_def == w_def
    for a, b in zip(g, w):
        a = np.asarray(a)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (a.view(np.uint16) == b.view(np.uint16)).all()


def _template(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


@pytest.fixture
def hub():
    """A store holding a base and two delta-stored variants; their trees."""
    store = DeltaTensorStore(InMemoryObjectStore(), "weights",
                             io=ReadExecutor(max_workers=4),
                             compression="zlib")
    base = _base()
    trees = {"m": base, "m~lora": _lora(base), "m~full": _full(base)}
    with store.models("m") as repo:
        repo.save(base)
        for name in ("lora", "full"):
            with repo.open_variant(name) as var:
                var.save(trees[f"m~{name}"])
    return store, trees


@pytest.mark.parametrize("prefix", ["m", "m~lora", "m~full"])
def test_device_load_is_bit_identical_and_on_device(hub, prefix):
    store, trees = hub
    with store.models(prefix) as repo:
        got = repo.load(_template(trees[prefix]), device=True)
        host = repo.load(_template(trees[prefix]))
    for leaf in jax.tree_util.tree_leaves(got):
        assert isinstance(leaf, jax.Array)
        assert leaf.devices() == {jax.devices()[0]}
    for leaf in jax.tree_util.tree_leaves(host):
        assert isinstance(leaf, np.ndarray)
    _same_bits(got, trees[prefix])
    _same_bits(host, trees[prefix])


@pytest.mark.parametrize("device", [False, True])
def test_a_stored_dtype_other_than_the_template_raises(hub, device):
    store, trees = hub
    wrong = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, np.float32), trees["m"])
    with store.models("m") as repo, pytest.raises(TypeError, match="float32"):
        repo.load(wrong, device=device)


def test_concurrent_device_loads_share_one_flight(hub):
    store, trees = hub
    release = threading.Event()
    with Gateway(store, max_inflight=1) as gw:
        gw.submit("blocker", release.wait)  # the flight waits in the queue
        futs = [gw.load_model(f"t{i}", "m~full", _template(trees["m~full"]),
                              device=True) for i in range(4)]
        release.set()
        got = [f.result(30) for f in futs]
        st = gw.stats()
    assert len({id(f) for f in futs}) == 1
    assert st["flights_created"] == 1 and st["coalesced_hits"] == 3
    assert st["loads_run"] == 1
    for tree in got:
        assert tree is got[0]
        _same_bits(tree, trees["m~full"])


def test_a_host_and_a_device_load_do_not_coalesce(hub):
    store, trees = hub
    release = threading.Event()
    with Gateway(store, max_inflight=1) as gw:
        gw.submit("blocker", release.wait)  # both loads wait in the queue
        on_host = gw.load_model("a", "m", _template(trees["m"]))
        on_dev = gw.load_model("b", "m", _template(trees["m"]), device=True)
        assert on_host is not on_dev
        release.set()
        host, dev = on_host.result(30), on_dev.result(30)
        st = gw.stats()
    assert st["flights_created"] == 2 and st["coalesced_hits"] == 0
    assert st["loads_run"] == 2
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(host))
    assert all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(dev))
    _same_bits(host, trees["m"])
    _same_bits(dev, trees["m"])


def test_the_gateway_counts_queue_wait_and_loads(hub):
    store, trees = hub
    release = threading.Event()
    with Gateway(store, max_inflight=1) as gw:
        gw.submit("blocker", release.wait)
        fut = gw.load_model("a", "m~lora", _template(trees["m~lora"]),
                            device=True)
        assert gw.stats()["loads_run"] == 0  # queued behind the blocker
        threading.Timer(0.2, release.set).start()
        fut.result(30)
        st = gw.stats()
    assert st["loads_run"] == 1
    # the load waited for the blocker: about 0.2 s between submission and
    # dispatch (the blocker's own wait is about 0)
    assert 0.15 < st["queue_wait_s"] < 10


def test_undelta_span_and_base_bytes_count_what_ran(hub):
    store, trees = hub
    store.io.cache.clear()
    before = store.io_stats()
    decoded = store.io.stats.frame_bytes_decoded
    spans.reset()
    spans.enable(True)
    try:
        with Gateway(store) as gw:
            gw.load_model("a", "m~full", _template(trees["m~full"]),
                          device=True).result(30)
            gw.load_model("a", "m", _template(trees["m"]),
                          device=True).result(30)
        table = spans.snapshot()
    finally:
        spans.enable(False)
        spans.reset()
    after = store.io_stats()
    deltas = after["deltas_reconstructed"] - before["deltas_reconstructed"]
    base_bytes = after["delta_base_bytes"] - before["delta_base_bytes"]
    # every chunk file of the full variant is a delta of the base's
    n_leaves = len(jax.tree_util.tree_leaves(trees["m"]))
    assert deltas >= n_leaves
    assert table["store.undelta"]["count"] == deltas
    assert table["store.undelta"]["self_s"] > 0
    # a base byte XORed for each byte of the variant's part files, which
    # hold the tree's chunks and their metadata
    tree_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(trees["m"]))
    assert tree_bytes <= base_bytes
    assert base_bytes <= store.io.stats.frame_bytes_decoded - decoded
    assert table["gateway.load"]["count"] == 2


def test_a_base_decoded_inline_has_a_decode_span_of_its_own():
    # with no block cache the delta's base is fetched and decoded inline,
    # inside store.undelta: that decode is a store.decode of its own, so
    # it leaves undelta's self time
    base = np.arange(1 << 14, dtype=np.float32).tobytes()
    raw = bytearray(base)
    raw[:8] = bytes(8)
    spec = parse_compression("zlib+shuffle")
    obj = InMemoryObjectStore()
    obj.put("t/base", encode_frame(base, spec, itemsize=4)[0])
    obj.put("t/var", encode_frame(bytes(raw), spec, itemsize=4,
                                  delta_base=DeltaBase("t/base", base))[0])
    io = ReadExecutor(cache_bytes=0)
    spans.reset()
    spans.enable(True)
    try:
        assert io.fetch(obj, "t/var") == bytes(raw)
        table = spans.snapshot()
    finally:
        spans.enable(False)
        spans.reset()
        io.shutdown()
    assert table["store.undelta"]["count"] == 1
    assert table["store.fetch"]["count"] == 2
    # the variant's decode and, nested in its undelta, the base's
    assert table["store.decode"]["count"] == 2
    assert io.stats.delta_base_bytes == len(base)


def test_from_repo_serves_device_weights():
    import repro.configs  # noqa: F401  (registers the architectures)
    from repro.models import transformer
    from repro.models.config import get_arch

    cfg = get_arch("phi3-mini-3.8b").reduced()
    key = jax.random.PRNGKey(0)
    params = jax.jit(transformer.init_params, static_argnums=0)(cfg, key)
    store = DeltaTensorStore(InMemoryObjectStore(), "weights")
    with store.models("w") as repo:
        repo.save(params)
    with ServeEngine.from_repo(store.models("w"), _template(params), cfg,
                               n_slots=1, max_len=8) as eng:
        for got, want in zip(jax.tree_util.tree_leaves(eng.params),
                             jax.tree_util.tree_leaves(params)):
            assert isinstance(got, jax.Array)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
