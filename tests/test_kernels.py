"""Pallas kernels (interpret mode) vs pure-jnp oracles, swept over shapes/dtypes."""

import jax.numpy as jnp
import numpy as np
import pytest
from ._hypothesis_compat import given, settings, st  # skips property tests if hypothesis is missing

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)

SHAPES_BLOCKS = [
    ((16, 128), (8, 128)),
    ((32, 256), (8, 128)),
    ((24, 384), (8, 128)),
    ((64, 128), (16, 64)),
    ((9, 130), (4, 64)),     # ragged: wrapper pads
]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]


def _mk(shape, dtype, seed=0):
    x = np.random.default_rng(seed).standard_normal(shape)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray((x * 10).astype(np.int32), dtype=dtype)
    return jnp.asarray(x, dtype=dtype)


@pytest.mark.parametrize("shape,bs", SHAPES_BLOCKS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_block_gather_matches_ref(shape, bs, dtype):
    x = _mk(shape, dtype, seed=1)
    gh = -(-shape[0] // bs[0])
    gw = -(-shape[1] // bs[1])
    n_blocks = gh * gw
    k = min(n_blocks, 5)
    ids = jnp.asarray(RNG.choice(n_blocks + 1, size=k, replace=False), jnp.int32)
    got = ops.block_gather(x, ids, bs, use_pallas=True)
    want = ops.block_gather(x, ids, bs, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), rtol=0, atol=0)


@pytest.mark.parametrize("shape,bs", SHAPES_BLOCKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_scatter_matches_ref(shape, bs, dtype):
    base = _mk(shape, dtype, seed=2)
    gh = -(-shape[0] // bs[0])
    gw = -(-shape[1] // bs[1])
    n_blocks = gh * gw
    k = min(n_blocks, 4)
    ids = jnp.asarray(RNG.choice(n_blocks + 1, size=k, replace=False), jnp.int32)
    blocks = _mk((k,) + bs, dtype, seed=3)
    got = ops.block_scatter(base, ids, blocks, use_pallas=True)
    want = ops.block_scatter(base, ids, blocks, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), rtol=0, atol=0)


@pytest.mark.parametrize("g,b", [(8, 128), (16, 64), (3, 256), (40, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_norms_matches_ref(g, b, dtype):
    bv = _mk((g, b), dtype, seed=4)
    got = ops.block_norms(bv, use_pallas=True)
    want = ref.block_norms(bv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("size,k", [(512, 17), (1024, 100), (640, 1), (130, 9)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coo_scatter_matches_ref(size, k, dtype):
    idx = jnp.asarray(RNG.choice(size, size=k, replace=False), jnp.int32)
    vals = _mk((k,), dtype, seed=5)
    got = ops.coo_scatter(idx, vals, size, use_pallas=True)
    want = ref.coo_scatter(idx, vals, size)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_coo_scatter_padding_indices_drop():
    idx = jnp.asarray([5, 700, 1000], jnp.int32)  # 700/1000 out of range
    vals = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    out = ops.coo_scatter(idx, vals, 512, use_pallas=True)
    assert float(out[5]) == 1.0
    assert float(jnp.sum(out)) == 1.0


def test_block_topk_matches_ref():
    x = _mk((32, 256), jnp.float32, seed=6)
    ids_p, blk_p = ops.block_topk(x, (8, 128), k=3, use_pallas=True)
    ids_r, blk_r = ref.block_topk(x, (8, 128), k=3)
    np.testing.assert_array_equal(np.sort(np.asarray(ids_p)), np.sort(np.asarray(ids_r)))
    np.testing.assert_allclose(np.asarray(blk_p)[np.argsort(np.asarray(ids_p))],
                               np.asarray(blk_r)[np.argsort(np.asarray(ids_r))])


@settings(max_examples=15, deadline=None)
@given(data=st.data())
def test_property_gather_scatter_inverse(data):
    """scatter(zeros, ids, gather(x, ids)) keeps exactly the chosen tiles."""
    gh = data.draw(st.integers(1, 4))
    gw = data.draw(st.integers(1, 3))
    bs = (8, 128)
    shape = (gh * bs[0], gw * bs[1])
    x = _mk(shape, jnp.float32, seed=data.draw(st.integers(0, 99)))
    n_blocks = gh * gw
    k = data.draw(st.integers(1, n_blocks))
    ids = jnp.asarray(np.random.default_rng(k).choice(n_blocks, size=k, replace=False),
                      jnp.int32)
    tiles = ops.block_gather(x, ids, bs, use_pallas=True)
    back = ops.block_scatter(jnp.zeros_like(x), ids, tiles, use_pallas=True)
    mask = np.zeros(shape, bool)
    for i in np.asarray(ids):
        r, c = divmod(int(i), gw)
        mask[r * bs[0]:(r + 1) * bs[0], c * bs[1]:(c + 1) * bs[1]] = True
    np.testing.assert_array_equal(np.asarray(back)[mask], np.asarray(x)[mask])
    assert (np.asarray(back)[~mask] == 0).all()
