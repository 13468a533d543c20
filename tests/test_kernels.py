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


def _coo_dense(idx, vals, size):
    """numpy oracle of a COO scatter-add: pairs applied one at a time in
    their order (each add rounded to the dtype), negative indices counted
    from the end, out-of-range ones dropped."""
    idx = np.asarray(idx, np.int64)
    vals = np.asarray(vals)
    keep = (idx >= -size) & (idx < size)
    out = np.zeros(size, vals.dtype)
    np.add.at(out, idx[keep], vals[keep])
    return out


def _assert_coo_exact(idx, vals, shape):
    size = int(np.prod(shape))
    got = np.asarray(ops.coo_scatter(idx, vals, shape))
    assert got.dtype == np.asarray(vals).dtype and got.shape == shape
    want = _coo_dense(idx, vals, size).reshape(shape)
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    flat = np.asarray(ref.coo_scatter(idx, vals, size)).reshape(shape)
    np.testing.assert_array_equal(got.view(np.uint8), flat.view(np.uint8))


@pytest.mark.parametrize("size,k", [(512, 17), (1024, 100), (640, 1), (130, 9),
                                    (4099, 300),       # not a multiple of 128 or 1024
                                    (65536, 30000)])   # K far above an Uber day's 20,002
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coo_scatter_matches_ref(size, k, dtype):
    # distinct indices in random, unsorted order
    idx = jnp.asarray(RNG.choice(size, size=k, replace=False), jnp.int32)
    vals = _mk((k,), dtype, seed=5)
    _assert_coo_exact(idx, vals, (size,))


def _coo_case(name, rng):
    """(idx, values as float64, shape) for one structure of COO pairs."""
    if name == "padding":
        # coo_encode pads with index == size; beyond it and below -size drop
        # too, and -1 counts from the end as in jnp indexing
        idx = np.array([5, 512, 700, 1000, -1, -513, 2**31 - 1, 511])
        return idx, rng.standard_normal(len(idx)), (512,)
    if name == "duplicates":
        # 40 pairs on 7 indices: each index accumulates its pairs in order
        idx = rng.integers(100, 107, 40)
        return idx, rng.standard_normal(40), (1000,)
    if name == "hub_and_empty_tiles":
        # one hot region of 256 cells holds 5000 pairs, a few far ones
        # hit other tiles, and most tiles of the buffer stay empty
        idx = np.concatenate([rng.integers(20_000, 20_256, 5000),
                              rng.choice(1 << 18, 50, replace=False)])
        return rng.permutation(idx), rng.standard_normal(len(idx)), (1 << 18,)
    if name == "descending":
        idx = np.arange(5000)[::-1] * 7
        return idx, rng.standard_normal(len(idx)), (35_003,)
    if name == "specials":
        # inf, -inf, NaN and -0.0 keep their bits; other cells stay +0.0
        idx = np.array([0, 1, 2, 3, 127, 128])
        vals = np.array([np.inf, -np.inf, np.nan, -0.0, 1e-30, -2.5])
        return idx, vals, (129,)
    if name == "nd_slice":
        # an X[i] of a 4-D tensor, ragged trailing dims, padded with size:
        # flat indices land row-major in the N-D result
        shape = (1, 6, 13, 17)
        size = int(np.prod(shape))
        idx = np.concatenate([rng.choice(size, 400, replace=False),
                              [size, size, -1, -size - 1, 5, 5]])
        return idx, rng.standard_normal(len(idx)), shape
    raise KeyError(name)


@pytest.mark.parametrize("case", ["padding", "duplicates", "hub_and_empty_tiles",
                                  "descending", "specials", "nd_slice"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coo_scatter_padding_indices_drop(case, dtype):
    idx, vals, shape = _coo_case(case, np.random.default_rng(11))
    _assert_coo_exact(jnp.asarray(idx, jnp.int32), jnp.asarray(vals, dtype), shape)


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
