"""The benchmark's own generators: exact counts, the same answer per seed."""

import numpy as np

from chipbench.data import ffhq, uber

from .conftest import load


def test_uber_full_size_has_the_configured_distinct_count():
    cfg = load("chipbench/configs/paper-sparse-uber.json")
    shape, nnz = tuple(cfg["shape"]), cfg["nnz"]
    assert nnz == 3_266_789 == round(0.00038 * int(np.prod(shape)))
    idx, val = uber.generate(2**31 + 11, shape, nnz, cfg["structure"])
    assert idx.shape == (nnz, 4) and val.dtype == np.float32
    flat = np.ravel_multi_index(idx.T, shape)
    assert len(np.unique(flat)) == nnz
    assert (val > 0).all()
    per_day = np.bincount(idx[:, 0], minlength=shape[0])
    want = uber.day_counts(shape[0], nnz, cfg["structure"]["weekly"])
    np.testing.assert_array_equal(per_day, want)
    # every day slice stays under the kernel's largest compiled K
    assert want.max() < 22_280
    # shares of a day sum to one; their mantissas are not bfloat16's
    assert abs(val[idx[:, 0] == 0].astype(np.float64).sum() - 1) < 1e-4
    assert (val.view(np.uint32) & 0xFFFF).any()


def test_uber_is_deterministic_per_seed_and_moves_with_it():
    cfg = load("chipbench/configs/paper-sparse-uber.json")
    shape, p = (14, 24, 300, 400), cfg["structure"]
    a = uber.generate(5, shape, 40_000, p)
    b = uber.generate(5, shape, 40_000, p)
    c = uber.generate(6, shape, 40_000, p)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    np.testing.assert_array_equal(np.bincount(a[0][:, 0]),
                                  np.bincount(c[0][:, 0]))


def test_uber_dense_slice_is_a_plain_scatter():
    idx = np.array([[0, 1, 2], [1, 0, 0], [1, 2, 3], [2, 2, 2]])
    val = np.array([1.5, 2.5, 3.5, 4.5], np.float32)
    out = uber.dense_slice(idx, val, (3, 3, 4), [(1, 2)])
    want = np.zeros((1, 3, 4), np.float32)
    want[0, 0, 0], want[0, 2, 3] = 2.5, 3.5
    np.testing.assert_array_equal(out, want)


def test_day_counts_sum_exactly():
    c = uber.day_counts(183, 3_266_789, [1, 2, 3])
    assert c.sum() == 3_266_789 and c.min() > 0


def test_ffhq_rows_do_not_depend_on_the_batch():
    whole = ffhq.rows(2**33 + 1, 0, 6, (3, 40, 48))
    part = ffhq.rows(2**33 + 1, 2, 3, (3, 40, 48))
    np.testing.assert_array_equal(whole[2:5], part)
    assert whole.dtype == np.uint8 and whole.shape == (6, 3, 40, 48)
    assert not np.array_equal(whole[0], ffhq.rows(2**33 + 2, 0, 1,
                                                  (3, 40, 48))[0])
    # 8x8 blocks plus a small gradient and noise: neighbours stay close
    d = np.abs(np.diff(whole[0, 0, :8, :8].astype(int), axis=1))
    assert d.max() <= 5
