"""The comparison that decides ``correct`` fails what it must.

At a size a test run can hold, on the CPU: the control (the reference in
the nearest precision below the configuration's, in the program's place)
and each fault a read can have, planted in the program where the answer is
produced, all come out not correct. ``chipbench/control.py`` runs the
control on the chip at the cells' own sizes.
"""

import time

import jax
import numpy as np
import pytest

from chipbench import check, harness
from repro.lake import device as lake_device


def _run(cfg, mix, tmp_path, **kw):
    return harness.run(cfg, mix, [], seed=2**32 + 3, seconds=0.3,
                       trace=False, started=time.perf_counter(),
                       device=jax.devices()[0], work=str(tmp_path), **kw)


@pytest.mark.parametrize("which", ["tiny_dense", "tiny_sparse"])
def test_control_is_not_correct(request, tmp_path, which):
    cfg, mix = request.getfixturevalue(which)
    out = _run(cfg, mix, tmp_path, control=True)
    assert not out["correct"]
    assert out["checks"]["mismatched_elements"]["value"] > 0


def _altered_gather(self):
    buf = self._buf.copy()
    buf.reshape(-1).view(np.uint8)[0] ^= 1
    return lake_device.to_device(buf)


def _half_gather(self):
    buf = self._buf.copy()
    buf[len(buf) // 2:] = 0
    return lake_device.to_device(buf)


_scatter = lake_device.scatter_coo


def _altered_scatter(flat_idx, values, size, **kw):
    values = values.copy()
    values[:1] *= 2
    return _scatter(flat_idx, values, size, **kw)


def _half_scatter(flat_idx, values, size, **kw):
    keep = len(values) // 2
    return _scatter(flat_idx[:keep], values[:keep], size, **kw)


@pytest.mark.parametrize("which, target, fault", [
    ("tiny_dense", "ChunkAssembler.gather", _altered_gather),
    ("tiny_dense", "ChunkAssembler.gather", _half_gather),
    ("tiny_sparse", "scatter_coo", _altered_scatter),
    ("tiny_sparse", "scatter_coo", _half_scatter),
], ids=["dense-answer-altered", "dense-half-left-out",
        "sparse-answer-altered", "sparse-half-left-out"])
def test_fault_is_not_correct(request, monkeypatch, tmp_path, which, target,
                              fault):
    cfg, mix = request.getfixturevalue(which)
    if "." in target:
        cls, name = target.split(".")
        monkeypatch.setattr(getattr(lake_device, cls), name, fault)
    else:
        monkeypatch.setattr(lake_device, target, fault)
    out = _run(cfg, mix, tmp_path)
    assert not out["correct"]
    assert out["checks"]["mismatched_elements"]["value"] > 0


def test_mismatched_counts_bits_shape_and_dtype():
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    b = a.copy()
    assert check.mismatched(a, b) == 0
    b[1, 2] = np.nextafter(b[1, 2], np.float32(9))
    assert check.mismatched(a, b) == 1
    n = np.array([np.nan], np.float32)
    assert check.mismatched(n, n.copy()) == 0
    assert check.mismatched(a, a.astype(np.float64)) == 6
    assert check.mismatched(a.reshape(3, 2), a) == 6
    assert check.passed({"x": {"value": 0, "limit": 0}})
    assert not check.passed({"x": {"value": 1, "limit": 0}})


def test_mismatched_tree_sums_leaves_and_counts_a_changed_structure():
    a = {"w": np.zeros((2, 3), np.float32), "b": np.ones(4, np.float32)}
    b = {k: v.copy() for k, v in a.items()}
    assert check.mismatched_tree(a, b) == 0
    b["b"][0] = 2
    b["w"][1, 1] = 1
    assert check.mismatched_tree(a, b) == 2
    assert check.mismatched_tree({"w": a["w"]}, a) == 10
    assert check.mismatched_tree(a["w"], a["w"].copy()) == 0
    assert check.mismatched_tree(a["b"], b["b"]) == 1
