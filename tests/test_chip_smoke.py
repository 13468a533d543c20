"""chip_smoke.py: its phases at a tiny size on the CPU, checked against
their references, and its refusal to run without a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dense_phase_tiny(smoke, tmp_path):
    res = smoke.phase_dense(str(tmp_path), seed=3, rows=6, read=5, side=16,
                            batch=4, shuffled=(4, 8, 16))
    assert res["path"] == "staged"
    assert res["device_bytes"] == 5 * 3 * 16 * 16
    assert res["host_staged_bytes"] == res["device_bytes"]
    assert res["shuffled_frames"] > 0


def test_sparse_phase_tiny(smoke, tmp_path):
    res = smoke.phase_sparse(str(tmp_path), seed=3, shape=(6, 4, 30, 40),
                             nnz_ratio=0.01, probe=(8, 64))
    assert res["path"] == "coo_scatter"
    assert [r["i"] for r in res["reads"]] == [0, 3, 5]
    # each X[i] lands whole in HBM: one float32 (4, 30, 40) slice
    assert all(r["device_bytes"] == 4 * 30 * 40 * 4 for r in res["reads"])
    assert res["nnz"] > 0 and res["probe_nnz"] == 5


def test_serve_phase_tiny(smoke, tmp_path, monkeypatch):
    # keep the launcher's compile cache out of this process's jax config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    res = smoke.phase_serve(str(tmp_path), seed=3, reduced=True, requests=2,
                            max_new=3, max_len=32)
    assert res["requests"] == 2 and res["tokens"] == 6
    assert res["stored_bytes"] > 0 and res["leaves"] > 0


def test_phase_mismatch_raises(smoke, tmp_path, monkeypatch):
    # a reference that disagrees with what the store returns must fail
    monkeypatch.setattr(smoke.SparseCOO, "to_dense",
                        lambda self: np.ones(self.shape, self.values.dtype))
    with pytest.raises(smoke.SmokeFailure, match="differs"):
        smoke.phase_sparse(str(tmp_path), seed=3, shape=(6, 4, 30, 40),
                           nnz_ratio=0.01, probe=(8, 64))


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
