"""Tiny configurations and mixes for running the benchmark's harness on
the CPU: the real files' keys, at sizes a test run can hold."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.fixture
def bench():
    return load("BENCHMARK.json")


@pytest.fixture
def tiny_dense():
    cfg = load("chipbench/configs/paper-dense-ffhq.json")
    cfg.update(rows=8, row_shape=[3, 32, 32], file_rows=2)
    mix = {"loop": "closed", "clients": 3, "warmup": 1, "check_per_client": 2,
           "slice": [{"start": [0, 4], "length": 4}]}
    return cfg, mix


@pytest.fixture
def tiny_sparse():
    cfg = load("chipbench/configs/paper-sparse-uber.json")
    cfg.update(shape=[14, 24, 60, 80], nnz=3000)
    mix = {"loop": "closed", "clients": 2, "warmup": "each_start",
           "check_per_client": 2,
           "slice": [{"start": [7, 13], "length": 1}]}
    return cfg, mix
