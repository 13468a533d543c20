"""A cell of another shape comes from new files alone.

A stand-in kind, loop and pair of metric readers are registered under
names the benchmark does not have (``sys.modules``, as new files under
``chipbench/kinds``, ``chipbench/loops`` and ``chipbench/metrics`` would
be found); the configuration has no ``shape`` and the mix no ``slice``.
The cell is a tree of tensors loaded whole into device memory, a weight
tree in small. It runs through ``harness.run_cell`` on the CPU, its mix
checked by its own loop, and its readers get a counter from
``Built.counters`` and a span of the loop's own from the record.
"""

import json
import sys
import time
import types
from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax
import ml_dtypes
import numpy as np
import pytest

from chipbench import harness
from chipbench.kinds import Built
from chipbench.loops import Window

KIND, LOOP = "standin_tree", "standin_burst"
LOADS, LOAD_MS = "standin_loads", "standin_load_ms"
SPAN = "standin.load"


@dataclass
class TreeBuilt(Built):
    """What the stand-in kind hands its loop: a way to load the tree."""

    load: Callable[[], Dict[str, Any]] = None


def _tree(cfg, seed):
    rng = np.random.default_rng([int(seed), 9])
    return {leaf: rng.standard_normal(shape).astype(np.float32)
            for leaf, shape in cfg["leaves"].items()}


def _build(cfg, seed, root):
    from repro.core import DeltaTensorStore
    from repro.lake import LocalFSObjectStore

    store = DeltaTensorStore(LocalFSObjectStore(root), "tensors")
    tree = _tree(cfg, seed)
    for leaf, x in tree.items():
        store.put(x, tensor_id=f"{cfg['name']}.{leaf}")
    loads = {"n": 0}

    def load():
        loads["n"] += 1
        return {leaf: store.get_device(f"{cfg['name']}.{leaf}")
                for leaf in cfg["leaves"]}

    def control(t):
        return {k: v.astype(ml_dtypes.bfloat16).astype(v.dtype)
                for k, v in t.items()}

    return TreeBuilt(
        store=store, tensor_id=cfg["name"], shape=(),
        logical_bytes=sum(x.nbytes for x in tree.values()),
        reference=lambda spec: _tree(cfg, seed), control=control,
        kernel_bytes=lambda spec: 0,
        counters=lambda: {"tree_loads": loads["n"]}, load=load)


def _check(mix):
    if "loads_per_burst" not in mix:
        raise ValueError("burst mix: no 'loads_per_burst'")


def _warm(built, mix, seed):
    jax.block_until_ready(built.load())


def _run(built, mix, seed, seconds, mark):
    from repro.lake import spans

    w = Window()
    with mark():
        w.start = w.end = time.perf_counter()
        while w.end < w.start + seconds:
            for _ in range(int(mix["loads_per_burst"])):
                t0 = time.perf_counter()
                w.attempted += 1
                with spans.span(SPAN):
                    tree = jax.block_until_ready(built.load())
                w.end = time.perf_counter()
                w.latencies.append(w.end - t0)
                w.bytes += sum(int(x.nbytes) for x in tree.values())
                w.kernel_bytes.append(0)
                if len(w.kept) < int(mix["check"]):
                    w.kept.append((0, None, tree, None))
    return w


def _module(name, **fns):
    mod = types.ModuleType(name)
    for k, v in fns.items():
        setattr(mod, k, v)
    return mod


def _loads(rec):
    return rec["counters"].get("tree_loads")


def _load_ms(rec):
    row = (rec["spans"] or {}).get(SPAN)
    return None if row is None else 1e3 * row["total_s"] / row["count"]


@pytest.fixture
def room(monkeypatch, tmp_path):
    """A bench of one stand-in cell, its files in a checkout at
    ``tmp_path``."""
    monkeypatch.setattr(harness, "CHECKOUT", str(tmp_path))
    modules = {
        f"chipbench.kinds.{KIND}": _module("k", build=_build),
        f"chipbench.loops.{LOOP}": _module(
            "l", check=_check, warm=_warm, run=_run),
        f"chipbench.metrics.{LOADS}": _module("m", read=_loads),
        f"chipbench.metrics.{LOAD_MS}": _module("m", read=_load_ms)}
    for name, mod in modules.items():
        monkeypatch.setitem(sys.modules, name, mod)
    cfg = {"name": "standin-tree", "kind": KIND,
           "leaves": {"embed": [64, 32], "mlp.w1": [32, 96], "norm": [32]}}
    mix = {"loop": LOOP, "loads_per_burst": 3, "check": 2}
    (tmp_path / "configs").mkdir()
    (tmp_path / harness.TRAFFIC).mkdir(parents=True)
    (tmp_path / "configs" / "standin-tree.json").write_text(json.dumps(cfg))
    (tmp_path / harness.TRAFFIC / "bursts.json").write_text(json.dumps(mix))
    bench = {
        "configs": [{"name": "standin-tree",
                     "file": "configs/standin-tree.json"}],
        "workloads": [{"name": "standin-tree.bursts",
                       "config": "standin-tree", "traffic": "bursts"}],
        "end_to_end": [{"name": "read_GBps", "unit": "GB/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": LOADS, "unit": "count"},
                      {"name": LOAD_MS, "unit": "ms"}]}
    return bench, tmp_path


def _run_cell(bench, root, trace, **kw):
    return harness.run_cell(bench, "standin-tree.bursts", trace=trace,
                            seed=2**31 + 21, seconds=0.3,
                            started=time.perf_counter(),
                            device=jax.devices()[0],
                            peaks={"hbm_bytes_per_s": 819e9},
                            work=str(root / "work"), **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_a_tree_cell_runs_from_new_files_alone(room, trace):
    bench, root = room
    out = _run_cell(bench, root, trace)
    assert out["correct"], out["checks"]
    assert out["reads"]["checked"] == 2
    got = out["metrics"]
    if not trace:
        assert set(got) == {"read_GBps", "setup_s"}
        return
    # the warm-up's load is not in the window's delta
    assert got[LOADS]["value"] == out["reads"]["completed"] > 0
    assert got[LOAD_MS]["value"] > 0
    assert got[LOAD_MS]["unit"] == "ms"


def test_a_tree_cell_control_is_not_correct(room):
    bench, root = room
    out = _run_cell(bench, root, False, control=True)
    assert out["program_checks"]["mismatched_elements"]["value"] == 0
    assert not out["correct"]
    assert out["checks"]["mismatched_elements"]["value"] > 0


def test_a_mix_is_checked_by_its_own_loop(room):
    bench, root = room
    path = root / harness.TRAFFIC / "bursts.json"
    path.write_text(json.dumps({"loop": LOOP, "check": 2}))
    with pytest.raises(ValueError, match="loads_per_burst"):
        harness.cell(bench, "standin-tree.bursts", False)
    path.write_text(json.dumps({"loads_per_burst": 3, "check": 2}))
    with pytest.raises(ValueError, match="loop"):
        harness.cell(bench, "standin-tree.bursts", False)
